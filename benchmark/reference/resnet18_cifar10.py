"""Plain reference of the ``resnet18_cifar10`` configuration:
torchvision.models.resnet18 (He et al., arXiv:1512.03385) with a 10-way
head on 32x32 inputs — 7x7/2 stem, BatchNorm2d, ReLU, 3x3/2 max-pooling
with padding 1, four stages of two BasicBlocks (3x3 conv, BN, ReLU, 3x3
conv, BN, add the shortcut, ReLU; the shortcut is a 1x1/stride conv and
BN where the shape changes), the mean over positions, one linear layer —
in float32.  Parameter names are the program's (``layer<s>.block<b>``),
so the same seeded weights feed both.
"""
from __future__ import annotations

from . import common


def _bn(x, p, st):
    return common.batch_norm_train(x, p["scale"], p["bias"], st["mean"],
                                   st["var"])


def forward(config: dict):
    stages = config["stages"]
    blocks = config["blocks_per_stage"]

    def apply(params, stats, x):
        new_stats = {}
        x = common.conv(x, params["conv1"]["kernel"], 2, 3)
        x, new_stats["bn1"] = _bn(x, params["bn1"], stats["bn1"])
        x = common.max_pool(x * (x > 0), 3, 2, 1)
        for si, (_, stride) in enumerate(stages, start=1):
            for bi in range(blocks):
                name = f"layer{si}.block{bi}"
                blk, bst, ns = params[name], stats[name], {}
                s = stride if bi == 0 else 1
                y = common.conv(x, blk["conv1"]["kernel"], s, 1)
                y, ns["bn1"] = _bn(y, blk["bn1"], bst["bn1"])
                y = common.conv(y * (y > 0), blk["conv2"]["kernel"], 1, 1)
                y, ns["bn2"] = _bn(y, blk["bn2"], bst["bn2"])
                if "downsample" in blk:
                    x = common.conv(x, blk["downsample"]["conv"]["kernel"],
                                    s, 0)
                    x, ns["downsample_bn"] = _bn(
                        x, blk["downsample"]["bn"], bst["downsample_bn"])
                x = y + x
                x = x * (x > 0)
                new_stats[name] = ns
        x = x.mean(axis=(1, 2))
        return x @ params["fc"]["weight"] + params["fc"]["bias"], new_stats

    return apply


def layer_shapes(config: dict) -> list:
    """The configuration's layers for ``flops.py``, per sample."""
    h, w, c_in = config["input"]

    def out(n, k, s, p):
        return (n + 2 * p - k) // s + 1

    h, w = out(h, 7, 2, 3), out(w, 7, 2, 3)
    layers = [("conv", 7, 7, c_in, config["stem_width"], h, w, False)]
    h, w = out(h, 3, 2, 1), out(w, 3, 2, 1)
    c_in = config["stem_width"]
    for width, stride in config["stages"]:
        for bi in range(config["blocks_per_stage"]):
            s = stride if bi == 0 else 1
            h2, w2 = out(h, 3, s, 1), out(w, 3, s, 1)
            layers.append(("conv", 3, 3, c_in, width, h2, w2, True))
            layers.append(("conv", 3, 3, width, width, h2, w2, True))
            if s != 1 or c_in != width:
                layers.append(("conv", 1, 1, c_in, width, h2, w2, True))
            h, w, c_in = h2, w2, width
    layers.append(("linear", c_in, config["num_classes"], True))
    return layers
