"""Plain reference of the ``vgg_cifar10`` configuration: the VGG of the
reference scripts (singlegpu.py:47-82) — 3x3 convolutions without bias,
BatchNorm2d, ReLU, 2x2 max-pooling at each "M", the mean over the last
2x2 positions, one linear layer — in float32.  Layer names follow the
scripts' ``add()`` helper (``conv0``/``bn0``, ...), which is also how the
program's parameter tree is keyed, so the same seeded weights feed both.
"""
from __future__ import annotations

from . import common


def forward(config: dict):
    arch = config["arch"]

    def apply(params, stats, x):
        new_stats, i = {}, 0
        for a in arch:
            if a == "M":
                x = common.max_pool(x, 2, 2, 0)
                continue
            x = common.conv(x, params["backbone"][f"conv{i}"]["kernel"], 1, 1)
            bn = params["backbone"][f"bn{i}"]
            x, new_stats[f"bn{i}"] = common.batch_norm_train(
                x, bn["scale"], bn["bias"], stats[f"bn{i}"]["mean"],
                stats[f"bn{i}"]["var"])
            x = x * (x > 0)
            i += 1
        x = x.mean(axis=(1, 2))
        cls = params["classifier"]
        return x @ cls["weight"] + cls["bias"], new_stats

    return apply


def layer_shapes(config: dict) -> list:
    """The configuration's layers for ``flops.py``, per sample."""
    h, w, c_in = config["input"]
    layers, first = [], True
    for a in config["arch"]:
        if a == "M":
            h, w = h // 2, w // 2
            continue
        layers.append(("conv", 3, 3, c_in, a, h, w, not first))
        c_in, first = a, False
    layers.append(("linear", c_in, config["num_classes"], True))
    return layers
