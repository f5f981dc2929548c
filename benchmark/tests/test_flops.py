"""``flops.py`` against counts made by hand from the layer shapes."""
import json
import os

import pytest

from benchmark import flops
from benchmark.reference import resnet18_cifar10, vgg_cifar10
from conftest import ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_vgg_hand_count():
    # 3x3 convolutions, MACs a sample, written out: c_in*c_out*9*H*W.
    macs = [3 * 64 * 9 * 1024, 64 * 128 * 9 * 1024,          # 32x32
            128 * 256 * 9 * 256, 256 * 256 * 9 * 256,        # 16x16
            256 * 512 * 9 * 64, 512 * 512 * 9 * 64,          # 8x8
            512 * 512 * 9 * 16, 512 * 512 * 9 * 16,          # 4x4
            512 * 10]                                        # classifier
    forward = 2 * sum(macs)
    # Backward: weight gradient everywhere, input gradient everywhere but
    # in the first convolution, whose input is the data.
    train = 3 * forward - 2 * macs[0]
    layers = vgg_cifar10.layer_shapes(_config("vgg_cifar10"))
    assert flops.train_flops_per_sample(layers) == train
    assert train == pytest.approx(3.63e9, rel=2e-3)  # "3.6 GFLOP a sample"
    assert flops.conv_train_flops_per_sample(layers) == train - 6 * macs[-1]


def test_resnet18_hand_count():
    def block(c_in, c, hw, down):
        m = [c_in * c * 9 * hw, c * c * 9 * hw]
        return m + ([c_in * c * hw] if down else [])
    macs = [3 * 64 * 49 * 256]                                # 7x7/2: 16x16
    macs += block(64, 64, 64, False) + block(64, 64, 64, False)       # 8x8
    macs += block(64, 128, 16, True) + block(128, 128, 16, False)     # 4x4
    macs += block(128, 256, 4, True) + block(256, 256, 4, False)      # 2x2
    macs += block(256, 512, 1, True) + block(512, 512, 1, False)      # 1x1
    macs += [512 * 10]
    train = 3 * 2 * sum(macs) - 2 * macs[0]
    layers = resnet18_cifar10.layer_shapes(_config("resnet18_cifar10"))
    assert len(layers) == 21 and len(macs) == 21
    assert flops.train_flops_per_sample(layers) == train
    assert train == pytest.approx(0.217e9, rel=5e-3)


def test_mfu_and_peaks():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = flops.peak_for(peaks, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    # 30,000 samples/s of 3.63 GFLOP on 197 TFLOP/s.
    assert flops.mfu_pct(30000, 3.63e9, 197e12) == pytest.approx(55.28, abs=0.01)
    with pytest.raises(KeyError):
        flops.peak_for(peaks, "TPU v9")
    with pytest.raises(KeyError):
        flops.peak_for(peaks, "_source")
