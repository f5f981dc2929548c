"""Operations and bytes a configuration's training step needs, from its
layer shapes alone (the yardstick: no cost model of the program is read).

A configuration's reference module (``reference/<name>.py``) lists its
layers as plain tuples, per sample:

    ("conv", kh, kw, c_in, c_out, h_out, w_out, input_grad)
    ("linear", d_in, d_out, input_grad)

``input_grad`` is False for a layer fed by the data, whose backward pass
needs no gradient with respect to its input.  One multiply-accumulate
counts as two operations.  Forward is one pass over the MACs; backward is
one more for the weight gradient and, where ``input_grad``, one for the
input gradient.  Nothing recomputed is counted, and neither are the
elementwise layers (BN, ReLU, pooling, the update): they are bound by
bytes, not operations, and are under 1% of the count for these models.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def layer_macs(layer: Tuple) -> int:
    kind = layer[0]
    if kind == "conv":
        _, kh, kw, c_in, c_out, h_out, w_out, _ = layer
        return kh * kw * c_in * c_out * h_out * w_out
    if kind == "linear":
        _, d_in, d_out, _ = layer
        return d_in * d_out
    raise ValueError(f"unknown layer kind {kind!r}")


def train_flops_per_sample(layers: Iterable[Tuple],
                           kinds: Tuple[str, ...] = ("conv", "linear")
                           ) -> int:
    """Forward and backward operations for one sample, over the layers of
    the given kinds."""
    total = 0
    for la in layers:
        if la[0] in kinds:
            passes = 3 if la[-1] else 2
            total += 2 * layer_macs(la) * passes
    return total


def conv_train_flops_per_sample(layers: Iterable[Tuple]) -> int:
    """The convolutions' part of :func:`train_flops_per_sample` — the
    numerator of ``conv_roofline_pct``."""
    return train_flops_per_sample(layers, kinds=("conv",))


def mfu_pct(samples_per_s_per_chip: float, flops_per_sample: float,
            peak_flops_per_s: float) -> float:
    return 100.0 * samples_per_s_per_chip * flops_per_sample / peak_flops_per_s


def peak_for(peaks: dict, device_kind: str) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json; "
            "add it with its source before measuring on it")
    return peaks[device_kind]
