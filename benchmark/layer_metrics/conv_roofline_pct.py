"""The convolutions' share of their roofline: their forward and backward
operations for the traced samples (``flops.py``, from the layer shapes),
over the chip's bf16 peak, over the device time of the operations that
hold a convolution (``trace_reduce.conv_seconds``).  These convolutions
are bound by operations, not bytes: the least time the chip could take
is operations over peak."""
from benchmark import flops, trace_reduce


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    conv_s = trace_reduce.conv_seconds(tr)
    if conv_s <= 0:
        return None
    ops = (flops.conv_train_flops_per_sample(ctx["layers"])
           * tr["samples"] / ctx["chips"])
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / conv_s
