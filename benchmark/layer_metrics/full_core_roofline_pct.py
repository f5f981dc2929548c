"""The full attention cores' share of their roofline: the operations of
scores and values over the causal half, every full layer held, three
passes (``flops_laguna.core_train_flops``), for the traced sequences,
over the bf16 peak, over the device time in the scope ``full_core``
(scores, softmax, values: ``ops/attention.py:causal_gqa`` where it took
the shape, else the XLA loop).  None with no time in the scope."""
from benchmark import flops_laguna
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "full_core")
    if not secs:
        return None
    ops = flops_laguna.core_train_flops(
        ctx["layers"], ctx["seq_len"], ctx["trace"]["samples"] / ctx["chips"],
        "full_core")
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
