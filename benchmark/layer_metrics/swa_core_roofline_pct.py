"""The window attention cores' share of their roofline: the operations of
scores and values over the keys each query's window shows (``min(i + 1,
window)`` at position ``i``), every window layer held, three passes
(``flops_laguna.core_train_flops``), for the traced sequences, over the
bf16 peak, over the device time in the scope ``swa_core`` (scores,
softmax, values: ``ops/attention.py:causal_gqa`` under its window where it
took the shape, else the XLA loop).  Its ceiling on the kernel's walk is
near 50%: a query block of 512 visits two key tiles of 512, the diagonal
and the one across the window's lower edge, for the 512 keys a query
sees, so half of what the tiles compute is masked.  None with no time in
the scope."""
from benchmark import flops_laguna
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "swa_core")
    if not secs:
        return None
    ops = flops_laguna.core_train_flops(
        ctx["layers"], ctx["seq_len"], ctx["trace"]["samples"] / ctx["chips"],
        "swa_core")
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
