"""The windowed attention core's share of its roofline: the operations
of two score maps and one value product a head pair over the keys INSIDE
the window (``min(i + 1, window)`` a query; three passes,
``flops_sambay.attn_core_train_flops``) for the traced sequences, over
the bf16 peak, over the device time in the scope ``attn_window`` (scores,
softmax, values, ``lam``, norm).  A layer that computes all ``T x T``
scores and masks reads an eighth of what it could at 8,192 tokens; whole
key blocks around a 512-key window read about half.  None with no time in
the scope."""
from benchmark import flops_sambay
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "attn_window")
    if not secs:
        return None
    ops = flops_sambay.attn_core_train_flops(
        ctx["layers"], ctx["seq_len"],
        ctx["trace"]["samples"] / ctx["chips"], kinds=("window",))
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
