"""The SwiGLU layers' share of their roofline: the operations of every
held layer's two products (three passes, ``flops_sambay.
mlp_train_flops``: three fifths of the step's operations at the published
sizes) for the traced tokens, over the bf16 peak, over the device time in
the scope ``mlp``.  None with no time in the scope."""
from benchmark import flops_sambay
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "mlp")
    if not secs:
        return None
    tokens = ctx["trace"]["samples"] * ctx["seq_len"] / ctx["chips"]
    ops = flops_sambay.mlp_train_flops(ctx["layers"], tokens)
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
