"""The latent-attention cores' share of their roofline: the operations of
scores and values over the causal half, every block held and the
prediction module's (three passes, ``flops_glm4_moe_lite.
mla_core_train_flops``) for the traced sequences, over the bf16 peak,
over the device time in the scope ``mla_core`` (scores, softmax, values:
``ops/attention.py:causal_gqa`` where it took the shape, else the XLA
loop).  None with no time in the scope."""
from benchmark import flops_glm4_moe_lite
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "mla_core")
    if not secs:
        return None
    ops = flops_glm4_moe_lite.mla_core_train_flops(
        ctx["layers"], ctx["seq_len"],
        ctx["trace"]["samples"] / ctx["chips"])
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
