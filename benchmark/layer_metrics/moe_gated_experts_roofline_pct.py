"""The gated (three-matrix SwiGLU) routed experts' share of their
roofline: the operations of the assignments the program COUNTED over the
traced epochs (its routing counters; three passes,
``flops_glm4_moe_lite.expert_train_flops``) over the bf16 peak, over the
device time in the scope ``moe_experts`` (the experts' products over the
row tiles).  Padding and empty row tiles show as a low share.  None with
no time in the scope."""
from benchmark import flops_glm4_moe_lite
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "moe_experts")
    if not secs:
        return None
    ops = flops_glm4_moe_lite.expert_train_flops(
        ctx["layers"], ctx["trace"]["assignments"] / ctx["chips"])
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
