"""The global attention cores' share of their roofline: the operations of
two score maps and one value product a head pair over the causal half,
for the full layer and the cross layers together (three passes,
``flops_sambay.attn_core_train_flops``) for the traced sequences, over
the bf16 peak, over the device time in the scopes ``attn_full`` and
``attn_cross`` (scores, softmax, values, ``lam``, norm).  None with no
time in either scope."""
from benchmark import flops_sambay
from benchmark.layer_metrics import _scopes


def read(ctx):
    full = _scopes.seconds(ctx, "attn_full")
    cross = _scopes.seconds(ctx, "attn_cross")
    secs = (full or 0.0) + (cross or 0.0)
    if not secs:
        return None
    ops = flops_sambay.attn_core_train_flops(
        ctx["layers"], ctx["seq_len"],
        ctx["trace"]["samples"] / ctx["chips"], kinds=("full", "cross"))
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
