"""Attention's share of its roofline: the causal half of scores and
values for the traced sequences (three passes,
``flops_seq.attn_core_train_flops``) over the bf16 peak, over the device
time in the scope ``attn_core`` (scores, softmax, values).  Keys past a
query's position that the program computes and masks show as a low
share.  None with no time in the scope."""
from benchmark import flops_seq
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "attn_core")
    if not secs:
        return None
    ops = flops_seq.attn_core_train_flops(
        ctx["layers"], ctx["seq_len"],
        ctx["trace"]["samples"] / ctx["chips"])
    return 100.0 * ops / ctx["peak"]["bf16_flops_per_s"] / secs
