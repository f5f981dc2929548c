"""Share of the device's busy time in the scope ``ssm_conv``: a Mamba-2
mixer's causal depthwise convolution over ``x``, ``B``, ``C`` with its
bias and ``silu``, in float32 XLA (``ops/seq.py:causal_conv_silu``),
forward, under the block's checkpoint and backward.  Device trace, by the
program's scopes; any cell whose program names the scope can list it."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "ssm_conv")
