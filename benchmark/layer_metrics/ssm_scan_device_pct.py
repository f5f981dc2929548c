"""Share of the device's busy time in the scope ``ssm_scan``: the Mamba-2
mixers' chunked recurrence and gated norm, forward, recomputed and
backward.  Device trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "ssm_scan")
