"""Share of the device's busy time in the scope ``sel_scan``: the Mamba-1
mixers' selective scan (softplus of ``dt``, the recurrence a token a step
over its float32 state, the skip and the gate), forward, recomputed and
backward.  Device trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "sel_scan")
