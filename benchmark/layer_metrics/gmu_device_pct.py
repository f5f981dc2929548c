"""Share of the device's busy time in the scope ``gmu``: the gated memory
units' two products and the gate by another layer's scan output.  Device
trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "gmu")
