"""Share of the device's busy time in the scope ``moe_route``: router,
top-k, the sort of the assignments, the gather of the rows and the
combine back to token order.  Device trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "moe_route")
