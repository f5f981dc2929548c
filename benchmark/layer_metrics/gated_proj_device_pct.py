"""Share of the device's busy time in the scope ``gated_proj``: the
attention layers' five products (queries, keys, values, the per-head gate,
the output), both rotary forms, the gate's sigmoid and product and the
head-major layout of ``q``, ``k``, ``v`` around the core.  Device trace,
by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "gated_proj")
