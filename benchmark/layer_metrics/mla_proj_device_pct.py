"""Share of the device's busy time in the scope ``mla_proj``: latent
attention's five projections, the two latent norms, the rotary embedding
and the assembly of the heads' queries and keys (thin products and
concatenations, bound by bytes).  Device trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "mla_proj")
