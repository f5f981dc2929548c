"""Share of the device's busy time in the scope ``mtp``: what only the
multi-token-prediction module has (its two input norms, the shifted
embedding, ``W_eh``, its final norm; its block's parts carry the block's
scopes).  Device trace, by the program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "mtp")
