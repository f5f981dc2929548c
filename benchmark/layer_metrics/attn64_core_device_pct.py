"""Share of the device's busy time in the scope ``attn_core`` where the
heads are 64 wide: scores, softmax and values on the blocked XLA loop of
``ops/seq.py`` (``causal_gqa`` takes heads a multiple of 128 wide), a
(sequence, key-value head) pair at a time.  Device trace, by the
program's scopes."""
from benchmark.layer_metrics import _scopes


def read(ctx):
    return _scopes.busy_share_pct(ctx, "attn_core")
