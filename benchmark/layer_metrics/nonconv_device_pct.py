"""Share of the model's device time outside the operations that hold a
convolution: BN, pooling, residual adds, the gather, the update.  The
model's time is the operations' self time less the copies of a resident
table (``trace_reduce.table_seconds``; ``table_copy_pct`` reads those).
Device trace."""
from benchmark import trace_reduce


def read(ctx):
    tr, table = ctx.get("trace"), ctx.get("table")
    if not tr:
        return None
    total = sum(tr["ops"].values())
    if table:
        total -= trace_reduce.table_seconds(tr, **table)
    if total <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.conv_seconds(tr) / total)
