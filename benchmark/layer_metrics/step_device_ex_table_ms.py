"""Device-busy time an optimizer step outside the operations that write
a whole copy of the resident table (``trace_reduce.table_seconds``): the
model's forward, backward and update, the row gather and the
augmentation.  What a change to ``ops/layers.py`` or the step builders
moves, however large the table copy beside it is.  Device trace; nothing
to read where batches stream (no table)."""
from benchmark import trace_reduce


def read(ctx):
    tr, table = ctx.get("trace"), ctx.get("table")
    if not tr or not table:
        return None
    table_s = trace_reduce.table_seconds(tr, **table)
    return 1000.0 * (tr["busy_s"] - table_s) / tr["steps"]
