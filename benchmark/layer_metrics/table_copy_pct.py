"""Share of the device-busy time spent in operations that write a whole
copy of the resident table (``trace_reduce.table_seconds``): the
relayout for the row gather, which belongs before the epoch and not in
it.  Device trace.  A table that no operation copies reads 0.0: that is
the goal, and a reading.  None only where the input is missing: no trace
(a rehearsal), no resident table (batches stream), or a trace in which
nothing ran."""
from benchmark import trace_reduce


def read(ctx):
    tr, table = ctx.get("trace"), ctx.get("table")
    if not tr or not table or tr["busy_s"] <= 0:
        return None
    return 100.0 * trace_reduce.table_seconds(tr, **table) / tr["busy_s"]
