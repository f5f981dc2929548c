"""Share of the device-busy time spent in operations that write a whole
copy of the resident table (``trace_reduce.table_seconds``): the
relayout for the row gather, which belongs before the epoch and not in
it.  Device trace; nothing to read where no operation writes one."""
from benchmark import trace_reduce


def read(ctx):
    tr, table = ctx.get("trace"), ctx.get("table")
    if not tr or not table or tr["busy_s"] <= 0:
        return None
    table_s = trace_reduce.table_seconds(tr, **table)
    return 100.0 * table_s / tr["busy_s"] if table_s > 0 else None
