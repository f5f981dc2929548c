"""Share of the window's wall time that no span of the consumer thread
names: the window less the union of its non-overlap spans.  The guard
that the program's spans tile its loop; what stays is the loop's own
Python between two spans."""
from benchmark import trace_reduce


def read(ctx):
    named = trace_reduce.length(trace_reduce.union(
        (s["start_s"], s["start_s"] + s["dur_s"])
        for s in ctx["spans"] if not s["overlap"]))
    if not named:
        return None
    return 100.0 * (ctx["window_s"] - named) / ctx["window_s"]
