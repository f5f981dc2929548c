"""Seconds of set-up inside JAX's preparation of executables, whoever
called: the length of the union of the ``prepare_trace``,
``prepare_lower`` and ``prepare_compile`` spans that start before the
window (a union, because a jitted function traced inside another nests
in it)."""
from benchmark import trace_reduce
from benchmark.layer_metrics import _startup


def read(ctx):
    found = _startup.before_window(ctx)
    if found is None:
        return None
    return trace_reduce.length(trace_reduce.union(
        (s["start_s"], s["start_s"] + s["dur_s"]) for s in found[0]
        if s["phase"] in _startup.PREPARE_PHASES))
