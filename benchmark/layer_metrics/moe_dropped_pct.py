"""Assignments to a held expert that found no room, over all routed
here, over the window.  Must read 0.0: the row buffer holds five times
the uniform load so that nothing is dropped, and a run with one dropped
is not correct.  The program's
routing counters.  None where the program counts no routing."""


def read(ctx):
    routing = ctx.get("routing")
    if not routing:
        return None
    dropped = sum(v["dropped"] for v in routing.values())
    routed = sum(int(v["assignments"].sum()) for v in routing.values())
    if routed + dropped <= 0:
        return None
    return 100.0 * dropped / (routed + dropped)
