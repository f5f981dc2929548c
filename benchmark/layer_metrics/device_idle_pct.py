"""Share of the traced window in which nothing ran on the device: one
less the busy union over the window, averaged over the devices.  For
cells whose epoch is one dispatch; where batches stream, the few traced
seconds start from an empty pipeline under a profiler that has just
started, and read 5 to 21% where the window itself leaves 1%
(``window_idle_pct``; PERF.md)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
