"""Share of the measured window's wall time in which nothing ran on the
device: one less the device-busy time of the window's steps over the
window's wall time.  The busy time a step is the traced epochs' (the
union of the operations' intervals over their steps: the same programs
on the same shapes, and a union does not see the gaps of the traced
call); the window is the untraced one the rate comes from, on the
host's clock, with its one start and its one drain.  What a change to
the loader, the prefetcher or the dispatch can win back end to end."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("window_steps"):
        return None
    busy_s = tr["busy_s"] / tr["steps"] * ctx["window_steps"]
    return 100.0 * (1.0 - busy_s / ctx["window_s"])
