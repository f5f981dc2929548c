"""Share of the window's wall time the trainer's loop spent waiting for
the next batch (the program's ``data_wait`` spans, consumer side).
Nothing to read where batches are resident: no such span exists."""


def read(ctx):
    waits = [s["dur_s"] for s in ctx["spans"]
             if s["phase"] == "data_wait" and not s["overlap"]]
    if not waits:
        return None
    return 100.0 * sum(waits) / ctx["window_s"]
