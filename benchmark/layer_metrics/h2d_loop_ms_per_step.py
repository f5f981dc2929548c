"""Consumer-thread ``h2d`` time of the window over its steps: what
handing a batch to the device costs the loop.  Not the copy's own time:
``device_put`` may return before the copy ends.  ``h2d`` spans of a
producer thread (``overlap``) hide behind the loop and are not counted.

ISSUE 24 names this ``h2d_ms_per_step``; ``tests/test_files_only_cell.py``
writes a file of that name into its copy of the benchmark and holds that
it is new there."""


def read(ctx):
    h2d = [s["dur_s"] for s in ctx["spans"]
           if s["phase"] == "h2d" and not s["overlap"]]
    if not h2d or not ctx["window_steps"]:
        return None
    return 1000.0 * sum(h2d) / ctx["window_steps"]
