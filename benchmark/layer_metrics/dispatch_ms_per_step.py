"""Median ``dispatch`` span of the window: the host's price of one
per-step launch (the jitted call's enqueue, and whatever XLA makes it
wait for)."""
import statistics


def read(ctx):
    calls = [s["dur_s"] for s in ctx["spans"] if s["phase"] == "dispatch"]
    return 1000.0 * statistics.median(calls) if calls else None
