"""Median, over the window's epochs, of an epoch's summed ``epoch_close``
time: what the consumer thread does after the epoch's last dispatch
under no other name (the prefetch pool's shutdown, the stack of the
losses, the straggler record, the preemption check).  An epoch's spans
share their ``step``, its first global step.  The ``loss_flush`` that
lies among them keeps its own name and is not counted."""
import statistics


def read(ctx):
    per_epoch = {}
    for s in ctx["spans"]:
        if s["phase"] == "epoch_close":
            per_epoch[s["step"]] = per_epoch.get(s["step"], 0.0) + s["dur_s"]
    if not per_epoch:
        return None
    return 1000.0 * statistics.median(per_epoch.values())
