"""Median ``epoch_setup`` span of the window: the consumer thread's time
from an epoch's entry to its first wait for a batch (header, sampler
reshuffle, the prefetch pool and its first submissions) or, where
batches are resident, to its first dispatch (index matrices built and
shipped).  Nothing to read from a program that records no such span."""
import statistics


def read(ctx):
    setups = [s["dur_s"] for s in ctx["spans"] if s["phase"] == "epoch_setup"]
    return 1000.0 * statistics.median(setups) if setups else None
