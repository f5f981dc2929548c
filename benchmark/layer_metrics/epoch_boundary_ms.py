"""Median host time at an epoch boundary: from the end of an epoch's last
``dispatch`` span to the start of the next epoch's first, less the part
spent inside ``loss_flush`` spans.  The flush is where the host, one
epoch ahead, waits for the device to finish the epoch before; what is
left is the host's own work between epochs (sampler reshuffle, index
matrix or prefetcher restart), which the device would wait for if it
ever caught up."""
import statistics


def read(ctx):
    spans = sorted((s for s in ctx["spans"] if s["phase"] == "dispatch"
                    and s["step"] is not None),
                   key=lambda s: s["start_s"])
    if len(spans) < 2:
        return None
    flushes = [(s["start_s"], s["start_s"] + s["dur_s"])
               for s in ctx["spans"] if s["phase"] == "loss_flush"]
    base, per_epoch = spans[0]["step"], ctx["steps_per_epoch"]
    gaps = []
    for a, b in zip(spans, spans[1:]):
        if (b["step"] - base) % per_epoch:
            continue
        lo, hi = a["start_s"] + a["dur_s"], b["start_s"]
        waited = sum(max(0.0, min(hi, e) - max(lo, s)) for s, e in flushes)
        gaps.append(hi - lo - waited)
    return 1000.0 * statistics.median(gaps) if gaps else None
