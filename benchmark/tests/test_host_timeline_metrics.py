"""The six readers of the host's timeline (ISSUE 24) over hand-made
spans: a value worked out by hand, and None where the program records no
such span or count (as the commit before them does not)."""
import importlib

import pytest

import conftest  # noqa: F401  (puts the repository root on sys.path)


def _span(phase, start_s, dur_s, step=0, overlap=False, **counts):
    return dict({"phase": phase, "step": step, "start_s": start_s,
                 "dur_s": dur_s, "overlap": overlap, "req": None,
                 "n": None, "nbytes": None}, **counts)


# Two epochs of two steps in a window of 1 s that opens at 10.0 on the
# tracer's clock.  Epoch 0 (steps 0, 1) and epoch 1 (steps 2, 3).
SPANS = [
    _span("epoch_setup", 10.000, 0.010, step=0),
    _span("host_augment", 10.005, 0.300, step=0, overlap=True),
    _span("data_wait", 10.010, 0.020, step=0),
    _span("h2d", 10.030, 0.004, step=0, nbytes=9_000_000),
    _span("dispatch", 10.034, 0.002, step=0, n=3072),
    _span("data_wait", 10.040, 0.100, step=1),     # 4 ms unnamed before
    _span("h2d", 10.140, 0.006, step=1, nbytes=3_000_000),
    _span("dispatch", 10.146, 0.004, step=1, n=1024),
    _span("epoch_close", 10.150, 0.030, step=0),   # the pool's shutdown
    _span("epoch_close", 10.180, 0.002, step=0),   # the stack
    _span("epoch_close", 10.182, 0.001, step=0),   # stragglers, preemption
    _span("epoch_setup", 10.183, 0.020, step=2),
    _span("data_wait", 10.203, 0.010, step=2),
    _span("h2d", 10.213, 0.002, step=2, nbytes=9_000_000),
    _span("h2d", 10.213, 0.050, step=3, overlap=True, nbytes=3_000_000),
    _span("dispatch", 10.215, 0.010, step=2, n=3072),
    _span("dispatch", 10.225, 0.006, step=3, n=1024),
    _span("epoch_close", 10.231, 0.009, step=2),
    _span("loss_flush", 10.240, 0.700, step=0, n=2),
    _span("epoch_close", 10.940, 0.002, step=2),
    _span("loss_flush", 10.942, 0.048, step=2, n=2),  # ends at 10.990
]
CTX = {"spans": SPANS, "window_s": 1.0, "window_steps": 4}
# Named on the consumer thread: [10.000, 10.036], [10.040, 10.990]
# (every later span starts where the one before it ends), so 0.036 +
# 0.950 of the 1 s; 1.4% is under no name.
EXPECTED = {
    "epoch_setup_ms": 15.0,             # median of 10 and 20
    "epoch_close_ms": 22.0,             # median of 30+2+1 and 9+2
    "host_untraced_pct": 1.4,
    "h2d_loop_ms_per_step": 3.0,        # (4 + 6 + 2) ms over 4 steps
    "h2d_mb_per_step": 6.0,             # 24 MB over 4 steps, any thread
    "dispatch_ms_per_step": 5.0,        # median of 2, 4, 10, 6
}
# What a reader must find to read anything.
NEEDS = {
    "epoch_setup_ms": lambda s: s["phase"] != "epoch_setup",
    "epoch_close_ms": lambda s: s["phase"] != "epoch_close",
    "host_untraced_pct": lambda s: s["overlap"],
    "h2d_loop_ms_per_step": lambda s: s["phase"] != "h2d" or s["overlap"],
    "h2d_mb_per_step": lambda s: s["phase"] != "h2d",
    "dispatch_ms_per_step": lambda s: s["phase"] != "dispatch",
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_spans(name):
    read = importlib.import_module("benchmark.layer_metrics." + name).read
    assert read(CTX) == pytest.approx(EXPECTED[name])
    without = [s for s in SPANS if NEEDS[name](s)]
    assert read(dict(CTX, spans=without)) is None
    assert read(dict(CTX, spans=[])) is None
    # The spans of the commit before: no count on any of them, and (but
    # for req) no key for one.
    bare = [{k: v for k, v in s.items() if k not in ("n", "nbytes")}
            for s in SPANS if s["phase"] not in ("epoch_setup",
                                                 "epoch_close")]
    value = read(dict(CTX, spans=bare))
    if name in ("epoch_setup_ms", "epoch_close_ms", "h2d_mb_per_step"):
        assert value is None
    else:
        assert value is not None
