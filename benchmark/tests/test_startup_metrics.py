"""The five readers of set-up (ISSUE 37) over a hand-written timeline: a
value worked out by hand, 0.0 where the timeline holds nothing of the
reader's kind, None where the program keeps no timeline (as the commit
before them does not); and two rehearsals that print all five."""
import importlib

import pytest

from conftest import run_cell  # (puts the repository root on sys.path)

NAMES = ("setup_before_program_s", "trainer_init_s", "setup_prepare_s",
         "setup_compiled_count", "dispatch_prepares")


def _span(phase, start_s, dur_s, overlap=False, **extra):
    return dict({"phase": phase, "step": None, "start_s": start_s,
                 "dur_s": dur_s, "overlap": overlap, "req": None,
                 "n": None, "nbytes": None, "name": None}, **extra)


# The tracer was built when the process was 6.5 s old; its clock's zero
# is that moment.  Trainer.__init__ 0.25 .. 2.25 with the table's upload
# and one small executable (read back) inside it; a launcher's own check
# prepares the step outside any span (compiled: 10 s); the first epoch's
# dispatch prepares the step again (read back) and a tail shape
# (compiled); the second epoch's prepares the first again, as the
# program does (ROADMAP A2).  The window opens at 40.0 and holds one
# more preparation, which is no longer set-up.
ZERO_AGE_S = 6.5
TIMELINE = [
    _span("trainer_init", 0.25, 2.0),
    _span("resident_upload", 0.5, 0.5, nbytes=1 << 20),
    _span("prepare_trace", 1.0, 0.125, name="fill"),
    _span("prepare_lower", 1.125, 0.125, name="jit_fill"),
    _span("prepare_compile", 1.25, 0.25, n=0, name="jit(fill)"),
    _span("prepare_trace", 3.0, 2.0, name="step"),
    _span("prepare_trace", 3.5, 0.5, name="inner"),       # inside the outer
    _span("prepare_lower", 5.0, 1.0, name="jit_step"),
    _span("prepare_compile", 6.0, 10.0, n=1, name="jit(step)"),
    _span("epoch_setup", 20.0, 0.5),
    _span("dispatch", 20.5, 4.0, step=0, n=3072),
    _span("prepare_trace", 20.75, 0.25, name="step"),
    _span("prepare_lower", 21.0, 0.5, name="jit_step"),
    _span("prepare_compile", 21.5, 2.0, n=0, name="jit(step)"),
    _span("dispatch", 24.5, 3.0, step=1, n=848),
    _span("prepare_trace", 24.75, 0.25, name="step"),
    _span("prepare_lower", 25.0, 0.5, name="jit_step"),
    _span("prepare_compile", 25.5, 1.5, n=1, name="jit(step)"),
    _span("host_augment", 24.0, 9.0, overlap=True),
    _span("dispatch", 30.0, 2.0, step=2, n=3072),
    _span("prepare_trace", 30.25, 0.25, name="step"),
    _span("prepare_lower", 30.5, 0.25, name="jit_step"),
    _span("prepare_compile", 30.75, 1.0, n=0, name="jit(step)"),
    _span("epoch_setup", 40.0, 0.5),
    _span("dispatch", 40.5, 1.0, step=3, n=3072),
    _span("prepare_compile", 40.75, 0.5, n=1, name="jit(late)"),
]
CTX = {"spans": [s for s in TIMELINE if s["start_s"] >= 40.0]}
EXPECTED = {
    "setup_before_program_s": 6.75,     # 6.5 + trainer_init's start
    "trainer_init_s": 2.0,
    # [1, 1.5] + [3, 16] + [20.75, 23.5] + [24.75, 27] + [30.25, 31.75]
    "setup_prepare_s": 0.5 + 13.0 + 2.75 + 2.25 + 1.5,
    "setup_compiled_count": 2.0,        # the check's and the tail's
    "dispatch_prepares": 3.0,           # not fill's, not the check's
}
# What a reader must find on the timeline to read anything but zero.
NEEDS = {
    "trainer_init_s": lambda s: s["phase"] != "trainer_init",
    "setup_prepare_s": lambda s: not s["phase"].startswith("prepare_"),
    "setup_compiled_count": lambda s: s["n"] != 1,
    "dispatch_prepares": lambda s: s["phase"] != "dispatch",
}


@pytest.fixture
def timeline(monkeypatch):
    from ddp_tpu.obs import startup
    held = {"value": (TIMELINE, ZERO_AGE_S)}
    monkeypatch.setattr(startup, "timeline", lambda: held["value"])
    return held


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_hand_written_timeline(name, timeline):
    read = importlib.import_module("benchmark.layer_metrics." + name).read
    assert read(CTX) == pytest.approx(EXPECTED[name])
    assert isinstance(read(CTX), float)
    # Looked for and found nothing: 0.0, never None (the readers' rule).
    if name in NEEDS:
        timeline["value"] = ([s for s in TIMELINE if NEEDS[name](s)],
                             ZERO_AGE_S)
        assert read(CTX) == 0.0 and isinstance(read(CTX), float)
    # A timeline with no span before the window: the program's first
    # span is the window's first.
    timeline["value"] = (list(CTX["spans"]), ZERO_AGE_S)
    assert read(CTX) == (46.5 if name == "setup_before_program_s" else 0.0)
    # An empty timeline and an empty window: the tracer's zero.
    timeline["value"] = ([], ZERO_AGE_S)
    assert read({"spans": []}) == (
        ZERO_AGE_S if name == "setup_before_program_s" else 0.0)
    # No tracer was attached, or the program keeps no timeline.
    timeline["value"] = None
    assert read(CTX) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_from_a_program_without_the_module(
        name, monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "ddp_tpu.obs.startup", None)
    import ddp_tpu.obs
    monkeypatch.delattr(ddp_tpu.obs, "startup", raising=False)
    read = importlib.import_module("benchmark.layer_metrics." + name).read
    assert read(CTX) is None


@pytest.mark.parametrize("cell", ["vgg_train_stream_1chip",
                                  "nemotron3_nano_train_8k_1chip"])
def test_traced_rehearsal_prints_all_five(cell):
    rc, result, err = run_cell(cell, "--seconds", "1", "--trace", "1",
                               "--rehearse")
    assert rc == 0, err[-3000:]
    got = {n: result["metrics"][n]["value"] for n in NAMES}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values()), got
    assert got["setup_before_program_s"] > 0.0
    assert got["trainer_init_s"] > 0.0 and got["setup_prepare_s"] > 0.0
    if cell.startswith("vgg"):
        # Two shapes, the full one twice (ROADMAP A2).
        assert got["dispatch_prepares"] >= 2.0
    else:
        # The runner's first-step check prepared the step, under no
        # dispatch: looked for and found zero.
        assert got["dispatch_prepares"] == 0.0
    assert "not read" not in err
