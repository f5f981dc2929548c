"""Set-up on the program's own timeline (ISSUE 37): JAX's preparation of
an executable as spans of the attached tracer (obs/startup.py), the
``trainer_init`` span, the nesting of serial spans (obs/export.py) and
the sums that must not count a nested span twice.  All on the CPU."""
import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from ddp_tpu.obs import export, startup
from ddp_tpu.obs.tracer import SpanTracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREPARE = ("prepare_trace", "prepare_lower", "prepare_compile")


def _named(spans, fn_name):
    return [s for s in spans if s["name"] and fn_name in s["name"]]


def test_a_jitted_call_is_one_named_triple_inside_the_span_that_caused_it():
    @jax.jit
    def startup_probe_inner(x):
        return jnp.tanh(x) * 3.0

    @jax.jit
    def startup_probe_fn(x):
        return startup_probe_inner(x) + 1.0

    x = jnp.ones((3, 5))  # an executable of its own: before the tracer
    tr = SpanTracer()
    startup.attach(tr)
    with tr.span("dispatch", step=0):
        startup_probe_fn(x)
        startup_probe_fn(x)  # prepared: no second triple
    with tr.span("dispatch", step=1):
        startup_probe_fn(x)
    spans = tr.spans_since(float("-inf"))
    mine = _named(spans, "startup_probe_fn")
    assert sorted(s["phase"] for s in mine) == sorted(PREPARE)
    # What is traced inside it (the inner function, every jnp operation)
    # reports a trace of its own, which the outer's span holds: not kept.
    assert _named(spans, "startup_probe_inner") == []
    assert [s["phase"] for s in spans] == list(PREPARE) + ["dispatch"] * 2
    assert all(not s["overlap"] and s["dur_s"] >= 0.0 for s in mine)
    # trace, then lower, then compile, on the tracer's clock.
    starts = {s["phase"]: s["start_s"] for s in mine}
    assert starts["prepare_trace"] <= starts["prepare_lower"] \
        <= starts["prepare_compile"]
    compiled = [s for s in mine if s["phase"] == "prepare_compile"]
    assert compiled[0]["n"] in (0, 1)
    assert all(s["n"] is None for s in mine if s not in compiled)
    # Each lies in the first dispatch, which the intervals alone say.
    parents = export.span_parents(spans)
    first = next(i for i, s in enumerate(spans)
                 if s["phase"] == "dispatch" and s["step"] == 0)
    for i, s in enumerate(spans):
        if s in mine:
            assert parents[i] == first, (s, parents[i])
        if s["phase"] == "dispatch":
            assert parents[i] is None


def test_spans_go_to_the_tracer_attached_last_and_nowhere_when_it_is_gone():
    first, second = SpanTracer(), SpanTracer()
    startup.attach(first)
    startup.attach(second)
    jax.jit(lambda x: x * 2.0 + 7.0)(jnp.ones(4))
    assert first.spans_since(float("-inf")) == []
    assert {s["phase"] for s in second.spans_since(float("-inf"))} \
        == set(PREPARE)
    spans, zero_age_s = startup.timeline()
    assert spans == second.spans_since(float("-inf"))
    # The tracer was built a moment ago by a process some seconds old.
    assert 0.0 < zero_age_s <= time.monotonic() - startup.PROCESS_START
    del spans, second
    startup.attach(SpanTracer())  # held weakly: gone at once
    assert startup.timeline() is None
    jax.jit(lambda x: x * 2.0 + 9.0)(jnp.ones(4))  # and nothing raises


def test_attach_registers_one_listener_pair_a_process():
    from jax._src import monitoring  # the public module lists nothing
    startup.attach(SpanTracer())
    spans_before = monitoring.get_event_time_span_listeners()
    events_before = monitoring.get_event_listeners()
    for _ in range(3):
        startup.attach(SpanTracer())
    assert monitoring.get_event_time_span_listeners() == spans_before
    assert monitoring.get_event_listeners() == events_before
    assert spans_before.count(startup._on_time_span) == 1
    assert events_before.count(startup._on_event) == 1
    assert monitoring.get_scalar_listeners().count(startup._on_start) == 1


_CACHE_SCRIPT = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ddp_tpu.obs import startup
from ddp_tpu.obs.tracer import SpanTracer
tr = SpanTracer()
startup.attach(tr)

@jax.jit
def cache_probe_fn(x):
    return jnp.sin(x) @ x.T

cache_probe_fn(jnp.ones((8, 8)))
jax.clear_caches()  # in memory only: the next preparation is fresh
cache_probe_fn(jnp.ones((8, 8)))
print([s["n"] for s in tr.spans_since(float("-inf"))
       if s["phase"] == "prepare_compile" and "cache_probe_fn" in s["name"]])
"""


def test_compile_reads_n_1_and_a_read_from_the_persistent_cache_n_0(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "[1, 0]"


def _tiny_trainer(tmp_path, **kw):
    from ddp_tpu.data import TrainLoader, synthetic
    from ddp_tpu.models import get_model
    from ddp_tpu.optim import SGDConfig, triangular_lr
    from ddp_tpu.parallel import make_mesh
    from ddp_tpu.train import Trainer
    train_ds, _ = synthetic(n_train=32)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=2)
    sched = functools.partial(triangular_lr, base_lr=0.02, num_epochs=2,
                              steps_per_epoch=len(loader))
    return Trainer(model, loader, params, stats, mesh=make_mesh(2),
                   lr_schedule=sched, sgd_config=SGDConfig(lr=0.02),
                   save_every=100, snapshot_path=None, **kw)


def test_a_null_tracer_trainer_registers_and_calls_nothing(tmp_path,
                                                           monkeypatch):
    from jax._src import monitoring  # the public module lists nothing

    def boom(_tracer):
        raise AssertionError("attach called with tracing off")

    monkeypatch.setattr(startup, "attach", boom)
    before = (monitoring.get_event_listeners(),
              monitoring.get_event_time_span_listeners(),
              monitoring.get_event_duration_listeners(),
              monitoring.get_scalar_listeners())
    trainer = _tiny_trainer(tmp_path)
    assert not trainer.tracer.enabled
    assert before == (monitoring.get_event_listeners(),
                      monitoring.get_event_time_span_listeners(),
                      monitoring.get_event_duration_listeners(),
                      monitoring.get_scalar_listeners())


def test_a_traced_trainer_has_trainer_init_and_prepares_under_dispatch(
        tmp_path, capsys):
    from benchmark.layer_metrics import (dispatch_prepares, setup_prepare_s,
                                         trainer_init_s)
    tr = SpanTracer(ring=1 << 16)
    trainer = _tiny_trainer(tmp_path, tracer=tr)
    trainer.train(2)
    capsys.readouterr()
    spans, _zero_age_s = startup.timeline()
    inits = [s for s in spans if s["phase"] == "trainer_init"]
    assert len(inits) == 1 and inits[0]["dur_s"] > 0.0
    # It is the program's first span and holds what __init__ prepared.
    assert inits[0]["start_s"] == min(s["start_s"] for s in spans)
    parents = export.span_parents(spans)
    caused = [spans[p]["phase"] if p is not None else None
              for s, p in zip(spans, parents)
              if s["phase"] == "prepare_compile"]
    assert "dispatch" in caused
    # The readers, with nothing yet in a window: all of it is set-up.
    ctx = {"spans": []}
    assert dispatch_prepares.read(ctx) == caused.count("dispatch") >= 1
    assert trainer_init_s.read(ctx) == pytest.approx(inits[0]["dur_s"])
    assert 0.0 < setup_prepare_s.read(ctx) <= tr.now()
    # The step program: once for the unplaced state, once for the placed
    # (ROADMAP A2), under the same name.
    steps = [s["name"] for s, p in zip(spans, parents)
             if s["phase"] == "prepare_compile" and p is not None
             and spans[p]["phase"] == "dispatch"]
    assert len(steps) > len(set(steps))


# A recorded spill: a 3 s dispatch whose call made JAX trace (an inner
# jitted function inside the outer), lower and compile; then a step.
_SPILL = [
    {"phase": "trainer_init", "step": None, "start_s": 2.0, "dur_s": 1.0},
    {"phase": "resident_upload", "step": None, "start_s": 2.2, "dur_s": 0.5,
     "nbytes": 1000},
    {"phase": "prepare_trace", "step": None, "start_s": 3.2, "dur_s": 0.1,
     "name": "inner"},
    {"phase": "prepare_trace", "step": None, "start_s": 3.1, "dur_s": 0.4,
     "name": "step"},
    {"phase": "prepare_lower", "step": None, "start_s": 3.5, "dur_s": 0.2,
     "name": "jit_step"},
    {"phase": "prepare_compile", "step": None, "start_s": 3.7, "dur_s": 2.2,
     "n": 1, "name": "jit(step)"},
    {"phase": "dispatch", "step": 0, "start_s": 3.0, "dur_s": 3.0, "n": 8},
    {"phase": "host_augment", "step": 1, "start_s": 3.0, "dur_s": 2.0,
     "overlap": True},
    {"phase": "dispatch", "step": 1, "start_s": 6.0, "dur_s": 1.0, "n": 8},
]


@pytest.fixture
def spill_spans(tmp_path):
    path = tmp_path / "spill.jsonl"
    path.write_text("".join(json.dumps(dict(s, host=0)) + "\n"
                            for s in _SPILL))
    return export.read_spill([str(path)])


def test_parents_are_the_innermost_enclosing_serial_span(spill_spans):
    parents = export.span_parents(spill_spans)
    got = {(s["phase"], s.get("name") or s["step"]):
           (None if p is None else
            (spill_spans[p]["phase"], spill_spans[p].get("name")))
           for s, p in zip(spill_spans, parents)}
    assert got == {
        ("trainer_init", None): None,
        ("resident_upload", None): ("trainer_init", None),
        ("dispatch", 0): None,
        ("prepare_trace", "step"): ("dispatch", None),
        ("prepare_trace", "inner"): ("prepare_trace", "step"),
        ("prepare_lower", "jit_step"): ("dispatch", None),
        ("prepare_compile", "jit(step)"): ("dispatch", None),
        ("host_augment", 1): None,  # another thread: no parent
        ("dispatch", 1): None,
    }
    # Hosts' clocks are independent: a span of another host is no parent.
    other = [dict(s, host=1) if s["phase"] == "dispatch" else s
             for s in spill_spans]
    assert all(p is None or other[p]["phase"] != "dispatch"
               for p in export.span_parents(other))


def test_coverage_counts_a_nested_span_once(spill_spans):
    rows, wall_s, critical_s = export.phase_summary(spill_spans)
    assert wall_s == pytest.approx(5.0)           # 2.0 .. 7.0
    assert critical_s == pytest.approx(5.0)       # 1 + 3 + 1, nothing twice
    lanes = {(r["phase"], r["lane"]): r for r in rows}
    assert lanes[("prepare_compile", "nested")]["n"] == 1
    assert lanes[("prepare_trace", "nested")]["count"] == 2
    assert lanes[("resident_upload", "nested")]["nbytes"] == 1000
    assert lanes[("dispatch", "serial")]["total_ms"] == pytest.approx(4000.0)
    assert lanes[("host_augment", "overlap")]["overlap"] is True
    assert not any(r["overlap"] for r in rows if r["lane"] != "overlap")
    report = export.format_report(spill_spans)
    assert "= 100.0% of wall" in report
    assert "a nested span's time is its parent's" in report
    line = next(l for l in report.splitlines()
                if l.startswith("prepare_compile"))
    assert line.split()[1] == "nested"


def test_set_up_phases_lead_the_phase_order_and_the_export_names_them(
        spill_spans):
    order = export.PHASE_ORDER
    for phase in ("backend_start", "data_load", "model_init", "trainer_init",
                  "resident_upload") + PREPARE:
        assert order.index(phase) < order.index("epoch_setup")
    trace = export.to_trace_events(spill_spans)
    export.validate_trace_events(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    compiled = next(e for e in xs if e["name"] == "prepare_compile")
    assert compiled["args"]["name"] == "jit(step)"
    assert compiled["args"]["n"] == 1
    assert all("name" not in e["args"] for e in xs
               if not e["name"].startswith("prepare_"))


def test_name_is_spilled_only_where_given_and_t0_anchors_the_clock(tmp_path):
    spill = str(tmp_path / "spill.jsonl")
    now = time.monotonic()
    tr = SpanTracer(spill_path=spill, t0=now - 100.0)
    assert tr.t0 == now - 100.0
    tr.add_span("prepare_compile", now - 40.0, 1.5, n=0, name="jit(step)")
    tr.add_span("backend_start", now - 98.0, 2.0)
    with tr.span("dispatch", step=0):
        pass
    tr.close()
    spans = {s["phase"]: s for s in tr.spans_since(0.0)}
    assert spans["backend_start"]["start_s"] == pytest.approx(2.0)
    assert spans["prepare_compile"]["start_s"] == pytest.approx(60.0)
    assert spans["dispatch"]["start_s"] >= 100.0 and tr.now() >= 100.0
    assert spans["prepare_compile"]["name"] == "jit(step)"
    assert spans["dispatch"]["name"] is None
    lines = {l["phase"]: l for l in map(json.loads, open(spill))}
    assert lines["prepare_compile"]["name"] == "jit(step)"
    assert lines["prepare_compile"]["n"] == 0
    assert "name" not in lines["dispatch"] \
        and "name" not in lines["backend_start"]
    # The default is unchanged: construction time.
    assert SpanTracer().now() < 5.0
