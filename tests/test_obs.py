"""Telemetry subsystem (ddp_tpu/obs/): span tracer, Perfetto export,
live stats, straggler aggregation, and the CLI/e2e wiring — plus the
profiling edge cases the round-7 satellites name (attribute_streaming
clamping, categorize on full-definition-line op names)."""
import json
import os
import re
import threading
import time

import pytest

from ddp_tpu.obs import aggregate, export
from ddp_tpu.obs.live import LiveStats, model_mfu
from ddp_tpu.obs.tracer import (NullTracer, SpanTracer, get_tracer,
                                set_tracer)

# ---------------------------------------------------------------------------
# tracer


def test_tracer_records_spans_and_spills(tmp_path):
    spill = str(tmp_path / "spill.jsonl")
    tr = SpanTracer(spill_path=spill, host=3)
    with tr.span("dispatch", step=7):
        time.sleep(0.002)
    with tr.span("host_augment", step=8, overlap=True):
        pass
    tr.close()
    spans = tr.spans_since(0.0)
    assert [s["phase"] for s in spans] == ["dispatch", "host_augment"]
    assert spans[0]["step"] == 7 and spans[0]["dur_s"] >= 0.002
    assert spans[0]["overlap"] is False and spans[1]["overlap"] is True
    lines = [json.loads(l) for l in open(spill)]
    assert len(lines) == 2
    assert lines[0]["phase"] == "dispatch" and lines[0]["host"] == 3
    assert lines[1]["overlap"] is True


def test_tracer_aborted_span_not_recorded():
    """A span whose body raises never lands — which is what makes 'last
    completed span' the right stall diagnostic, and keeps the iterator-
    exhaustion StopIteration probe from leaving a bogus record."""
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("dispatch", step=0):
            raise RuntimeError("boom")
    assert tr.spans_since(0.0) == []
    assert tr.describe_last() == "no spans completed"


def test_tracer_ring_bounded_and_window():
    tr = SpanTracer(ring=8)
    for i in range(20):
        with tr.span("dispatch", step=i):
            pass
    spans = tr.spans_since(0.0)
    assert len(spans) == 8  # ring bound
    assert [s["step"] for s in spans] == list(range(12, 20))
    t_mid = tr.now()
    with tr.span("eval"):
        pass
    assert [s["phase"] for s in tr.spans_since(t_mid)] == ["eval"]
    last = tr.last_spans()
    assert last["dispatch"]["step"] == 19
    assert "eval" in tr.describe_last()


def test_tracer_thread_safety():
    tr = SpanTracer(ring=10_000)

    def work(tid):
        for i in range(200):
            with tr.span("host_augment", step=i, overlap=True):
                pass

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans_since(0.0)) == 800


def test_null_tracer_is_inert_and_default():
    null = NullTracer()
    with null.span("dispatch", step=1):
        pass
    assert null.spans_since(0.0) == [] and null.last_spans() == {}
    assert not null.enabled
    null.flush(fsync=True)
    null.close()
    # The process default is the NullTracer, and set/get round-trips.
    assert not get_tracer().enabled
    tr = SpanTracer()
    set_tracer(tr)
    try:
        assert get_tracer() is tr
    finally:
        set_tracer(None)
    assert not get_tracer().enabled


def test_tracer_counts_recorded_returned_and_spilled_only_when_set(tmp_path):
    """``n`` and ``nbytes`` ride a span into the ring, into what
    ``spans_since`` returns, and into the spill line — there only where
    the site gave them, so count-less lines stay as lean as before."""
    spill = str(tmp_path / "spill.jsonl")
    tr = SpanTracer(spill_path=spill)
    with tr.span("dispatch", step=3, n=512):
        pass
    with tr.span("h2d", step=3, nbytes=1_575_936):
        pass
    with tr.span("data_wait", step=3):
        pass
    with tr.span("epoch_setup", step=4) as sp:
        sp.count(nbytes=160)  # known only inside the body
    tr.add_span("loss_flush", time.monotonic(), 0.0, step=0, n=17)
    tr.close()
    got = {s["phase"]: (s["n"], s["nbytes"]) for s in tr.spans_since(0.0)}
    assert got == {"dispatch": (512, None), "h2d": (None, 1_575_936),
                   "data_wait": (None, None), "epoch_setup": (None, 160),
                   "loss_flush": (17, None)}
    assert tr.last_spans()["h2d"]["nbytes"] == 1_575_936
    lines = {l["phase"]: l for l in map(json.loads, open(spill))}
    assert lines["dispatch"]["n"] == 512 and "nbytes" not in lines["dispatch"]
    assert lines["h2d"]["nbytes"] == 1_575_936 and "n" not in lines["h2d"]
    assert lines["epoch_setup"]["nbytes"] == 160
    assert lines["loss_flush"]["n"] == 17
    assert set(lines["data_wait"]) == {"phase", "step", "start_s", "dur_s",
                                       "overlap", "host"}


def test_null_tracer_takes_counts_and_returns_the_shared_noop():
    """--obs_off: the counted call sites cost what the count-less ones
    did — the one shared no-op span, nothing allocated, nothing read."""
    null = NullTracer()
    plain = null.span("dispatch", step=1)
    counted = null.span("h2d", step=1, n=3, nbytes=4096)
    assert counted is plain
    with counted as sp:
        assert sp is plain
        sp.count(n=1, nbytes=2)
    assert plain.__enter__() is plain  # a hand-entered span, ended by hand
    plain.end()
    null.add_span("loss_flush", 0.0, 0.0, step=0, n=17, nbytes=68)
    assert null.spans_since(0.0) == []
    assert not hasattr(plain, "__dict__")  # count() kept nothing on it


def test_hand_entered_span_records_at_end():
    """A phase that opens in one function and closes in another
    (epoch_setup: opened by the trainer, ended by the prefetch engine)
    is entered by hand and lands when ``end()`` is called."""
    tr = SpanTracer()
    sp = tr.span("epoch_setup", step=5).__enter__()
    assert tr.spans_since(0.0) == []
    with tr.span("host_augment", step=5, overlap=True):
        pass
    sp.end()
    assert [(s["phase"], s["step"]) for s in tr.spans_since(0.0)] == [
        ("host_augment", 5), ("epoch_setup", 5)]


def _profiled_ddp_events(trace_dir):
    """``{name: [stats dict, ...]}`` of the ``ddp:`` host events in the
    newest profile under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ddp:"):
                    events.setdefault(ev.name, []).append(dict(ev.stats))
    return events


def test_spans_show_in_the_profiler_trace_one_dispatch_event_a_step(tmp_path):
    """While a jax.profiler session runs, every span of a SpanTracer
    built with no special argument is a host event ``ddp:<phase>`` of
    that trace, with ``step`` and the counts as its arguments: a real
    Trainer's two epochs leave one ``ddp:dispatch`` a step."""
    import jax

    from test_prefetch import tiny_trainer
    tr = SpanTracer(ring=1 << 20)  # as benchmark/runners/train.py builds it
    trainer, loader = tiny_trainer(tr, depth=2)
    trainer.train(1)  # compile outside the profiled stretch
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = tr.now()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        trainer.train(2)
    finally:
        jax.profiler.stop_trace()
    spans = tr.spans_since(t0)
    events = _profiled_ddp_events(str(tmp_path))
    steps = 2 * len(loader)
    dispatches = [s for s in spans if s["phase"] == "dispatch"]
    assert len(dispatches) == steps
    assert sorted(e["step"] for e in events["ddp:dispatch"]) == sorted(
        s["step"] for s in dispatches)
    assert sorted(e["n"] for e in events["ddp:dispatch"]) == sorted(
        s["n"] for s in dispatches)
    # Every phase the tracer timed through span() has its events, as
    # many as spans (add_span records an interval that is over: none).
    for phase in {s["phase"] for s in spans}:
        assert len(events["ddp:" + phase]) == sum(
            s["phase"] == phase for s in spans), phase
    assert all("nbytes" in e for e in events["ddp:h2d"])
    # Outside a session the same tracer leaves nothing behind and the
    # spans still land.
    with tr.span("dispatch", step=10**6, n=1):
        pass
    assert tr.last_spans()["dispatch"]["step"] == 10**6


# ---------------------------------------------------------------------------
# export / report


def _sample_spans():
    return [
        {"phase": "host_augment", "step": 0, "start_s": 0.0,
         "dur_s": 0.010, "overlap": True, "host": 0},
        {"phase": "data_wait", "step": 0, "start_s": 0.011,
         "dur_s": 0.001, "overlap": False, "host": 0},
        {"phase": "dispatch", "step": 0, "start_s": 0.012, "dur_s": 0.100,
         "overlap": False, "host": 0},
        {"phase": "dispatch", "step": 1, "start_s": 0.112, "dur_s": 0.300,
         "overlap": False, "host": 0},
        {"phase": "loss_flush", "step": 0, "start_s": 0.412, "dur_s": 0.05,
         "overlap": False, "host": 0},
        {"phase": "dispatch", "step": 2, "start_s": 0.1, "dur_s": 0.2,
         "overlap": False, "host": 1},
    ]


def test_to_trace_events_schema_and_tracks():
    trace = export.to_trace_events(_sample_spans())
    n = export.validate_trace_events(trace)
    assert n == len(trace["traceEvents"])
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 6
    # One process per host...
    assert {e["args"]["name"] for e in meta if e["name"] == "process_name"} \
        == {"host 0", "host 1"}
    # ...one named track per phase, same tid on every host.
    tid_by_name = {}
    for e in meta:
        if e["name"] == "thread_name":
            tid_by_name.setdefault(e["args"]["name"], set()).add(e["tid"])
    assert all(len(tids) == 1 for tids in tid_by_name.values())
    dispatch_tid = next(iter(tid_by_name["dispatch"]))
    assert all(e["tid"] == dispatch_tid for e in xs
               if e["name"] == "dispatch")
    # ts/dur in microseconds, step in args.
    d0 = next(e for e in xs if e["name"] == "dispatch" and e["pid"] == 0
              and e["args"]["step"] == 0)
    assert d0["ts"] == pytest.approx(0.012e6) and \
        d0["dur"] == pytest.approx(0.1e6)


def test_validate_trace_events_rejects_malformed():
    good = export.to_trace_events(_sample_spans())
    with pytest.raises(ValueError):
        export.validate_trace_events({"no": "traceEvents"})
    with pytest.raises(ValueError):
        export.validate_trace_events({"traceEvents": []})
    bad = json.loads(json.dumps(good))
    bad["traceEvents"][-1]["ts"] = -5.0
    with pytest.raises(ValueError, match="ts"):
        export.validate_trace_events(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["traceEvents"][0]["ph"] = "B"
    with pytest.raises(ValueError, match="ph"):
        export.validate_trace_events(bad2)


def test_read_spill_merges_and_skips_torn_tail(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text(json.dumps({"phase": "dispatch", "step": 0,
                             "start_s": 1.0, "dur_s": 0.1}) + "\n"
                 + '{"phase": "dispatch", "st')  # torn tail (SIGKILL)
    b.write_text(json.dumps({"phase": "eval", "step": None, "start_s": 0.5,
                             "dur_s": 0.2, "host": 1,
                             "overlap": False}) + "\n")
    spans = export.read_spill([str(a), str(b)])
    assert [s["phase"] for s in spans] == ["eval", "dispatch"]  # sorted
    assert spans[1]["host"] == 0 and spans[1]["overlap"] is False  # defaults


def test_phase_summary_separates_serial_from_overlap():
    rows, wall_s, critical_s = export.phase_summary(_sample_spans())
    by = {(r["phase"], r["overlap"]): r for r in rows}
    assert by[("host_augment", True)]["count"] == 1
    assert by[("dispatch", False)]["count"] == 3
    # Serial sum excludes the overlapped producer span.
    assert critical_s == pytest.approx(0.001 + 0.1 + 0.3 + 0.05 + 0.2)
    assert wall_s == pytest.approx(0.462)  # 0.0 .. 0.412+0.05


def test_phase_summary_sums_counts_and_report_shows_them_where_present():
    spans = [s for s in _sample_spans() if s["host"] == 0]
    rows, _, _ = export.phase_summary(spans)
    assert all(r["n"] is None and r["nbytes"] is None for r in rows)
    assert "items" not in export.format_report(spans)  # old spills: as before
    counted = [dict(s, n=512) if s["phase"] == "dispatch" else dict(s)
               for s in spans]
    counted += [{"phase": "h2d", "step": k, "start_s": 0.5 + k, "dur_s": 0.1,
                 "overlap": False, "host": 0, "nbytes": 2_000_000}
                for k in range(3)]
    rows, _, _ = export.phase_summary(counted)
    by = {r["phase"]: r for r in rows}
    assert (by["dispatch"]["n"], by["dispatch"]["nbytes"]) == (1024, None)
    assert (by["h2d"]["n"], by["h2d"]["nbytes"]) == (None, 6_000_000)
    assert by["data_wait"]["n"] is None
    report = export.format_report(counted)
    assert "items" in report and "MB" in report
    dispatch_line = next(l for l in report.splitlines()
                         if l.startswith("dispatch"))
    assert dispatch_line.split()[-2:] == ["1024", "-"]
    h2d_line = next(l for l in report.splitlines() if l.startswith("h2d"))
    assert h2d_line.split()[-2:] == ["-", "6.00"]
    # The Perfetto export carries the counts in a slice's args.
    xs = [e for e in export.to_trace_events(counted)["traceEvents"]
          if e["ph"] == "X"]
    assert {e["args"].get("n") for e in xs if e["name"] == "dispatch"} \
        == {512}
    assert all(e["args"]["nbytes"] == 2_000_000 for e in xs
               if e["name"] == "h2d")


def test_step_walls_and_slowest_steps():
    # Per-step grouping is a per-host operation (format_report filters by
    # host first — hosts have independent clocks and their serial lanes
    # each tile their own wall); loss_flush (boundary phase) and the
    # overlap host_augment span are excluded from the grouping.
    host0 = [s for s in _sample_spans() if s["host"] == 0]
    walls = export.step_walls(host0)
    assert walls[0]["total"] == pytest.approx(101.0)  # data_wait + dispatch
    assert walls[1]["total"] == pytest.approx(300.0)
    top = export.slowest_steps(host0, 2)
    assert [s for s, _ in top] == [1, 0]
    report = export.format_report(_sample_spans(), top=3, bins=4)
    assert "phase sum (serial lanes)" in report
    assert "slowest" in report and "histogram" in report
    # Multi-host spills report per host — no pooled double-counting.
    assert "=== host 0" in report and "=== host 1" in report


# ---------------------------------------------------------------------------
# profiling satellites: attribute_streaming edges + categorize bare names


def test_attribute_streaming_clamps_wall_below_floor():
    """Measurement noise can put the streaming wall BELOW the slowest
    isolated stage; the gap must clamp to 0 and efficiency cap at 1.0
    (a negative gap would mis-sum in trend consumers)."""
    from ddp_tpu.utils.profiling import attribute_streaming
    attr = attribute_streaming(1.0, 2.0, 210.0, 100.0)
    assert attr["bottleneck"] == "device_step_ms"
    assert attr["pipeline_floor_ms"] == 210.0
    assert attr["dispatch_gap_ms"] == 0.0
    assert attr["overlap_efficiency"] == 1.0
    # The normal case is unchanged.
    attr2 = attribute_streaming(1.0, 2.0, 100.0, 125.0)
    assert attr2["dispatch_gap_ms"] == pytest.approx(25.0)
    assert attr2["overlap_efficiency"] == pytest.approx(0.8)


def test_attribute_streaming_zero_wall():
    from ddp_tpu.utils.profiling import attribute_streaming
    attr = attribute_streaming(1.0, 2.0, 3.0, 0.0)
    assert attr["overlap_efficiency"] == 0.0
    assert attr["dispatch_gap_ms"] == 0.0
    assert attr["pipeline_floor_ms"] == 3.0


def test_categorize_full_definition_line_operand_pollution():
    """Full-definition-line op names: classification keys on the op's own
    bare name, never on operand names — a fusion CONSUMING a copy-done
    or a convolution operand is neither a copy nor a conv."""
    from ddp_tpu.utils.profiling import categorize
    ops = [
        ("%fusion.2 = (f32[128]) fusion(%copy-done.57, %convolution.3)",
         10.0, 1.0),
        ("%copy.9 = f32[8] copy(%fusion.4)", 4.0, 0.4),
        # conv_ops reclassification must also see the BARE name when the
        # trace hands back a full definition line.
        ("%fusion.164 = (f32[64]) fusion(%param.1)", 8.0, 0.8),
    ]
    conv_ops = {"fusion.164": "conv (fused, kind per HLO)"}
    got = {label: per for label, _, per in categorize(ops, conv_ops)}
    assert got["elementwise/reduction fusions"] == 1.0
    assert got["layout copies / bitcasts"] == 0.4
    assert got["conv (fused, kind per HLO)"] == 0.8


# ---------------------------------------------------------------------------
# live stats


class _FakeMetrics:
    def __init__(self):
        self.records = []

    def log_live(self, *, step, **fields):
        self.records.append({"step": step, **fields})


def test_live_stats_window_and_mfu():
    m = _FakeMetrics()
    live = LiveStats(m, global_batch=512, n_chips=1, log_every=4,
                     window=8, model="vgg", device_kind="TPU v5 lite")
    for i in range(8):
        live.step(0.100 if i != 5 else 0.500, step=i + 1)
    assert [r["step"] for r in m.records] == [4, 8]
    rec = m.records[-1]
    assert rec["step_ms_median"] == pytest.approx(100.0)
    assert rec["step_ms_p90"] == pytest.approx(500.0)
    assert rec["samples_per_sec"] == pytest.approx(5120.0)
    # MFU against the single-home FLOP/peak tables (obs/live.py).
    assert rec["mfu"] == pytest.approx(
        model_mfu(5120.0, "vgg", "TPU v5 lite"), abs=1e-3)
    # Unknown device kind -> no mfu field rather than a wrong one.
    m2 = _FakeMetrics()
    live2 = LiveStats(m2, global_batch=8, n_chips=1, log_every=1,
                      model="vgg", device_kind="CPU")
    live2.step(0.01, step=1)
    assert "mfu" not in m2.records[0]


def test_live_stats_prefetch_occupancy():
    from ddp_tpu.data import PrefetchStats
    m = _FakeMetrics()
    ps = PrefetchStats()
    live = LiveStats(m, global_batch=8, n_chips=2, log_every=2,
                     prefetch_stats=ps)
    ps._add("wait_s", 0.004)
    ps._add("host_s", 0.02)
    ps.count_batch()
    ps.count_batch()
    live.step(0.1, step=1)
    live.step(0.1, step=2)
    rec = m.records[0]
    assert rec["prefetch_wait_ms_per_step"] == pytest.approx(2.0)
    assert rec["prefetch_host_ms_per_step"] == pytest.approx(10.0)
    assert 0.0 <= rec["prefetch_occupancy"] <= 1.0
    # Differential sampling: a second window with no new waits is clean.
    live.step(0.1, step=3)
    live.step(0.1, step=4)
    assert m.records[1]["prefetch_occupancy"] == 1.0


def test_step_walls_replay_latest_trajectory_wins():
    """--on_nan restore replays steps under the same global ids; the
    per-step report must describe the latest trajectory, not sum both
    into a fake 2x straggler."""
    spans = [
        {"phase": "h2d", "step": 5, "start_s": 0.9, "dur_s": 0.004,
         "overlap": False, "host": 0},
        {"phase": "dispatch", "step": 5, "start_s": 1.0, "dur_s": 0.100,
         "overlap": False, "host": 0},
        # ... restore rewinds; step 5 replays (same phases, new times):
        {"phase": "h2d", "step": 5, "start_s": 8.9, "dur_s": 0.002,
         "overlap": False, "host": 0},
        {"phase": "dispatch", "step": 5, "start_s": 9.0, "dur_s": 0.150,
         "overlap": False, "host": 0},
    ]
    walls = export.step_walls(spans)
    # The replayed trajectory only — not old+new summed (254 ms).
    assert walls[5]["total"] == pytest.approx(152.0)
    assert walls[5]["dispatch"] == pytest.approx(150.0)
    assert walls[5]["h2d"] == pytest.approx(2.0)


def test_threaded_prefetch_no_phantom_sentinel_span():
    """The threaded engine's final queue get returns the end-of-stream
    sentinel, not a batch — it must not record a data_wait span numbered
    as the NEXT epoch's first step (it would double-count into that step
    in the per-step reports)."""
    import numpy as np

    from ddp_tpu.data.prefetch import prefetch_to_device
    from ddp_tpu.parallel import make_mesh

    mesh = make_mesh(1)
    batches = iter([{"image": np.zeros((1, 2, 2, 3), np.float32),
                     "label": np.zeros((1,), np.int32)} for _ in range(3)])
    tr = SpanTracer()
    out = list(prefetch_to_device(batches, mesh, depth=2,
                                  shard_fn=lambda b, m: b, tracer=tr,
                                  step0=10))
    assert len(out) == 3
    waits = [s for s in tr.spans_since(0.0) if s["phase"] == "data_wait"]
    assert [s["step"] for s in waits] == [10, 11, 12]  # no step-13 phantom


# ---------------------------------------------------------------------------
# aggregation


def test_phase_medians_and_straggler_report():
    spans = [{"phase": "dispatch", "dur_s": d / 1e3} for d in (10, 20, 30)]
    spans += [{"phase": "h2d", "dur_s": 0.004}]
    med = aggregate.phase_medians(spans)
    assert med["dispatch"] == pytest.approx(20.0)
    assert med["h2d"] == pytest.approx(4.0)
    report = aggregate.straggler_report(med)  # single-host identity
    assert report["dispatch"] == {"slowest_host": 0, "slowest_ms": 20.0,
                                  "median_ms": 20.0, "skew_pct": 0.0}
    assert "eval" not in report  # untimed phases omitted
    # Record shape survives the tracer round trip.
    tr = SpanTracer()
    with tr.span("dispatch", step=0):
        pass
    rec = aggregate.epoch_straggler_record(tr, None, 0.0,
                                           metrics=None, epoch=0)
    assert set(rec) == {"dispatch"}
    assert aggregate.epoch_straggler_record(NullTracer(), None, 0.0) is None


# ---------------------------------------------------------------------------
# watchdog stall context


def test_watchdog_stall_report_includes_last_spans(capsys):
    from ddp_tpu.resilience.watchdog import Watchdog
    fired = []
    wd = Watchdog(0.2, tag="obs-unit",
                  context=lambda: "dispatch[step 41] ended @1.0s")
    wd._exit = fired.append  # seam: don't kill pytest
    wd.start()
    try:
        time.sleep(0.2 * 4)
    finally:
        wd.stop()
    assert fired == [124]
    err = capsys.readouterr().err
    assert "last completed spans on this host" in err
    assert "dispatch[step 41]" in err


# ---------------------------------------------------------------------------
# e2e: the CLI wiring, the obs CLI, and the --obs_off kill-switch


_E2E_ARGV = ["2", "1", "--batch_size", "8", "--synthetic", "--model",
             "deepnn", "--lr", "0.02", "--num_devices", "2",
             "--synthetic_size", "64", "--metrics_path", "m.jsonl",
             "--log_every", "2"]


def test_cli_default_run_spills_and_reports(tmp_path, capsys, monkeypatch):
    """The acceptance loop: a default-flag run produces a spill file;
    ``python -m ddp_tpu.obs`` renders the phase table with a sane
    serial-sum-vs-wall identity; the Perfetto export schema-validates;
    live records carry the prefetch occupancy (satellite: PrefetchStats
    no longer dies with the engine object); each epoch logs a
    phase_stragglers record."""
    from ddp_tpu import cli
    from ddp_tpu.obs.__main__ import main as obs_main

    monkeypatch.chdir(tmp_path)
    args = cli.build_parser("t").parse_args(_E2E_ARGV)
    cli.run(args, num_devices=None)
    capsys.readouterr()
    assert (tmp_path / "trace_spill.jsonl").exists()
    # The run restored the process default tracer on exit.
    assert not get_tracer().enabled
    # The spill reads in process age, set-up first: cli.run's three
    # phases in order, then trainer_init, all at positive starts, and
    # JAX's preparations with their names (obs/startup.py).
    spill = export.read_spill(["trace_spill.jsonl"])
    first = {}
    for s in spill:
        first.setdefault(s["phase"], s)
    order = ["backend_start", "data_load", "model_init", "trainer_init",
             "epoch_setup"]
    starts = [first[p]["start_s"] for p in order]
    assert starts == sorted(starts) and starts[0] > 0.0
    assert first["prepare_compile"]["name"] \
        and first["prepare_compile"]["n"] in (0, 1)

    # Metrics stream: live records with prefetch occupancy + stragglers.
    recs = [json.loads(l) for l in open("m.jsonl")]
    live = [r for r in recs if r.get("event") == "live"]
    assert live, "no live records despite --log_every"
    assert all("step_ms_median" in r and "samples_per_sec" in r
               for r in live)
    assert any("prefetch_occupancy" in r and
               "prefetch_wait_ms_per_step" in r for r in live)
    stragglers = [r for r in recs if r.get("event") == "phase_stragglers"]
    assert [r["epoch"] for r in stragglers] == [0, 1]
    assert "dispatch" in stragglers[0]["phases"]
    # wall_s rides on every record (the shared monotonic clock).
    assert all("wall_s" in r for r in recs)

    # End-of-run Prometheus scrape file next to the metrics JSONL: the
    # run's registry exposition, strict-parseable, with the prefetch
    # occupancy counters mirrored from PrefetchStats.
    from ddp_tpu.obs.registry import parse_exposition
    fams = parse_exposition(open("m.jsonl.prom").read())
    assert fams["ddp_prefetch_batches_total"]["samples"][
        ("ddp_prefetch_batches_total", ())] > 0
    assert fams["ddp_prefetch_host_seconds_total"]["samples"][
        ("ddp_prefetch_host_seconds_total", ())] >= 0
    assert "ddp_guard_decisions_total" in fams

    # The obs CLI: phase table + histogram + slowest-K + Perfetto export.
    rc = obs_main(["trace_spill.jsonl", "--perfetto", "trace.json",
                   "--top", "3"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dispatch" in out and "slowest" in out
    m = re.search(r"phase sum \(serial lanes\): ([0-9.]+) ms = "
                  r"([0-9.]+)% of wall", out)
    assert m, out
    # The identity the acceptance pins at within-10% on a quiet box;
    # loose bounds here to keep CI noise-immune.
    assert 50.0 <= float(m.group(2)) <= 120.0
    n = export.validate_trace_events(json.load(open("trace.json")))
    assert n > 0


def test_default_spill_path_anchors_on_snapshot_dir():
    """The unset-default resolver: spills land next to the checkpoint
    head; a bare head (CWD run) keeps the bare name; explicit paths are
    the caller's problem and never pass through here."""
    from ddp_tpu.obs.tracer import default_spill_path

    assert default_spill_path("run/ckpt.pt", "trace_spill.jsonl") == \
        os.path.join("run", "trace_spill.jsonl")
    assert default_spill_path("/a/b/ckpt.pt", "serve_spill.jsonl") == \
        "/a/b/serve_spill.jsonl"
    assert default_spill_path("checkpoint.pt", "trace_spill.jsonl") == \
        "trace_spill.jsonl"


def test_default_spill_lands_in_run_dir_not_cwd(tmp_path, capsys,
                                                monkeypatch):
    """Regression pin (a repo-root trace_spill.jsonl once got committed):
    a run with --snapshot_path pointing into a run directory and NO
    --trace_spill flag must spill there, not into whatever directory the
    CLI launched from."""
    from ddp_tpu import cli

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    cwd = tmp_path / "cwd"
    cwd.mkdir()
    monkeypatch.chdir(cwd)
    args = cli.build_parser("t").parse_args(
        ["1", "1", "--batch_size", "8", "--synthetic", "--model",
         "deepnn", "--num_devices", "2", "--synthetic_size", "32",
         "--metrics_path", str(run_dir / "m.jsonl"),
         "--snapshot_path", str(run_dir / "ckpt.pt")])
    cli.run(args, num_devices=None)
    capsys.readouterr()
    assert (run_dir / "trace_spill.jsonl").exists()
    assert not (cwd / "trace_spill.jsonl").exists()
    # The serve CLI resolves its default the same way (unset default is
    # None → anchored on the snapshot dir at runtime).
    from ddp_tpu.serve.__main__ import build_parser as serve_parser
    assert serve_parser().parse_args([]).trace_spill is None


def test_cli_obs_off_emits_nothing(tmp_path, capsys, monkeypatch):
    """--obs_off is a true kill-switch: no spill file, no live records,
    no straggler events — the metrics loss stream itself stays."""
    from ddp_tpu import cli

    monkeypatch.chdir(tmp_path)
    # A stale spill from an earlier traced run must not survive an
    # --obs_off run — the obs CLI would silently report the wrong run.
    (tmp_path / "trace_spill.jsonl").write_text('{"stale": true}\n')
    args = cli.build_parser("t").parse_args(_E2E_ARGV + ["--obs_off"])
    cli.run(args, num_devices=None)
    capsys.readouterr()
    assert not (tmp_path / "trace_spill.jsonl").exists()
    recs = [json.loads(l) for l in open("m.jsonl")]
    assert not any(r.get("event") in ("live", "phase_stragglers")
                   for r in recs)
    assert any("loss" in r for r in recs)  # the loss stream is untouched


# ---------------------------------------------------------------------------
# request-scoped tracing: flow events, chains, the --requests view


def _serve_spans_with_retry():
    """A two-request serve spill shaped like the chaos drill: q1's first
    routing attempt dies with the replica (retry span), the retry lands
    on the post-swap replica's batch (global seq 9) — so its chain must
    connect across hosts.  q2 is a boring one-hop request."""
    def sp(phase, start, dur, host, step=None, req=None, overlap=False):
        return {"phase": phase, "start_s": start, "dur_s": dur,
                "host": host, "step": step, "req": req,
                "overlap": overlap}
    return [
        # q1: route -> crash observed -> retry -> queue_wait on the
        # replacement replica -> that batch's engine stages (step 9).
        sp("route", 0.000, 0.300, 0, req="q1", overlap=True),
        sp("retry", 0.050, 0.001, 0, req="q1", overlap=True),
        sp("queue_wait", 0.060, 0.030, 1, step=9, req="q1"),
        sp("batch_form", 0.090, 0.002, 1, step=9),
        sp("pad", 0.092, 0.001, 1, step=9),
        sp("h2d", 0.093, 0.002, 1, step=9),
        sp("forward", 0.095, 0.080, 1, step=9),
        sp("d2h", 0.175, 0.002, 1, step=9),
        # q2: single-hop on the original replica (batch step 5).
        sp("route", 0.010, 0.040, 0, req="q2", overlap=True),
        sp("queue_wait", 0.012, 0.005, 0, step=5, req="q2"),
        sp("batch_form", 0.017, 0.001, 0, step=5),
        sp("forward", 0.018, 0.020, 0, step=5),
    ]


def test_request_chain_joins_engine_stages_across_replicas():
    chains = export.request_chains(_serve_spans_with_retry())
    assert set(chains) == {"q1", "q2"}
    q1 = [s["phase"] for s in chains["q1"]]
    # The chain has q1's own spans plus step 9's engine stages — and
    # nothing from step 5 (q2's batch).
    assert q1 == ["route", "retry", "queue_wait", "batch_form", "pad",
                  "h2d", "forward", "d2h"]
    assert {s["host"] for s in chains["q1"]} == {0, 1}
    assert [s["phase"] for s in chains["q2"]] == [
        "route", "queue_wait", "batch_form", "forward"]


def test_flow_events_render_request_as_one_connected_chain():
    """The acceptance shape: a crash->retry->hot-swap request exports as
    ONE Perfetto flow (s -> t... -> f sharing an id), each flow event
    bound to its slice (same pid/tid, ts at the slice midpoint)."""
    spans = _serve_spans_with_retry()
    trace = export.to_trace_events(spans)
    assert export.validate_trace_events(trace) > 0
    flows = [e for e in trace["traceEvents"]
             if e.get("ph") in ("s", "t", "f")]
    by_name = {}
    for e in flows:
        by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) == {"req q1", "req q2"}
    for name, chain in by_name.items():
        assert len({e["id"] for e in chain}) == 1  # one flow id
        assert chain[0]["ph"] == "s" and chain[-1]["ph"] == "f"
        assert all(e["ph"] == "t" for e in chain[1:-1])
        assert chain[-1]["bp"] == "e"
    # q1's chain spans both replica processes and covers every hop.
    q1 = by_name["req q1"]
    assert len(q1) == 8 and {e["pid"] for e in q1} == {0, 1}
    # Each flow event binds inside its slice: a matching X slice exists
    # on the same pid/tid whose [ts, ts+dur] contains the flow ts.
    slices = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    for e in flows:
        assert any(s["pid"] == e["pid"] and s["tid"] == e["tid"]
                   and s["ts"] <= e["ts"] <= s["ts"] + s["dur"]
                   for s in slices), f"unbound flow event {e}"


def test_request_flows_totals_retries_and_report():
    spans = _serve_spans_with_retry()
    flows = export.request_flows(spans)
    q1 = flows["q1"]
    assert q1["retries"] == 1 and q1["batch_steps"] == [9]
    assert q1["total_ms"] == pytest.approx(300.0)  # 0.000 -> 0.300
    assert flows["q2"]["retries"] == 0
    assert flows["q2"]["batch_steps"] == [5]
    # Slowest-first ordering and the per-hop text breakdown.
    assert [r for r, _ in export.slowest_requests(spans, 5)] == [
        "q1", "q2"]
    rep = export.format_requests_report(spans, top=5)
    assert "q1" in rep and "1 retries" in rep
    assert "retry" in rep and "forward" in rep and "@9" in rep
    # A train spill has no request ids — the report says so.
    assert "no request-scoped spans" in export.format_requests_report(
        _sample_spans())


# ---------------------------------------------------------------------------
# python -m ddp_tpu.obs: exit-2 diagnoses, --requests, --ledger


def _write_spill(path, spans):
    with open(path, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")


def test_obs_main_diagnoses_unusable_spills(tmp_path, capsys):
    from ddp_tpu.obs.__main__ import main as obs_main
    # Missing file.
    assert obs_main([str(tmp_path / "nope.jsonl")]) == 2
    assert "cannot read spill" in capsys.readouterr().err
    # Empty spill.
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert obs_main([empty]) == 2
    assert "no spans" in capsys.readouterr().err
    # Mixed train+serve concatenation.
    mixed = str(tmp_path / "mixed.jsonl")
    _write_spill(mixed, _sample_spans() + _serve_spans_with_retry())
    assert obs_main([mixed]) == 2
    assert "mixed train+serve" in capsys.readouterr().err


def test_obs_main_requests_view(tmp_path, capsys):
    from ddp_tpu.obs.__main__ import main as obs_main
    spill = str(tmp_path / "serve.jsonl")
    _write_spill(spill, _serve_spans_with_retry())
    assert obs_main([spill, "--requests"]) == 0
    out = capsys.readouterr().out
    assert "2 request(s)" in out and "q1" in out
    assert obs_main([spill, "--requests", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["q1"]["retries"] == 1


def test_obs_main_ledger_join(tmp_path, capsys):
    from ddp_tpu.obs.__main__ import main as obs_main
    spill = str(tmp_path / "train.jsonl")
    _write_spill(spill, _sample_spans())
    calib = str(tmp_path / "calib.json")
    with open(calib, "w") as f:
        json.dump({"predicted_ms_per_step": {"train_step@dp8": 50.0,
                                             "train_step@accum": 1.0},
                   "coefficients": {"c_flop": 1e-12}}, f)
    assert obs_main([spill, "--ledger", calib, "--ledger_scale", "2",
                     "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    row = {r["phase"]: r for r in doc["rows"]}["dispatch"]
    # The @dp variant wins over @accum.  Each host's first dispatch span
    # is the compile-paying call and is split out: host0's first is
    # 100 ms and host1's ONLY span (200 ms) is its first, so
    # first_call_ms = median(100, 200) = 150, and the steady-state
    # median is the remaining 300 ms vs 50 ms predicted x2 scale ->
    # +200% gap.
    assert row["program"] == "train_step@dp8"
    assert row["predicted_ms"] == pytest.approx(100.0)
    assert row["measured_ms"] == pytest.approx(300.0)
    assert row["gap_pct"] == pytest.approx(200.0)
    assert row["first_call_ms"] == pytest.approx(150.0)
    assert "first_call_only" not in row
    # Unpriced phases get the same first-call split (data_wait ran once,
    # so its first call is its measurement).
    dw = {r["phase"]: r for r in doc["unpriced"]}["data_wait"]
    assert dw["first_call_ms"] == pytest.approx(dw["measured_ms"])
    # (>1 is possible here: the sample spill is two hosts whose serial
    # lanes each tile their own wall, merged onto one clock.)
    assert doc["pred_scale"] == 2.0 and doc["serial_coverage"] > 0
    # Host-side phases the model can't price are listed, not dropped.
    assert "data_wait" in {r["phase"] for r in doc["unpriced"]}
    # A calibration record without predictions is an exit-2 diagnosis.
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"coefficients": {}}, f)
    assert obs_main([spill, "--ledger", bad]) == 2
    assert "predicted_ms_per_step" in capsys.readouterr().err
