"""Every per-layer reader of BENCHMARK.json on what the train runner
hands it, made here from the trace recorded on the chip (one step of the
VGG resident cell and the start of the next): each returns a number or
None, and the arithmetic of the ones that are not a plain ratio."""
import importlib
import json
import math
import os

import pytest

from benchmark import trace_reduce as tr
from conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
DATA = os.path.join(ROOT, "benchmark", "testdata")


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(DATA, "vgg_resident_v5e_100ms.json")) as f:
        trace = json.load(f)
    with open(os.path.join(DATA, "vgg_resident_v5e_hlo_excerpt.txt")) as f:
        reduced = tr.reduce(trace, hlo_texts=[f.read()])
    reduced.update(epochs=1, steps=1, samples=3072)
    reference = importlib.import_module("benchmark.reference.vgg_cifar10")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "vgg_cifar10.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    span = {"phase": "dispatch", "step": 0, "start_s": 0.0, "dur_s": 0.01,
            "overlap": False, "req": None}
    return {
        "chips": 1, "peak": peak, "layers": reference.layer_shapes(config),
        "steps_per_epoch": 17, "samples_per_epoch": 50000,
        "window_s": 1.0, "window_steps": 10,
        "spans": [span, dict(span, step=17, start_s=0.5),
                  dict(span, phase="data_wait", start_s=0.2)],
        "compiles_in_window": 0, "first_step_s": 17.0,
        "memory_peak_bytes": 5326609920,
        "table": {"rows": 50000, "row_elems": 3072}, "trace": reduced,
    }


def read_metric(name, c):
    return importlib.import_module("benchmark.layer_metrics." + name).read(c)


@pytest.fixture(scope="module")
def copy_free(ctx):
    """The same trace as a program that stopped copying its table would
    leave it: the three operations that write a whole copy taken out of
    ``ops`` (the relayout fix of ledger PR 25; the busy union stays)."""
    ops = ctx["trace"]["ops"]
    kept = {name: secs for name, secs in ops.items()
            if not tr.table_seconds({"ops": {name: secs}}, **ctx["table"])}
    assert len(ops) - len(kept) == 3
    return dict(ctx, trace=dict(ctx["trace"], ops=kept))


@pytest.mark.parametrize("which", ["ctx", "copy_free"])
@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_reader_reads_the_recorded_trace(request, which, name):
    c = request.getfixturevalue(which)
    value = read_metric(name, c)
    assert value is None or math.isfinite(value)
    declared = {m["name"]: m for m in SPEC["per_layer"]}[name]
    if declared["source"] == "device_trace":
        # A number with the table's copies and without them, but for the
        # collectives: one chip holds none.
        assert value is not None or name.startswith("collective_")
        # Without a trace (a rehearsal) a device reader reads nothing.
        assert read_metric(name, dict(c, trace=None)) is None


def test_a_table_never_copied_reads_zero_not_nothing(ctx, copy_free):
    assert read_metric("table_copy_pct", ctx) > 0
    assert read_metric("table_copy_pct", copy_free) == 0.0
    assert (read_metric("step_device_ex_table_ms", copy_free)
            == read_metric("step_device_ms", copy_free))
    assert math.isfinite(read_metric("nonconv_device_pct", copy_free))
    # None is for a missing input: no table, no trace, nothing ran.
    assert read_metric("table_copy_pct", dict(copy_free, table=None)) is None
    assert read_metric("table_copy_pct", dict(copy_free, trace=None)) is None
    assert read_metric("table_copy_pct", dict(copy_free, trace=dict(
        copy_free["trace"], busy_s=0.0))) is None


def test_the_runner_names_what_a_traced_line_lacks():
    from benchmark.runners.train import not_read_note
    wanted = [{"name": "step_device_ms"}, {"name": "table_copy_pct"}]
    got = {"step_device_ms": {"value": 78.0, "unit": "ms"}}
    assert not_read_note("a_cell", wanted, got) == (
        "benchmark: listed for a_cell, not read: table_copy_pct")
    got["table_copy_pct"] = {"value": 0.0, "unit": "%"}
    assert not_read_note("a_cell", wanted, got) is None


def test_table_and_window_arithmetic(ctx):
    def read(name, c=ctx):
        return read_metric(name, c)
    busy = ctx["trace"]["busy_s"]
    table = (582531 + 1398988 + 1372524) * 1e-9   # test_trace_reduce.py
    assert read("step_device_ms") == pytest.approx(1e3 * busy)
    assert read("step_device_ex_table_ms") == pytest.approx(
        1e3 * (busy - table))
    assert read("table_copy_pct") == pytest.approx(100 * table / busy)
    # Ten steps of the traced step's busy time in a window of 1 s.
    assert read("window_idle_pct") == pytest.approx(100 * (1 - 10 * busy))
    # Where batches stream there is no table: nothing to read, and the
    # non-convolution share is of all the operations' time.
    stream = dict(ctx, table=None)
    assert read("table_copy_pct", stream) is None
    assert read("step_device_ex_table_ms", stream) is None
    conv = tr.conv_seconds(ctx["trace"])
    total = sum(ctx["trace"]["ops"].values())
    assert read("nonconv_device_pct", stream) == pytest.approx(
        100 * (1 - conv / total))
    assert read("nonconv_device_pct") == pytest.approx(
        100 * (1 - conv / (total - table)))
