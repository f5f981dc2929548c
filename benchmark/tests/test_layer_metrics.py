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


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_reader_reads_the_recorded_trace(ctx, name):
    read = importlib.import_module("benchmark.layer_metrics." + name).read
    value = read(ctx)
    assert value is None or math.isfinite(value)
    # Without a trace (a rehearsal) a device reader reads nothing.
    declared = {m["name"]: m for m in SPEC["per_layer"]}[name]
    if declared["source"] == "device_trace":
        assert value is not None or name.startswith("collective_")
        assert read(dict(ctx, trace=None)) is None


def test_table_and_window_arithmetic(ctx):
    def read(name, c=ctx):
        return importlib.import_module(
            "benchmark.layer_metrics." + name).read(c)
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
