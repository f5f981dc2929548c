"""The benchmark's zero case, in tier-1: on a trace in which NO operation
writes a whole copy of the resident table, every ``device_trace`` metric
that ``BENCHMARK.json`` lists for a resident cell still gives a number.

The driver holds a traced line to every metric listed for its cell, so a
reader that returns None where the table's copies are gone refuses the
very change that removed them (ledger, PR 25; repaired by PR 26).  The
trace and the ``copy_free`` fixture are the benchmark's own
(``benchmark/tests/test_layer_metrics.py``, recorded on a v5e); this file
only takes them into the tier the driver runs.  No chip, no JAX backend.
"""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(ROOT, "benchmark", "tests")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _benchmark_test_module():
    """``benchmark/tests/test_layer_metrics.py`` says ``from conftest import
    ROOT`` and means its own directory's conftest, not this one's: lend it
    that name while it loads."""
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = _load(
        "benchmark_tests_conftest", os.path.join(BENCH_TESTS, "conftest.py"))
    try:
        return _load("benchmark_tests_layer_metrics",
                     os.path.join(BENCH_TESTS, "test_layer_metrics.py"))
    finally:
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours


_bench = _benchmark_test_module()
# Fixtures are found by name in the module that uses them.
ctx, copy_free, read_metric = _bench.ctx, _bench.copy_free, _bench.read_metric
SPEC = _bench.SPEC


def _resident_cells():
    cells = {}
    for w in SPEC["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            if json.load(f).get("resident"):
                cells[w["name"]] = w
    return cells


CELLS = _resident_cells()
# The recorded trace is one chip's: it holds no collective, and on four
# chips a collective reader's None is an input fault, not a zero.
CASES = [(cell, m["name"]) for m in SPEC["per_layer"]
         if m["source"] == "device_trace"
         and not m["name"].startswith("collective_")
         for cell in CELLS if cell in m.get("workloads", CELLS)]


def test_the_cases_cover_the_resident_cells():
    assert len(CELLS) >= 3
    for name in ("table_copy_pct", "step_device_ms",
                 "step_device_ex_table_ms"):
        assert {c for c, n in CASES if n == name} == set(CELLS)


@pytest.mark.parametrize("cell,name", CASES)
def test_copy_free_trace_reads_every_listed_device_metric(copy_free, cell,
                                                          name):
    c = dict(copy_free, cell=cell, chips=CELLS[cell]["chips"])
    value = read_metric(name, c)
    assert isinstance(value, float), (cell, name, value)
    if name == "table_copy_pct":
        assert value == 0.0
    if name == "step_device_ex_table_ms":
        assert value == read_metric("step_device_ms", c)
