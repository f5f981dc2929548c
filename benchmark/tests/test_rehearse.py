"""Every cell end to end at the tiny preset, as the driver starts it."""
import json
import os

import pytest

from conftest import DEVICE_KEYS, RESULT_KEYS, ROOT, run_cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = SPEC["workloads"]


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contract_line(cell, trace):
    rc, result, err = run_cell(cell["name"], "--seconds", "1", "--trace",
                               trace, "--rehearse", chips=cell["chips"])
    assert rc == 0, err[-3000:]
    # Exactly the contract's keys; a CPU trace has no device planes, so
    # no breakdown and no busy time.
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cell["chips"]
    assert result["correct"] is False  # a rehearsal is never a result
    assert result["attempted"] > 0 and result["failed"] == 0
    # The program's checks all passed all the same.
    detail = json.loads([ln for ln in err.splitlines() if ln.startswith(
        "benchmark-detail: ")][-1].split(": ", 1)[1])
    assert all(detail["checks"].values()), detail["checks"]
    names = set(result["metrics"])
    declared = {m["name"]: m for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float)
    if trace == "0":
        assert names == {"setup_s"}  # a rate needs the chip
    else:
        # Only what the host clock, the spans and the counters give.
        assert {"first_step_s", "compiles_in_window"} <= names
        assert all(declared[n]["source"] != "device_trace" for n in names)
        assert "peak_hbm_gb" not in names
        assert result["metrics"]["compiles_in_window"]["value"] == 0.0


def test_no_tpu_is_an_error_without_rehearse():
    rc, result, err = run_cell(CELLS[0]["name"], "--seconds", "1",
                               "--trace", "0")
    assert rc != 0 and result is None
    assert "no TPU" in err


def test_too_few_devices_is_an_error():
    four = [c for c in CELLS if c["chips"] == 4][0]
    rc, result, err = run_cell(four["name"], "--seconds", "1", "--trace",
                               "0", "--rehearse", chips=1)
    assert rc != 0 and result is None
    assert "needs 4 device" in err
