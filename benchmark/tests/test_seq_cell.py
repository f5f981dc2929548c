"""The second token-trained cell (PR 33), added as files and entries
only: its six readers' no-input case, zero case and values; the
``train_seq`` runner rehearsing its own cell and, unchanged, the first
token model's configuration."""
import hashlib
import importlib
import json
import os
import shutil

import pytest

from conftest import RESULT_KEYS, ROOT, run_cell

CELL = "phi4_mini_flash_train_8k_1chip"
READERS = ["sel_scan_device_pct", "sel_scan_roofline_pct",
           "attn_window_roofline_pct", "attn_global_roofline_pct",
           "mlp_roofline_pct", "gmu_device_pct"]
ROOFLINES = [r for r in READERS if r.endswith("roofline_pct")]


def test_the_cell_was_added_as_files_and_entries_only():
    """Every file the benchmark had before PR 28 is as it was (the later
    ones: ``git diff 343cea7 -- benchmark`` lists additions alone), and
    the entries came last in their lists."""
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "files_before_pr28.json")) as f:
        before = json.load(f)["files"]
    for rel, sha in before.items():
        with open(os.path.join(ROOT, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == sha, rel
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert spec["workloads"][-1] == {
        "name": CELL, "config": "phi4_mini_flash_stage14_19",
        "traffic": "seq_stream_s8192_b2", "chips": 1,
        "why": spec["workloads"][-1]["why"]}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    assert [m["name"] for m in spec["per_layer"]][-6:] == READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_mfu_pct"
               and m["layer"] == "kernels" and m["source"] == "device_trace"
               for m in spec["per_layer"][-6:])
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      "seq_stream_s8192_b2.json")))
    first = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                        "lm_stream_s8192_b2.json")))
    # The first token cell's traffic, through the runner that reads a
    # configuration's own operation count.
    assert mix["runner"] == "train_seq"
    for key in ("resident", "batch_per_chip", "prefetch_depth",
                "prefetch_workers"):
        assert mix[key] == first[key]
    assert {k: v for k, v in mix["data"].items() if k != "stands_for"} \
        == {k: v for k, v in first["data"].items() if k != "stands_for"}


DM = {"d": 8, "d_inner": 16, "n": 4, "k": 4, "dt_rank": 1, "pairs": 2,
      "kv_pairs": 1, "hd": 4, "window": 4, "ff": 16, "vocab": 32,
      "kinds": ["mamba", "window", "full", "gmu", "cross"]}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def ctx(scope_s):
    return {"chips": 1, "peak": PEAK, "layers": DM, "seq_len": 16,
            "routing": {},
            "trace": None if scope_s is None else {
                "busy_s": 2.0, "steps": 4, "samples": 8,
                "scope_s": scope_s}}


def read(name, c):
    return importlib.import_module("benchmark.layer_metrics." + name).read(c)


@pytest.mark.parametrize("name", READERS)
def test_reader_with_no_input_reads_nothing(name):
    """A rehearsal (no trace), or a program without scopes (the parent's
    side of a traced run): None, and no error."""
    assert read(name, ctx(None)) is None
    assert read(name, ctx({"-": 2.0})) is None


@pytest.mark.parametrize("name", READERS)
def test_scope_reader_zero_case(name):
    """Scopes were found, this one has no operation: a share of busy time
    reads 0.0, a share of a roofline None (no kernel time)."""
    expect = None if name in ROOFLINES else 0.0
    assert read(name, ctx({"update": 1.0, "-": 1.0})) == expect


def test_readers_read():
    c = ctx({"sel_scan": 1.0, "attn_window": 0.125, "attn_full": 0.25,
             "attn_cross": 0.25, "mlp": 0.5, "gmu": 0.25, "-": 0.125})
    assert read("sel_scan_device_pct", c) == 50.0
    assert read("gmu_device_pct", c) == 12.5
    # 128 tokens, one Mamba layer: 3 passes x ((3 x 16 + 2 x 4) x 2 + 16 x
    # 4) bytes a token bound it (the operations: 3 x 2 x 3 x 16 x 4).
    assert read("sel_scan_roofline_pct", c) == pytest.approx(
        100 * (3 * 176 * 128 / 1e11) / 1.0)
    # A visible key costs a token 2 x 16 multiply-adds (pairs x 2 x hd =
    # 16 wide); window 4 of 16: (10 + 12 x 4) / 16 keys a query.
    assert read("attn_window_roofline_pct", c) == pytest.approx(
        100 * (3 * 2 * 32 * (58 / 16) * 16 * 8 / 1e12) / 0.125)
    # full and cross: 8.5 keys a query each, over both scopes' time.
    assert read("attn_global_roofline_pct", c) == pytest.approx(
        100 * (2 * 3 * 2 * 32 * 8.5 * 16 * 8 / 1e12) / 0.5)
    # five layers' (8 x 32 + 16 x 8) multiply-adds a token
    assert read("mlp_roofline_pct", c) == pytest.approx(
        100 * (5 * 3 * 2 * 384 * 128 / 1e12) / 0.5)
    # One of the two global scopes alone is read too.
    c["trace"]["scope_s"].pop("attn_cross")
    assert read("attn_global_roofline_pct", c) == pytest.approx(
        100 * (2 * 3 * 2 * 32 * 8.5 * 16 * 8 / 1e12) / 0.25)


def test_the_runner_runs_the_first_token_models_configuration(tmp_path):
    """``train_seq`` branches on no name: a copy of the benchmark in
    which the first token cell's mix names it as runner rehearses that
    cell with every check of ``train_lm``, "none dropped" among them."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "traffic", "lm_stream_s8192_b2.json")) as f:
        mix = json.load(f)
    mix["runner"] = "train_seq"
    with open(os.path.join(bench, "traffic", "lm_via_seq.json"), "w") as f:
        json.dump(mix, f)
    # The tiny preset is found by the runner's name: the first runner's
    # preset under the second's name, with the second's own beside it.
    with open(os.path.join(bench, "tests", "tiny", "train_lm.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(bench, "tests", "tiny", "train_seq.json")) as f:
        own = json.load(f)
    with open(os.path.join(bench, "tests", "tiny", "train_seq.json"),
              "w") as f:
        json.dump({"config": {**own["config"], **tiny["config"]},
                   "traffic": tiny["traffic"]}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({
        "name": "nemotron_via_seq", "config": "nemotron3_nano_30b_a3b_ep16",
        "traffic": "lm_via_seq", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    rc, result, err = run_cell("nemotron_via_seq", "--seconds", "1",
                               "--trace", "1", "--rehearse", root=root,
                               pythonpath=ROOT)
    assert rc == 0, err[-3000:]
    assert set(result) == RESULT_KEYS
    detail = json.loads([ln for ln in err.splitlines() if ln.startswith(
        "benchmark-detail: ")][-1].split(": ", 1)[1])
    assert all(detail["checks"].values()), detail["checks"]
    assert detail["checks"]["none_dropped"] is True
    # flops_seq.py's count, not this PR's: the configuration names none.
    from benchmark import flops_seq
    from benchmark.reference import nemotron_h
    with open(os.path.join(bench, "configs",
                           "nemotron3_nano_30b_a3b_ep16.json")) as f:
        config = {**json.load(f), **tiny["config"]}
    assert detail["flops_per_sample"] == flops_seq.train_flops_per_sequence(
        nemotron_h.layer_shapes(config), 256)
