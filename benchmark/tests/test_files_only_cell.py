"""A later PR's cell, added as files and entries only: a new
configuration, job mix and per-layer reader in a temporary copy of the
benchmark, one new entry each in BENCHMARK.json, no file that was there
edited — and the harness runs it."""
import hashlib
import json
import os
import shutil

from conftest import RESULT_KEYS, ROOT, run_cell


def _hashes(top):
    out = {}
    for d, _dirs, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_cell_added_as_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(os.path.join(root, "benchmark"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    bench = os.path.join(root, "benchmark")
    # 1. a configuration: its file of sizes (here: ResNet-18 with another
    # momentum), its plain reference beside it (the one it shares).
    with open(os.path.join(bench, "configs", "resnet18_cifar10.json")) as f:
        config = json.load(f)
    config["name"] = "resnet18_m08"
    config["optimizer"]["momentum"] = 0.8
    with open(os.path.join(bench, "configs", "resnet18_m08.json"), "w") as f:
        json.dump(config, f)
    # 2. a job mix: parameters only.
    with open(os.path.join(bench, "traffic", "stream_b3072.json")) as f:
        mix = json.load(f)
    mix["prefetch_depth"], mix["prefetch_workers"] = 1, 2
    with open(os.path.join(bench, "traffic", "stream_shallow.json"), "w") as f:
        json.dump(mix, f)
    # 3. a per-layer metric: a small reader of its own.
    with open(os.path.join(bench, "layer_metrics", "h2d_ms_per_step.py"),
              "w") as f:
        f.write('"""Median h2d span, consumer side."""\n'
                "import statistics\n\n\n"
                "def read(ctx):\n"
                "    d = [s['dur_s'] for s in ctx['spans']"
                " if s['phase'] == 'h2d']\n"
                "    return 1000.0 * statistics.median(d) if d else None\n")
    # 4. the entries.
    spec["configs"].append({
        "name": "resnet18_m08", "source": "https://arxiv.org/abs/1512.03385",
        "file": "benchmark/configs/resnet18_m08.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "resnet18_m08_stream", "config": "resnet18_m08",
        "traffic": "stream_shallow", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "h2d_ms_per_step", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "trainer loop",
        "moves": "train_samples_per_s_per_chip",
        "workloads": ["resnet18_m08_stream"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    rc, result, err = run_cell("resnet18_m08_stream", "--seconds", "1",
                               "--trace", "1", "--rehearse", root=root,
                               pythonpath=ROOT)
    assert rc == 0, err[-3000:]
    assert set(result) == RESULT_KEYS
    assert result["metrics"]["h2d_ms_per_step"]["unit"] == "ms"
    assert result["metrics"]["h2d_ms_per_step"]["value"] > 0
    # The cell's own metric list: the new one, the unrestricted ones, and
    # none that names other cells.
    assert "data_wait_pct" not in result["metrics"]
    assert "first_step_s" in result["metrics"]

    after = _hashes(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before  # nothing edited
    assert set(after) - set(before) == {
        os.path.join("configs", "resnet18_m08.json"),
        os.path.join("traffic", "stream_shallow.json"),
        os.path.join("layer_metrics", "h2d_ms_per_step.py")}
