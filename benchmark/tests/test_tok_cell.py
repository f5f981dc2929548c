"""The ``train_tok`` runner's own pieces, without a chip: the first-step
comparison (every limit the reference module's, the thin leaves by name,
every depth's logits) and the four readers of
``glm47_flash_train_8k_1chip`` on a made-up trace."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_glm4_moe_lite, reference_check_tok  # noqa: E402
from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from benchmark.run import resolve  # noqa: E402

CELL = "glm47_flash_train_8k_1chip"
NEW = ["mla_core_roofline_pct", "mla_proj_device_pct",
       "moe_gated_experts_roofline_pct", "mtp_device_pct"]


def read(name, ctx):
    return importlib.import_module("benchmark.layer_metrics." + name).read(ctx)


def ctx(scope_s, busy_s=2.0, assignments=8 * 5 * 8 * 1024):
    resolved = resolve(CELL, rehearse=False)
    return {"layers": ref.layer_shapes(resolved["config"]), "seq_len": 8192,
            "chips": 1, "peak": {"bf16_flops_per_s": 197e12},
            "trace": {"scope_s": scope_s, "busy_s": busy_s, "samples": 16,
                      "assignments": assignments}}


def test_the_cell_resolves_to_its_files_and_lists_its_metrics():
    resolved = resolve(CELL, rehearse=False)
    assert resolved["mix"]["runner"] == "train_tok"
    assert resolved["config"]["reference"] == "glm4_moe_lite"
    names = [m["name"] for m in resolved["per_layer"]]
    assert set(NEW) <= set(names)
    assert {"first_step_s", "compiles_in_window", "window_idle_pct",
            "peak_hbm_gb", "epoch_setup_ms", "epoch_close_ms",
            "host_untraced_pct"} <= set(names)
    # The mix is the other two token cells' traffic to the letter.
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "seq_stream_s8192_b2.json")) as f:
        other = json.load(f)
    mine = resolved["mix"]
    assert {k: v for k, v in mine.items() if k not in ("runner", "why")} \
        == {k: v for k, v in other.items() if k not in ("runner", "why")}


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_a_trace_or_without_scopes(name):
    assert read(name, {"trace": None}) is None
    assert read(name, ctx({"-": 1.0})) is None


def test_readers_on_a_made_up_trace():
    c = ctx({"mla_core": 2.0, "mla_proj": 1.0, "moe_experts": 1.6,
             "mtp": 0.08, "-": 3.32}, busy_s=8.0)
    dm = c["layers"]
    assert read("mla_proj_device_pct", c) == 12.5
    assert read("mtp_device_pct", c) == 1.0
    assert read("mla_core_roofline_pct", c) == pytest.approx(
        100 * flops_glm4_moe_lite.mla_core_train_flops(dm, 8192, 16)
        / 197e12 / 2.0)
    assert read("moe_gated_experts_roofline_pct", c) == pytest.approx(
        100 * flops_glm4_moe_lite.expert_train_flops(dm, 8 * 5 * 8 * 1024)
        / 197e12 / 1.6)
    assert 0 < read("mla_core_roofline_pct", c) < 100
    # A scope with no operation: shares read 0.0, rooflines nothing.
    c = ctx({"mla_proj": 1.0})
    assert read("mtp_device_pct", c) == 0.0
    assert read("mla_core_roofline_pct", c) is None
    assert read("moe_gated_experts_roofline_pct", c) is None


def _tree(scale=1.0):
    rng = np.random.default_rng(0)
    return {"layers": {"layer_01": {
        "router": scale * rng.standard_normal((8, 4)).astype(np.float32),
        "q_a": scale * rng.standard_normal((8, 3)).astype(np.float32),
        "gate": scale * rng.standard_normal((2, 8, 5)).astype(np.float32)}}}


def test_compare_holds_every_depth_and_the_thin_leaves_by_name():
    grads = _tree()
    logits = np.random.default_rng(1).standard_normal((2, 6, 9)).astype(
        np.float32)
    ref_side = {"loss": 3.0, "grads": grads, "logits0": logits}
    update = {"layers": {"layer_01": {k: -0.5 * v for k, v in
                                      grads["layers"]["layer_01"].items()}}}
    out = reference_check_tok.compare(
        ref_side, loss=3.0, logits0=logits, momentum=grads, update=update,
        lr=0.5, tolerance=ref.TOLERANCE, thin=ref.THIN_LEAVES)
    assert out["ok"] and set(out["errors"]) == set(ref.TOLERANCE)
    assert all(v == 0 for v in out["errors"].values())
    # A fault in depth 1's logits alone, and one in a thin leaf alone.
    bad = logits.copy()
    bad[1] *= 1.1
    out = reference_check_tok.compare(
        ref_side, loss=3.0, logits0=bad, momentum=grads, update=update,
        lr=0.5, tolerance=ref.TOLERANCE, thin=ref.THIN_LEAVES)
    assert not out["ok"] and out["errors"]["logits_rel"] == 0
    assert out["errors"]["logits_rel_d1"] == pytest.approx(0.1, rel=1e-3)
    moved = _tree()
    moved["layers"]["layer_01"]["router"] *= 1.2
    out = reference_check_tok.compare(
        ref_side, loss=3.0, logits0=logits, momentum=moved, update=update,
        lr=0.5, tolerance=ref.TOLERANCE, thin=ref.THIN_LEAVES, leaves=True)
    # Two thin leaves, one 0.2 off: their root mean square.
    assert out["errors"]["momentum_rel_thin"] == pytest.approx(
        0.2 / np.sqrt(2), rel=1e-3)
    assert out["info"]["momentum_rel_thin_leaf"][0].endswith("['router']")
    assert not out["ok"]
