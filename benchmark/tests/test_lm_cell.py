"""The token-trained cell (PR 28): added as files only; its trace
reduction by the program's scopes on a hand-made compiled text; each of
its readers' zero case and no-input case."""
import hashlib
import importlib
import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark import scope_reduce

CELL = "nemotron3_nano_train_8k_1chip"
READERS = ["lm_step_device_ms", "ssm_scan_device_pct",
           "ssm_scan_roofline_pct", "moe_experts_roofline_pct",
           "moe_route_device_pct", "attn_core_roofline_pct",
           "moe_load_max_over_mean", "moe_dropped_pct"]


def test_the_cell_was_added_as_files_only():
    with open(os.path.join(ROOT, "benchmark", "testdata",
                           "files_before_pr28.json")) as f:
        before = json.load(f)["files"]
    for rel, sha in before.items():
        with open(os.path.join(ROOT, rel), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == sha, rel
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [w["name"] for w in spec["workloads"]][-1] == CELL
    assert len(spec["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    listed = [m["name"] for m in spec["per_layer"]
              if m.get("workloads") == [CELL]]
    assert listed == READERS
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      "lm_stream_s8192_b2.json")))
    assert (mix["batch_per_chip"], mix["resident"], mix["prefetch_depth"],
            mix["prefetch_workers"]) == (2, False, 2, 4)
    assert mix["data"] | {"stands_for": ""} == {
        "n_train": 16, "seq_len": 8192, "zipf_exponent": 1.0,
        "successor_p": 0.75, "successor": [31, 7], "stands_for": ""}


with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3_nano_30b_a3b_ep16.json")) as _f:
    SCOPES = json.load(_f)["scopes"]  # the configuration lists them

HLO = """HloModule jit_step

%fused_scan (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%p0), metadata={op_name="jit(step)/jvp(ssm_scan)/exp"}
  ROOT %mul.1 = f32[8]{0} multiply(%exp.1, %p0), metadata={op_name="jit(step)/jvp(ssm_scan)/mul"}
}

%fused_mixed (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %a.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/moe_route/add"}
  %a.2 = f32[8]{0} add(%a.1, %p0), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/moe_route/add"}
  ROOT %a.3 = f32[8]{0} add(%a.2, %p0), metadata={op_name="jit(step)/jvp(moe_shared)/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_scan, metadata={op_name="jit(step)/jvp(ssm_scan)/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(step)/jvp(moe_shared)/add"}
  %while.3 = bf16[64,8]{1,0} while(%fusion.2), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(moe_experts)/while"}
  %sub.4 = f32[8]{0} subtract(%fusion.2, %x), metadata={op_name="jit(step)/update/sub"}
  ROOT %copy.5 = f32[8]{0} copy(%sub.4), metadata={op_name="jit(step)/copy"}
}
"""


def test_scope_reduce_on_a_hand_made_text():
    keys = scope_reduce.scope_keys_from_hlo([HLO], SCOPES)
    by_name = {k[0].split(" = ")[0].lstrip("%"): v for k, v in keys.items()}
    assert by_name["fusion.1"] == "ssm_scan"
    # A fusion belongs to the scope most of its instructions carry, not to
    # the one its root (and so its own metadata) names.
    assert by_name["fusion.2"] == "moe_route"
    assert by_name["while.3"] == "moe_experts"
    assert by_name["sub.4"] == "update"
    assert "copy.5" not in by_name
    # Trace events carry the whole instruction, operands with shapes.
    ops = {
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, "
        "calls=%fused_scan": 0.5,
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, "
        "calls=%fused_mixed": 0.25,
        "%copy.5 = f32[8]{0} copy(f32[8]{0} %sub.4)": 0.125,
    }
    assert scope_reduce.scope_seconds(ops, keys) == {
        "ssm_scan": 0.5, "moe_route": 0.25, "-": 0.125}
    assert scope_reduce.breakdown(ops, keys)[0] == [
        "ssm_scan/fusion.1 kLoop f32[8]", 0.5]
    assert scope_reduce.scope_of(
        "jit(f)/transpose(jvp(attn_core))/attn_proj/dot",
        SCOPES) == "attn_proj"
    assert scope_reduce.scope_of("jit(f)/my_ssm_scan_x/dot", SCOPES) is None


DM = {"pattern": "M*E", "d": 8, "h": 2, "p": 4, "g": 1, "n": 4,
      "d_inner": 8, "conv_dim": 16, "k": 4, "heads": 2, "kv_heads": 1,
      "head_dim": 4, "router": 4, "first": 0, "count": 2, "top_k": 2,
      "expert": 8, "shared": 16, "vocab": 32}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def ctx(scope_s, routing):
    return {"chips": 1, "peak": PEAK, "layers": DM, "seq_len": 16,
            "routing": routing,
            "trace": None if scope_s is None else {
                "busy_s": 2.0, "steps": 4, "samples": 8, "assignments": 64,
                "scope_s": scope_s}}


def read(name, c):
    return importlib.import_module("benchmark.layer_metrics." + name).read(c)


@pytest.mark.parametrize("name", READERS)
def test_reader_with_no_input_reads_nothing(name):
    """A rehearsal (no trace), or a program without scopes or counters:
    None, and no error."""
    assert read(name, ctx(None, None)) is None
    if name != "lm_step_device_ms" and not name.startswith("moe_load") \
            and name != "moe_dropped_pct":
        assert read(name, ctx({"-": 2.0}, None)) is None


@pytest.mark.parametrize("name,expect", [
    ("ssm_scan_device_pct", 0.0), ("moe_route_device_pct", 0.0),
    # a share of a roofline reads None with no kernel time, never 0
    ("ssm_scan_roofline_pct", None), ("moe_experts_roofline_pct", None),
    ("attn_core_roofline_pct", None)])
def test_scope_reader_zero_case(name, expect):
    """Scopes were found, this one has no operation."""
    assert read(name, ctx({"update": 1.0, "-": 1.0}, None)) == expect


def test_readers_read():
    routing = {"layer_01": {"assignments": np.array([30, 10]), "dropped": 0},
               "layer_02": {"assignments": np.array([5, 5]), "dropped": 0}}
    c = ctx({"ssm_scan": 1.0, "moe_route": 0.5, "moe_experts": 0.25,
             "attn_core": 0.125, "-": 0.125}, routing)
    assert read("lm_step_device_ms", c) == 500.0
    assert read("ssm_scan_device_pct", c) == 50.0
    assert read("moe_route_device_pct", c) == 25.0
    assert read("moe_load_max_over_mean", c) == 1.5
    assert read("moe_dropped_pct", c) == 0.0
    # 128 tokens: 3 passes x 2 x (3 x 2 x 4 x 4 state MACs), and
    # 3 x ((3 x 8 + 2 x 4) x 2 + 2 x 4) bytes a token: bytes bound it.
    assert read("ssm_scan_roofline_pct", c) == pytest.approx(
        100 * (3 * 72 * 128 / 1e11) / 1.0)
    assert read("moe_experts_roofline_pct", c) == pytest.approx(
        100 * (3 * 2 * 2 * 8 * 8 * 64 / 1e12) / 0.25)
    assert read("attn_core_roofline_pct", c) == pytest.approx(
        100 * (3 * 2 * (2 * 2 * 4 * 8) * 16 * 8 / 1e12) / 0.125)
    routing["layer_02"]["dropped"] = 10
    assert read("moe_dropped_pct", c) == pytest.approx(100 * 10 / 60)


def _trees(scale_leaf=None, by=1.0):
    rng = np.random.default_rng(0)
    grads = {"layers": {"layer_00": {
        "A_log": rng.normal(size=8).astype(np.float32),
        "dt_bias": rng.normal(size=8).astype(np.float32),
        "in_proj": rng.normal(size=(256, 256)).astype(np.float32)},
        "layer_01": {"router": rng.normal(size=(64, 4)).astype(np.float32)}}}
    moved = {"layers": {lk: {k: v * (by if k == scale_leaf else 1.0)
                             for k, v in lv.items()}
                        for lk, lv in grads["layers"].items()}}
    return grads, moved


@pytest.mark.parametrize("leaf,by,ok,failing", [
    (None, 1.0, True, None),
    # The recurrence's own leaves are held ten times tighter than the rest.
    ("A_log", 1.1, False, "momentum_rel_scan"),
    ("dt_bias", 1.1, False, "momentum_rel_scan"),
    ("router", 1.1, True, None),
    ("router", 1.5, False, "momentum_rel_worst"),
])
def test_first_step_limits_hold_the_scan_leaves_apart(leaf, by, ok, failing):
    from benchmark import reference_check_lm as chk
    import jax
    grads, moved = _trees(leaf, by)
    logits = np.ones((4, 8), np.float32)
    out = chk.compare(
        {"loss": 1.0, "grads": grads, "logits0": logits}, loss=1.0,
        logits0=logits, momentum=moved, lr=0.5,
        update=jax.tree_util.tree_map(lambda g: -0.5 * g, moved))
    assert out["ok"] is ok
    over = [k for k, v in out["errors"].items() if v > chk.TOLERANCE[k]]
    assert over == ([failing] if failing else [])
