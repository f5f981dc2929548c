"""Auto-sharding search (parallel/tp/autoplan.py + analysis/search.py):
determinism, pruning correctness, the committed golden plan, plan-doc
validation, and hand-vs-auto training parity (ISSUE 17).

Everything searches on DEVICELESS abstract meshes
(parallel/mesh.py:abstract_mesh) except the parity test, which trains
for real on the suite's 8-virtual-device CPU mesh.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from ddp_tpu.analysis.search import (COEFFICIENT_KEYS, coefficients_from,
                                     trace_candidate)
from ddp_tpu.models import get_model
from ddp_tpu.parallel.tp.autoplan import (enumerate_recipes, plan_doc_dumps,
                                          plan_from_doc, read_plan_doc,
                                          search_plan, search_space_for,
                                          validate_plan_doc)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "plans", "deepnn_2x4.autoplan.json")

# Stand-in coefficients for tests that exercise search MECHANICS (the
# golden test uses the committed doc's real fitted ones).
COEFFS = {"conv_s_per_flop": 1e-10, "dot_s_per_flop": 5e-11,
          "elementwise_s_per_byte": 2e-10,
          "collective_s_per_payload_byte": 1e-9}


# ---------------------------------------------------------------- space

def test_enumerate_recipes_respects_dfa_and_barrier():
    """The layout enumerator walks the activation-width DFA: a column
    layer shards its output, only a row layer closes it, TP_BARRIERS
    layers must emit FULL activations (deepnn's flatten after conv3),
    and the terminal layer must emit full width.  deepnn's 6-layer space
    has exactly 10 legal recipes (incl. the all-replicated one)."""
    space = search_space_for("deepnn")
    assert space.stem == "features/conv0"
    assert "features/conv3" in space.barriers
    recipes = enumerate_recipes(space)
    assert len(recipes) == 10
    keys = [json.dumps(r, sort_keys=True) for r in recipes]
    assert len(set(keys)) == len(keys)
    last = space.layers[-1]
    for recipe in recipes:
        sharded = False
        for layer in space.layers:
            style = recipe.get(layer, "replicated")
            if style == "column":
                assert not sharded  # column wants full input
                sharded = True
            elif style == "row":
                assert sharded      # row wants sharded input
                sharded = False
            if layer in space.barriers:
                assert not sharded  # barrier: output must be full width
        assert not sharded          # terminal state full
        assert recipe.get(last) != "column"


def test_search_space_for_model_without_recipe():
    space = search_space_for("vgg")
    assert space.layers == ()
    assert enumerate_recipes(space) == [{}]


# ---------------------------------------------------- determinism + doc

def test_search_is_deterministic_bit_identical():
    """Two identical searches serialize to byte-identical plan JSON —
    the reproducibility contract the committed golden file hangs on."""
    kw = dict(coefficients=COEFFS, total_devices=8,
              mesh_shapes=[(2, 4), (4, 2)])
    a = search_plan("deepnn", **kw)
    b = search_plan("deepnn", **kw)
    assert plan_doc_dumps(a.doc) == plan_doc_dumps(b.doc)
    # ... and carries no timestamps or environment-dependent fields.
    assert "time" not in plan_doc_dumps(a.doc)


def test_plan_doc_roundtrip_and_validation(tmp_path):
    result = search_plan("deepnn", coefficients=COEFFS, total_devices=8,
                         mesh_shapes=[(2, 4)])
    path = tmp_path / "plan.json"
    path.write_text(plan_doc_dumps(result.doc))
    doc = read_plan_doc(str(path))
    assert doc == result.doc
    # Validation names every violation at once.
    bad = dict(doc)
    bad["kind"] = "other"
    bad["mesh_shape"] = [2, 0]
    bad["recipe"] = {"features/conv0": "diagonal"}
    with pytest.raises(ValueError) as e:
        validate_plan_doc(bad)
    msg = str(e.value)
    assert "kind" in msg and "mesh_shape" in msg and "diagonal" in msg


def test_coefficients_from_carriers():
    """Coefficients load from a calibrate record, a plan doc, or a bare
    mapping — and a missing key is a named error."""
    assert coefficients_from({"coefficients": COEFFS}) == COEFFS
    assert coefficients_from(COEFFS) == COEFFS
    partial = dict(COEFFS)
    partial.pop("dot_s_per_flop")
    with pytest.raises(ValueError, match="dot_s_per_flop"):
        coefficients_from(partial)
    assert set(COEFFS) == set(COEFFICIENT_KEYS)


# -------------------------------------------------------------- pruning

def test_divisibility_violations_are_pruned_never_emitted():
    """A model-axis size that does not divide deepnn's layer widths
    (tp/plan.py divisibility rules) is pruned, and the pruned counter
    says why; the emitted winner comes only from feasible shapes."""
    result = search_plan("deepnn", coefficients=COEFFS,
                         mesh_shapes=[(1, 5), (8, 1)], total_devices=8)
    assert result.doc["mesh_shape"] == [8, 1]
    assert result.doc["search"]["pruned"].get("divisibility", 0) > 0
    # Every SURVIVING candidate is feasible — no m=5 shape escapes the
    # prune (pruned rows stay in the table, flagged, ranked last).
    alive = [c for c in result.candidates if c["pruned"] is None]
    assert alive and all(c["mesh_shape"][1] != 5 for c in alive)
    for cand in result.candidates:
        if cand["mesh_shape"][1] == 5:
            assert cand["pruned"] == "divisibility"


def test_hbm_budget_prunes_and_bounds_choice():
    generous = search_plan("deepnn", coefficients=COEFFS, total_devices=8,
                           mesh_shapes=[(2, 4)])
    peaks = sorted(c["peak_live_bytes"] for c in generous.candidates
                   if c["pruned"] is None)
    # A budget below every candidate's liveness peak kills the search
    # loudly instead of emitting an infeasible plan.
    with pytest.raises(ValueError, match="hbm"):
        search_plan("deepnn", coefficients=COEFFS, total_devices=8,
                    mesh_shapes=[(2, 4)], hbm_budget_bytes=1)
    # A budget admitting only the leanest candidate(s) prunes exactly
    # the over-budget ones, and the chosen plan respects the budget.
    budget = peaks[0]
    capped = search_plan("deepnn", coefficients=COEFFS, total_devices=8,
                         mesh_shapes=[(2, 4)], hbm_budget_bytes=budget)
    assert capped.doc["peak_live_bytes"] <= budget
    assert capped.doc["search"]["pruned"].get("hbm", 0) == \
        sum(1 for p in peaks if p > budget)
    assert len(set(peaks)) > 1  # the space really exercises the prune


def test_batch_divisibility_prunes_mesh_shapes():
    """global_batch=4 cannot feed an 8-way data axis; the (8,1) shape is
    pruned as 'batch' and a feasible shape wins."""
    result = search_plan("deepnn", coefficients=COEFFS, total_devices=8,
                         global_batch=4)
    assert result.doc["search"]["pruned"].get("batch", 0) > 0
    assert result.doc["mesh_shape"][0] <= 4


# --------------------------------------------------------------- golden

def test_golden_plan_snapshot_reproduces_bit_identical():
    """The committed golden plan (deepnn on the (2,4)x8 virtual mesh)
    re-derives byte-identically from its own embedded coefficients and
    search metadata — search drift, cost-model drift, or doc-format
    drift all fail here first."""
    with open(GOLDEN, "r", encoding="utf-8") as fh:
        committed = fh.read()
    doc = json.loads(committed)
    meta = doc["search"]
    result = search_plan(
        doc["model"], coefficients=coefficients_from(doc),
        total_devices=meta["total_devices"],
        mesh_shapes=[tuple(s) for s in meta["mesh_shapes"]],
        hbm_budget_bytes=meta["hbm_budget_bytes"],
        global_batch=doc["global_batch"],
        zero_options=tuple(meta["zero_options"]))
    assert plan_doc_dumps(result.doc) == committed


def test_golden_plan_matches_hand_recipe():
    """On the hand-tuned (2,4) mesh the search lands on exactly the
    hand-written TP_RECIPE — the retirement argument: the recipe is now
    a search RESULT, not an input."""
    from ddp_tpu.models.deepnn import TP_RECIPE, TP_STEM
    doc = read_plan_doc(GOLDEN)
    assert doc["recipe"] == dict(TP_RECIPE)
    assert doc["stem"] == TP_STEM
    assert doc["zero"] is False


def test_golden_plan_audits_clean():
    """The golden plan's traced train step passes the strict collective
    auditor (expected_collectives arithmetic, axis whitelist)."""
    from ddp_tpu.analysis.search import audit_candidate
    doc = read_plan_doc(GOLDEN)
    closed, plan = trace_candidate(
        doc["model"], tuple(doc["mesh_shape"]), recipe=doc["recipe"],
        stem=doc["stem"], zero=doc["zero"],
        global_batch=doc["global_batch"])
    assert plan is not None
    assert audit_candidate("train_step@auto", closed, plan=plan,
                           zero=doc["zero"]) == []


def test_registry_builds_auto_program_from_committed_plan():
    """analysis/programs.py exposes the committed plan as the audited
    ``train_step@auto`` entry, and skips it for contexts with no
    committed plan file."""
    from ddp_tpu.analysis.programs import build_context, build_programs
    names = [p.name for p in build_programs(build_context())]
    assert "train_step@auto" in names
    names_42 = [p.name
                for p in build_programs(build_context(mesh_2d=(4, 2)))]
    assert "train_step@auto" not in names_42


# ---------------------------------------------------------------- parity

def test_auto_plan_trains_bit_compatibly_with_hand_recipe():
    """Two real train steps on the 8-device mesh: the plan loaded from
    the golden doc produces bit-identical params to the hand
    TP_RECIPE plan — --auto_plan is a new way to CHOOSE the layout, not
    a new numerical path."""
    from ddp_tpu.optim import SGDConfig, triangular_lr
    from ddp_tpu.parallel.mesh import batch_sharding, make_mesh
    from ddp_tpu.parallel.tp.plan import plan_for_model, state_shardings
    from ddp_tpu.train.step import init_train_state, make_train_step
    import functools

    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    doc = read_plan_doc(GOLDEN)
    mesh = make_mesh(shape=tuple(doc["mesh_shape"]))
    auto_plan = plan_from_doc(doc, params, stats)
    hand_plan = plan_for_model("deepnn", params, stats, model_size=4)
    assert auto_plan == hand_plan

    cfg = SGDConfig(lr=0.1)
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                              steps_per_epoch=4)
    batch = {"image": jax.device_put(
                 np.zeros((16, 32, 32, 3), np.uint8) + 7,
                 batch_sharding(mesh)),
             "label": jax.device_put(np.arange(16, dtype=np.int32) % 10,
                                     batch_sharding(mesh))}
    # The step donates its state; rebuild from host copies per plan.
    params_np, stats_np = jax.device_get((params, stats))
    finals = []
    for plan in (hand_plan, auto_plan):
        fn = make_train_step(model, cfg, sched, mesh, plan=plan)
        state = jax.device_put(init_train_state(params_np, stats_np),
                               state_shardings(plan, mesh, zero=False))
        rng = jax.random.key(1)
        for _ in range(2):
            state, _ = fn(state, batch, rng)
        finals.append(jax.device_get(state.params))
    flat_a = jax.tree_util.tree_leaves(finals[0])
    flat_b = jax.tree_util.tree_leaves(finals[1])
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ CLI smoke

def test_tp_search_cli_writes_golden_equivalent(tmp_path):
    """``python -m ddp_tpu.parallel.tp --search`` reproduces the
    committed golden file bit-identically from its own coefficients, and
    prints the schema-anchored search table."""
    out = tmp_path / "plan.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "ddp_tpu.parallel.tp", "--search",
         "--model", "deepnn", "--mesh_shape", "2,4",
         "--calib", GOLDEN, "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("auto-plan search: deepnn | devices=")
    assert "CHOSEN" in proc.stdout
    assert "tensor-parallel plan: deepnn" in proc.stdout
    with open(GOLDEN, "r", encoding="utf-8") as fh:
        assert out.read_text() == fh.read()


def test_autoplan_bench_child_search_equals_in_process():
    """``bench.py --autoplan_bench`` searches in a one-device CPU child
    (its parent must not touch a backend): the plan the auto child then
    loads is byte-identical to ``search_plan`` called in-process with the
    same inputs, and the record names the child's wall for what it is."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--autoplan_bench",
         "--calib", GOLDEN, "--num_devices", "4", "--batch_size", "8",
         "--steps", "1", "--warmup", "1", "--repeats", "1",
         "--autoplan_models", "deepnn"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    auto = rec["autoplan_bench"]["deepnn"]["auto"]
    with open(GOLDEN, "r", encoding="utf-8") as fh:
        coeffs = coefficients_from(json.load(fh))
    want = search_plan("deepnn", coefficients=coeffs, total_devices=4,
                       global_batch=8)
    assert plan_doc_dumps(auto["plan_doc"]) == plan_doc_dumps(want.doc)
    assert auto["mesh"] == "x".join(map(str, want.doc["mesh_shape"]))
    assert auto["search_child_s"] > 0 and "search_s" not in auto
    assert auto["ms_per_step"] > 0
    # --calib was a plan doc, not a calibrate record: no residual.
    assert rec["calibration_gap_pct"] is None


# ----------------------------------------------------- trivial-plan path

def test_trivial_plan_resolves_to_plain_dp():
    """A searched plan that kept every layer replicated (or a no-recipe
    model's plan) resolves to ``None`` — train/step.py then wires the
    plain data-parallel core, so a 'dp' plan is priced AND run as the
    plain program."""
    result = search_plan("vgg", coefficients=COEFFS, total_devices=8,
                         zero_options=(False,))
    model = get_model("vgg")
    params, stats = jax.eval_shape(model.init, jax.random.key(0))
    assert plan_from_doc(result.doc, params, stats) is None
    assert result.doc["recipe"] == {}


# ------------------------------------------------------- MFU denominator

def test_mfu_unknown_device_kind_yields_none():
    """The peak table is the only MFU denominator: a device kind that is
    not in it (this CPU box) gets no MFU — never a runtime-probed one —
    while a known kind is priced against its table entry."""
    from ddp_tpu.obs import live
    kind = jax.devices()[0].device_kind
    assert kind not in live.PEAK_TFLOPS_BF16_PASS  # cpu box
    assert live.model_mfu(10.0, "deepnn", kind) is None
    mfu = live.model_mfu(10.0, "deepnn", "TPU v5 lite")
    assert mfu is not None and mfu > 0
