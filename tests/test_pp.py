"""Pipeline parallelism (parallel/pp/): partitioner, schedules, 3-D mesh
validation, supervisor stage-awareness, and the bit-compat contract.

The load-bearing guarantee: a staged (d, m, s) run is BIT-compatible
with the (d, m) grad-accum step (s=1 degenerates to the standard path),
and the canonical checkpoint restores onto any (d', m', s').  Fast
shape/plan/policy tests run unmarked; everything that compiles XLA
programs or spawns training children is ``slow``.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from ddp_tpu.parallel.mesh import make_mesh
from ddp_tpu.parallel.pp import (format_stage_table, plan_stages,
                                 predicted_bubble, stage_model_psums)
from ddp_tpu.parallel.pp.partition import merge_subtrees, stage_subtree
from ddp_tpu.parallel.pp.schedule import schedule_ops

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- mesh-shape validation (the three named axes) --------------------------


def test_make_mesh_rejects_malformed_shapes():
    for bad in [(2, 1, 2, 2), (), (2, 0, 2), (2, -1), ("a", 1)]:
        with pytest.raises(ValueError) as ei:
            make_mesh(shape=bad)
        msg = str(ei.value)
        assert "data" in msg and "model" in msg and "stage" in msg, msg


def test_make_mesh_s1_collapses_to_2d():
    mesh = make_mesh(shape=(2, 1, 1))
    assert mesh.axis_names == ("data", "model")
    mesh3 = make_mesh(shape=(2, 1, 2))
    assert mesh3.axis_names == ("data", "model", "stage")
    assert mesh3.devices.size == 4


def test_cli_mesh_shape_parse_names_all_axes():
    from ddp_tpu.cli import _parse_mesh_shape
    assert _parse_mesh_shape("2,1,2") == (2, 1, 2)
    assert _parse_mesh_shape("4x2") == (4, 2)
    for bad in ["2,a", "2,1,2,2", "2,0,2", "2"]:
        with pytest.raises(SystemExit) as ei:
            _parse_mesh_shape(bad)
        assert "(data, model, pipeline stage)" in str(ei.value)


# -- stage partitioner -----------------------------------------------------


def test_plan_stages_balances_injected_costs():
    # Six deepnn blocks with a deliberately lopsided cost table: the
    # balanced 2-cut must isolate the expensive block.
    costs = {"features/conv0": 100.0, "features/conv1": 1.0,
             "features/conv2": 1.0, "features/conv3": 1.0,
             "classifier/linear0": 1.0, "classifier/linear1": 1.0}
    plan = plan_stages("deepnn", 2, costs=costs)
    assert plan.stages[0] == (0, 1)          # the 100-cost block alone
    assert plan.stage_costs == (100.0, 5.0)
    assert not plan.uniform_costs


def test_plan_stages_uniform_fallback_covers_blocks():
    plan = plan_stages("deepnn", 3)          # no params -> uniform costs
    assert plan.uniform_costs
    assert plan.stages[0][0] == 0 and plan.stages[-1][1] == len(
        plan.block_names)
    for (lo, hi), (lo2, _hi2) in zip(plan.stages, plan.stages[1:]):
        assert hi == lo2                     # contiguous cover


def test_plan_stages_reports_every_violation_at_once():
    with pytest.raises(ValueError) as ei:
        plan_stages("deepnn", 99)
    msg = str(ei.value)
    assert "stage count 99 exceeds" in msg
    # m>1 restricts cuts to full-width activation boundaries.
    with pytest.raises(ValueError) as ei:
        plan_stages("deepnn", 4, model_size=2)
    assert "full-width activation" in str(ei.value)
    # A model with no PP_BLOCKS names the opt-in contract.
    with pytest.raises(ValueError) as ei:
        plan_stages("vgg", 2)
    assert "PP_BLOCKS" in str(ei.value)


def test_stage_table_schema_anchor():
    plan = plan_stages("deepnn", 2)
    table = format_stage_table(plan, num_micro=4)
    first = table.splitlines()[0]
    assert first.startswith("pipeline-stage plan: deepnn | stage axis s=2")
    assert "bubble" in table                 # the predicted-bubble line


def test_predicted_bubble_values():
    assert predicted_bubble(1, 4) == 0.0
    assert predicted_bubble(2, 4) == pytest.approx(1 / 5)
    assert predicted_bubble(4, 4) == pytest.approx(3 / 7)
    with pytest.raises(ValueError):
        predicted_bubble(0, 4)


def test_stage_subtree_merge_roundtrip():
    plan = plan_stages("deepnn", 3)
    tree = {"features": {f"conv{i}": i for i in range(4)},
            "classifier": {"linear0": 10, "linear1": 11}}
    parts = [stage_subtree(plan, k, tree) for k in range(3)]
    assert merge_subtrees(parts) == tree


def test_stage_model_psums_counts():
    from ddp_tpu.models import get_model
    from ddp_tpu.parallel.tp.plan import plan_for_model
    params, stats = jax.device_get(get_model("deepnn").init(
        jax.random.key(0)))
    tp = plan_for_model("deepnn", params, stats, model_size=2)
    plan = plan_stages("deepnn", 2, model_size=2, params=params,
                       batch_stats=stats)
    styles = dict(tp.layers)
    for k in (0, 1):
        lo, hi = plan.stages[k]
        names = plan.block_names[lo:hi]
        n_row = sum(1 for b in names if styles.get(b) == "row")
        n_col = sum(1 for b in names if styles.get(b) == "column")
        assert stage_model_psums(plan, tp, k, role="forward") == n_row
        assert stage_model_psums(plan, tp, k, role="fwdbwd") == \
            n_row + n_col
        expect_bwd = n_row + n_col - (
            1 if k == 0 and tp.stem in names
            and styles.get(tp.stem) == "column" else 0)
        assert stage_model_psums(plan, tp, k, role="backward") == expect_bwd
        assert stage_model_psums(plan, tp, k, role="update") == 0
    assert stage_model_psums(plan, None, 0, role="forward") == 0
    with pytest.raises(ValueError):
        stage_model_psums(plan, tp, 0, role="sideways")


# -- schedules (pure op-list properties) -----------------------------------


@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
@pytest.mark.parametrize("a,s", [(1, 2), (2, 2), (4, 3), (3, 4)])
def test_schedule_ops_complete_and_dependency_ordered(kind, a, s):
    ops = schedule_ops(kind, a, s)
    # Completeness: every (micro, stage) forward, one fused FB per micro,
    # every backward below the last stage.
    assert sorted(op for op in ops if op[0] == "F") == \
        sorted(("F", j, k) for j in range(s - 1) for k in range(a))
    assert sorted(op for op in ops if op[0] == "FB") == \
        sorted(("FB", k) for k in range(a))
    assert sorted(op for op in ops if op[0] == "B") == \
        sorted(("B", j, k) for j in range(s - 1) for k in range(a))
    pos = {op: i for i, op in enumerate(ops)}
    for k in range(a):
        for j in range(1, s - 1):
            assert pos[("F", j, k)] > pos[("F", j - 1, k)]
        if s > 1:
            assert pos[("FB", k)] > pos[("F", s - 2, k)]
        for j in range(s - 2, -1, -1):
            after = pos[("FB", k)] if j == s - 2 else pos[("B", j + 1, k)]
            assert pos[("B", j, k)] > after


def test_unknown_schedule_rejected():
    with pytest.raises(ValueError):
        schedule_ops("zigzag", 2, 2)


# -- auto-plan 3-tuple docs ------------------------------------------------


def test_autoplan_doc_accepts_3_tuple_mesh():
    from ddp_tpu.parallel.tp.autoplan import (PLAN_FORMAT_VERSION,
                                              PLAN_KIND, validate_plan_doc)
    doc = {"kind": PLAN_KIND, "format_version": PLAN_FORMAT_VERSION,
           "model": "deepnn", "mesh_shape": [2, 1, 2], "recipe": {},
           "zero": False}
    validate_plan_doc(doc)                   # no raise
    assert json.loads(json.dumps(doc))["mesh_shape"] == [2, 1, 2]
    for bad in ([2, 1, 2, 2], [2, 0, 2], [2]):
        with pytest.raises(ValueError) as ei:
            validate_plan_doc({**doc, "mesh_shape": bad})
        assert "pipeline stage" in str(ei.value)


# -- supervisor stage-awareness --------------------------------------------


def test_shrink_mesh_stage_axis_first():
    from ddp_tpu.resilience.supervisor import shrink_mesh
    assert shrink_mesh((2, 1, 2), 4) == (2, 1, 2)
    assert shrink_mesh((2, 1, 2), 3) == (2, 1, 1)   # stage gives way
    assert shrink_mesh((2, 1, 2), 2) == (2, 1, 1)
    assert shrink_mesh((2, 2, 2), 6) == (2, 2, 1)
    assert shrink_mesh((4, 1, 4), 9) == (4, 1, 2)   # largest surviving s
    # Below one (d, m) plane the 2-D data-first policy takes over.
    assert shrink_mesh((2, 2, 2), 3) == (1, 2, 1)
    assert shrink_mesh((2, 2, 2), 1) == (1, 1, 1)
    # 2-D behaviour unchanged.
    assert shrink_mesh((8, 1), 4) == (4, 1)
    assert shrink_mesh((2, 4), 3) == (1, 2)


def test_supervisor_relaunch_recuts_stage_axis():
    from ddp_tpu.resilience.supervisor import Supervisor
    child = ["multigpu.py", "3", "1", "--mesh_shape", "2,1,2"]
    sup = Supervisor(child, device_probe=lambda env: 2, env={})
    argv = sup._relaunch_argv(list(child))
    i = argv.index("--mesh_shape")
    assert argv[i + 1] == "2,1,1"
    assert "--resume" in argv
    # Devices back: the next relaunch grows to the full staged mesh.
    sup2 = Supervisor(child, device_probe=lambda env: 4, env={})
    argv = sup2._relaunch_argv(list(child))
    assert argv[argv.index("--mesh_shape") + 1] == "2,1,2"


# -- analysis integration (abstract tracing, no XLA compile) ---------------


def test_pp_audit_bans_stage_axis_collectives():
    from ddp_tpu.analysis.jaxpr_audit import audit_collectives
    findings = audit_collectives("pp_fb@pp", "pp_fwdbwd",
                                 {("psum", ("stage",)): 1})
    errs = [f for f in findings if f.severity == "error"]
    assert errs and "stage handoff" in errs[0].detail


def test_pp_audit_exact_model_psum_budget():
    from ddp_tpu.analysis.jaxpr_audit import audit_collectives
    inv = {("psum", ("data",)): 1, ("psum", ("model",)): 2}
    ok = audit_collectives("pp_fb@pp", "pp_fwdbwd", inv,
                           model_psum_budget=2)
    assert not [f for f in ok if f.severity == "error"]
    bad = audit_collectives("pp_fb@pp", "pp_fwdbwd", inv,
                            model_psum_budget=3)
    errs = [f for f in bad if f.severity == "error"]
    assert errs and "stage_model_psums" in errs[0].detail
    # pp_update must be fully collective-free on the data axis.
    upd = audit_collectives("pp_update_s0@pp", "pp_update",
                            {("psum", ("data",)): 1}, model_psum_budget=0)
    assert [f for f in upd if f.severity == "error"]


def test_analysis_builds_staged_programs():
    from ddp_tpu.analysis.programs import build_context, build_programs
    ctx = build_context("deepnn", mesh_2d=(2, 1, 2))
    progs = {p.name: p for p in build_programs(
        ctx, ["pp_fwd_s0@pp", "pp_fb@pp", "pp_bwd_s0@pp",
              "pp_update_s0@pp", "pp_update_s1@pp"])}
    assert set(progs) == {"pp_fwd_s0@pp", "pp_fb@pp", "pp_bwd_s0@pp",
                          "pp_update_s0@pp", "pp_update_s1@pp"}
    assert progs["pp_update_s0@pp"].model_psum_budget == 0


# -- the bit-compat contract (XLA compiles: slow) --------------------------


def _deepnn_fixture():
    from ddp_tpu.models import get_model
    model = get_model("deepnn")
    params, stats = jax.device_get(model.init(jax.random.key(0)))
    rngb = np.random.RandomState(0)
    batches = [{"image": rngb.randint(0, 256, (2, 16, 32, 32, 3))
                .astype(np.uint8),
                "label": rngb.randint(0, 10, (2, 16)).astype(np.int32)}
               for _ in range(2)]
    return model, params, stats, batches


def _run_ref(model, params, stats, batches, d, m):
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.tp.plan import (is_trivial, plan_for_model,
                                          state_shardings)
    from ddp_tpu.train.step import (init_train_state, make_train_step,
                                    shard_batch_stacked)
    mesh = make_mesh(shape=(d, m))
    plan = plan_for_model("deepnn", params, stats, model_size=m)
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                              steps_per_epoch=4)
    step = make_train_step(model, SGDConfig(lr=0.1), sched, mesh,
                           plan=plan, accum=True)
    state = init_train_state(params, stats)
    if not is_trivial(plan):
        state = jax.device_put(state, state_shardings(plan, mesh))
    losses = []
    for b in batches:
        state, loss = step(state, shard_batch_stacked(b, mesh),
                           jax.random.key(7))
        losses.append(float(loss))
    return losses, jax.device_get(state.params)


def _run_pp(params, stats, batches, d, m, s, kind):
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.pp import make_pp_step, place_state, pp_shard_fn
    from ddp_tpu.parallel.tp.plan import plan_for_model
    from ddp_tpu.train.step import init_train_state
    mesh = make_mesh(shape=(d, m, s))
    plan = plan_for_model("deepnn", params, stats, model_size=m)
    pp = plan_stages("deepnn", s, model_size=m, params=params,
                     batch_stats=stats)
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                              steps_per_epoch=4)
    step = make_pp_step("deepnn", SGDConfig(lr=0.1), sched, mesh, pp,
                        tp_plan=plan, schedule=kind)
    state = place_state(init_train_state(params, stats), mesh, pp, plan)
    shard = pp_shard_fn(pp)
    losses = []
    for b in batches:
        state, loss = step(state, shard(b, mesh), jax.random.key(7))
        losses.append(float(loss))
    return losses, jax.device_get(state.params)


def _assert_bitwise(p_ref, p_pp):
    from jax.flatten_util import ravel_pytree
    f_ref, _ = ravel_pytree(p_ref)
    f_pp, _ = ravel_pytree(p_pp)
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pp))


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
def test_pp_step_bitwise_matches_accum_step(kind):
    """(2,1,2) staged step == (2,1) grad-accum step, to the bit, under
    both schedules — the s=1-degenerates-cleanly contract."""
    model, params, stats, batches = _deepnn_fixture()
    l_ref, p_ref = _run_ref(model, params, stats, batches, 2, 1)
    l_pp, p_pp = _run_pp(params, stats, batches, 2, 1, 2, kind)
    assert l_ref == l_pp
    _assert_bitwise(p_ref, p_pp)


@pytest.mark.slow
def test_tp_pp_composes_bitwise():
    """(2,2,2) — tensor AND pipeline parallel — == (2,2), to the bit."""
    model, params, stats, batches = _deepnn_fixture()
    l_ref, p_ref = _run_ref(model, params, stats, batches, 2, 2)
    l_pp, p_pp = _run_pp(params, stats, batches, 2, 2, 2, "1f1b")
    assert l_ref == l_pp
    _assert_bitwise(p_ref, p_pp)


@pytest.mark.slow
def test_trainer_pp_checkpoint_portability(tmp_path):
    """Trainer (2,1,2) == (2,1) bitwise; a (2,1)-saved checkpoint resumes
    bitwise onto the staged mesh; a pp-saved checkpoint resumes onto a
    plain 1-D mesh (functional across d — cross-d is never bitwise)."""
    from ddp_tpu.data import TrainLoader, synthetic
    from ddp_tpu.models import get_model
    from ddp_tpu.optim import SGDConfig, triangular_lr
    from ddp_tpu.train import Trainer
    train_ds, _ = synthetic(n_train=64, seed=5)
    model = get_model("deepnn")
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=2,
                              steps_per_epoch=2)

    def run(mesh_shape, pp=False, snapshot=None, resume=False, epochs=2):
        mesh = (make_mesh(mesh_shape[0]) if len(mesh_shape) == 1
                else make_mesh(shape=mesh_shape))
        params, stats = model.init(jax.random.key(0))
        loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=2,
                             augment=False, seed=1)
        kw = {}
        if pp:
            kw["pp_plan"] = plan_stages("deepnn", mesh_shape[2],
                                        params=params, batch_stats=stats)
        tr = Trainer(model, loader, params, stats, mesh=mesh,
                     lr_schedule=sched, sgd_config=SGDConfig(lr=0.05),
                     save_every=1, snapshot_path=snapshot,
                     grad_accum=2, resume=resume, **kw)
        tr.train(epochs)
        return tr

    ref = run((2, 1))
    pp = run((2, 1, 2), pp=True)
    assert [float(v) for v in ref.loss_history] == \
        [float(v) for v in pp.loss_history]
    _assert_bitwise(jax.device_get(ref.state.params),
                    jax.device_get(pp.state.params))

    # pp-saved -> plain 1-D resume (cross-d: functional, not bitwise).
    p_a = str(tmp_path / "a.pt")
    run((2, 1, 2), pp=True, snapshot=p_a, epochs=1)
    res = run((4,), pp=False, snapshot=p_a, resume=True, epochs=2)
    assert int(res.state.step) == 4

    # (2,1)-saved -> staged resume at the SAME d: bitwise.
    p_b = str(tmp_path / "b.pt")
    run((2, 1), pp=False, snapshot=p_b, epochs=1)
    res2 = run((2, 1, 2), pp=True, snapshot=p_b, resume=True, epochs=2)
    refpp = run((2, 1, 2), pp=True, epochs=2)
    assert [float(v) for v in res2.loss_history] == \
        [float(v) for v in refpp.loss_history[2:]]
    _assert_bitwise(jax.device_get(res2.state.params),
                    jax.device_get(refpp.state.params))


@pytest.mark.slow
def test_kill_stage_drill_zero_data_loss(tmp_path):
    """The chaos drill end-to-end: SIGTERM a (2,1,2) run mid-schedule,
    relaunch with one stage plane dead -> stage-first shrink to (2,1,1)
    -> bit-identical finish vs the undisturbed control."""
    out = tmp_path / "chaos.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_campaign.py"),
         "--drills", "kill_stage", "--out", str(out)],
        capture_output=True, text=True, timeout=840)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    card = json.loads(out.read_text())
    drill = card["drills"]["kill_stage"]
    assert drill["pass"] and drill["bit_identical"]
    assert drill["restart_reasons"] == {"preempted": 1}
