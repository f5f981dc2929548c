"""Tensor-parallel sharding subsystem (ddp_tpu/parallel/tp/) — ISSUE 5.

The contracts, in dependency order:

- MESH: ``make_mesh(shape=(d, m))`` builds the named 2-D (data × model)
  mesh; the batch-math helpers (``local_batch_slice``,
  ``local_replica_ids``, ``assemble_from_local``) divide by the DATA axis
  only — each was a silent flat-device-count assumption before this round
  (the regression tests here fail on a 2-D mesh without the fix).
- PLAN: the planner resolves a model's TP_RECIPE into per-leaf
  PartitionSpecs, validates divisibility by the model-axis size (all
  violations by name), renders the table, and its specs are what the LIVE
  arrays actually carry after a step (``jax.Array.sharding``).
- NUMERICS (the acceptance): at m=1 the tp path is BIT-IDENTICAL to the
  established 1-D path, dropout included — the machinery itself adds
  nothing.  Across mesh shapes ((2,4), (4,2) vs 1-D×8) the fp32
  trajectories agree to the same last-ulp epsilon two 1-D meshes of
  different size already exhibit (reduction order: the loss psum spans d
  shards) — asserted at TP_TRAJ_ATOL with dropout disabled, because the
  per-shard RNG fold is BY DESIGN a function of the data-axis size (the
  documented 1-D behavior, tests/test_train_step.py's dropout-free
  precedent).  The row-parallel psums and column-input gradient psums
  (Megatron's g/f pair) reduce over ``model`` only; the gradient psum
  stays on ``data`` only.
- COMPOSITION: ZeRO's data-axis weight-update sharding composes with the
  model-axis param sharding (momentum ``[m, L]`` over P(model, data),
  spec-merge asserted live; trajectories match the replicated-update tp
  step; the flat-buffer <-> canonical-pytree conversions round-trip).
- PORTABILITY: a checkpoint written on one mesh shape restores onto any
  other — (2,4) -> (4,2) and (2,4) -> 1-D×8 — bit-for-bit at restore,
  with continued training matching the never-interrupted single-mesh
  trajectory (dropout-free, at TP_TRAJ_ATOL).
"""
import functools
import os

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, batch_sharding,
                                   assemble_from_local, data_axis_size,
                                   local_batch_slice, local_replica_ids,
                                   make_mesh, model_axis_size,
                                   process_min_mib)
from ddp_tpu.parallel.tp.plan import (format_plan_table, local_param_count,
                                      plan_for_model, state_shardings)
from ddp_tpu.train.step import (init_train_state, make_eval_forward,
                                make_train_step, shard_batch,
                                shard_batch_stacked)

# Measured on this backend (fp32, 3 steps, lr 0.1): cross-mesh-shape max
# param delta is 1.5e-8 — identical to the PURE-DP delta between two 1-D
# meshes of different size (the loss psum's reduction order), i.e. tensor
# parallelism adds no error of its own.  Asserted with margin.
TP_TRAJ_ATOL = 2e-6

_SGD = SGDConfig(lr=0.1)
_SCHED = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                           steps_per_epoch=4)


@pytest.fixture(scope="module")
def deepnn_params():
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    return model, jax.device_get(params), stats


def _batches(n_batches=3, batch=32, seed=0):
    rs = np.random.RandomState(seed)
    return [{"image": rs.randint(0, 256, (batch, 32, 32, 3)).astype(np.uint8),
             "label": rs.randint(0, 10, (batch,)).astype(np.int32)}
            for _ in range(n_batches)]


def _flat(tree):
    return np.asarray(jax.flatten_util.ravel_pytree(jax.device_get(tree))[0])


def _run_steps(model, params0, mesh, plan, batches, *, zero=False):
    """Train len(batches) steps from params0; returns (flat params, losses,
    final state)."""
    if zero:
        from ddp_tpu.train.zero import init_opt_shard
        step = make_train_step(model, _SGD, _SCHED, mesh, plan=plan,
                               shard_update=True)
        state = init_train_state(
            jax.tree_util.tree_map(jnp.asarray, params0), {})
        state = state._replace(
            opt_state=init_opt_shard(state.params, mesh, plan=plan))
        if plan is not None:
            state = jax.device_put(state,
                                   state_shardings(plan, mesh, zero=True))
    else:
        step = make_train_step(model, _SGD, _SCHED, mesh, plan=plan)
        state = init_train_state(
            jax.tree_util.tree_map(jnp.asarray, params0), {})
        if plan is not None:
            state = jax.device_put(state, state_shardings(plan, mesh))
    rng = jax.random.key(7)
    losses = []
    for b in batches:
        state, loss = step(state, shard_batch(b, mesh), rng)
        losses.append(float(loss))
    return _flat(state.params), losses, state


# -- mesh: 2-D construction + axis-aware helpers ---------------------------

def test_make_mesh_2d_axes_and_1d_default():
    mesh = make_mesh(shape=(2, 4))
    assert mesh.axis_names == (DATA_AXIS, MODEL_AXIS)
    assert dict(mesh.shape) == {"data": 2, "model": 4}
    assert data_axis_size(mesh) == 2 and model_axis_size(mesh) == 4
    one_d = make_mesh(8)
    assert one_d.axis_names == (DATA_AXIS,)
    assert data_axis_size(one_d) == 8 and model_axis_size(one_d) == 1
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_mesh(shape=(4, 4))
    with pytest.raises(ValueError, match="not both"):
        make_mesh(4, shape=(2, 2))


def test_local_batch_slice_uses_data_axis_only():
    # Regression: the old helper divided by the flat device count, so a
    # (2,4) mesh rejected batch 32 (32 % 8 == 0 but per-"device" math
    # shrank the slice 4x) — batch math must see d=2 shards only.
    mesh = make_mesh(shape=(2, 4))
    assert local_batch_slice(32, mesh) == 32  # single host owns all rows
    assert local_batch_slice(6, mesh) == 6    # 6 % 2 == 0; 6 % 8 != 0
    with pytest.raises(ValueError, match="2-way data axis"):
        local_batch_slice(7, mesh)
    assert local_batch_slice(32, make_mesh(8)) == 32  # 1-D unchanged


def test_local_replica_ids_are_data_rows_on_2d_mesh():
    # Regression: flat enumeration returned 8 ids on a (2,4) mesh — 4x
    # too many feeds; a replica is a data-axis ROW (its model-axis
    # devices consume the same batch shard).
    assert local_replica_ids(make_mesh(shape=(2, 4))) == [0, 1]
    assert local_replica_ids(make_mesh(shape=(4, 2))) == [0, 1, 2, 3]
    assert local_replica_ids(make_mesh(8)) == list(range(8))


def test_assemble_from_local_2d_batch_and_min_mib():
    # Regression: assemble_from_local derived both block counts from raw
    # device counts, inflating the global batch extent 4x on a (2,4)
    # mesh; it must count distinct shard positions along the spec'd axes.
    mesh = make_mesh(shape=(2, 4))
    v = np.arange(12 * 3, dtype=np.float32).reshape(12, 3)
    arr = assemble_from_local(batch_sharding(mesh), v, 0)
    assert arr.shape == (12, 3)
    np.testing.assert_array_equal(np.asarray(jax.device_get(arr)), v)
    # process_min_mib rides the same helpers; 2-D must agree with 1-D.
    assert process_min_mib(mesh, 5 * 2 ** 20) == 5 * 2 ** 20
    assert process_min_mib(mesh, None) is None


# -- planner ---------------------------------------------------------------

def test_plan_specs_match_the_recipe(deepnn_params):
    _, params, stats = deepnn_params
    plan = plan_for_model("deepnn", params, stats, model_size=4)
    specs = plan.param_specs
    assert specs["features"]["conv0"]["kernel"] == P(None, None, None,
                                                     MODEL_AXIS)
    assert specs["features"]["conv0"]["bias"] == P(MODEL_AXIS)
    assert specs["features"]["conv1"]["kernel"] == P(None, None,
                                                     MODEL_AXIS, None)
    assert specs["features"]["conv1"]["bias"] == P()  # row bias: after psum
    assert specs["classifier"]["linear0"]["weight"] == P(None, MODEL_AXIS)
    assert specs["classifier"]["linear1"]["weight"] == P(MODEL_AXIS, None)
    assert specs["classifier"]["linear1"]["bias"] == P()
    # Per-model-shard parameter count: sharded leaves contribute 1/m.
    total = sum(int(np.prod(np.shape(leaf)))
                for leaf in jax.tree_util.tree_leaves(params))
    sharded = total - 64 - 32 - 10  # the three row biases stay replicated
    assert local_param_count(plan) == sharded // 4 + 106


def test_plan_table_schema(deepnn_params):
    _, params, stats = deepnn_params
    plan = plan_for_model("deepnn", params, stats, model_size=4)
    table = format_plan_table(plan).splitlines()
    assert table[0] == "tensor-parallel plan: deepnn | model axis m=4"
    assert table[1].split() == ["leaf", "style", "shape", "spec",
                                "per-shard", "collectives"]
    body = table[2:-2]
    assert len(body) == 12  # 6 layers x (kernel|weight, bias)
    assert {row.split()[1] for row in body} == {"column", "row"}
    # Expected-collectives column: row leaves psum in the forward, column
    # leaves in the backward.
    for row in body:
        fields = row.split()
        assert fields[-1] == ("psum(model)@fwd" if fields[1] == "row"
                              else "psum(model)@bwd")
    assert table[-2].startswith("total 1,186,986 params | sharded ")
    # The footer is the same accounting the jaxpr auditor enforces
    # (analysis/jaxpr_audit.py): 3 row layers psum in the forward, the
    # stem's backward psum is elided (grads are w.r.t. params only).
    assert table[-1] == ("expected collectives: psum(model) fwd=3 bwd=2 "
                         "train=5 (stem features/conv0: input-grad psum "
                         "elided)")


def test_plan_validation_errors(deepnn_params):
    _, params, stats = deepnn_params
    # Divisibility: every violation reported at once, by leaf path.
    with pytest.raises(ValueError) as e:
        plan_for_model("deepnn", params, stats, model_size=3)
    assert "features/conv0/kernel" in str(e.value)
    assert "classifier/linear0/weight" in str(e.value)
    # A model without a recipe is refused with the remedy named.
    vgg_params, vgg_stats = get_model("vgg").init(jax.random.key(0))
    with pytest.raises(ValueError, match="TP_RECIPE"):
        plan_for_model("vgg", vgg_params, vgg_stats, model_size=2)
    # A recipe rule matching nothing is drift, not silence.
    import ddp_tpu.models.deepnn as deepnn_mod
    good = dict(deepnn_mod.TP_RECIPE)
    try:
        deepnn_mod.TP_RECIPE["features/conv9"] = "column"
        with pytest.raises(ValueError, match="conv9"):
            plan_for_model("deepnn", params, stats, model_size=2)
    finally:
        deepnn_mod.TP_RECIPE.clear()
        deepnn_mod.TP_RECIPE.update(good)


# -- numerics (the acceptance) ---------------------------------------------

def test_tp_m1_bit_identical_to_1d_with_dropout(deepnn_params):
    """(8,1) tp mesh vs the established 1-D 8-device path, dropout ON:
    every tp mechanism runs (row psums, column-input psums, sharded
    dropout, plan shardings) and the result is BIT-identical — the
    machinery itself introduces nothing."""
    model, params0, stats = deepnn_params
    batches = _batches()
    f_ref, l_ref, _ = _run_steps(model, params0, make_mesh(8), None,
                                 batches)
    plan = plan_for_model("deepnn", params0, stats, model_size=1)
    f_tp, l_tp, _ = _run_steps(model, params0, make_mesh(shape=(8, 1)),
                               plan, batches)
    assert l_tp == l_ref
    np.testing.assert_array_equal(f_tp, f_ref)


def test_tp_24_42_match_1d_and_live_shardings(deepnn_params, monkeypatch):
    """(2,4) and (4,2) DeepNN training vs the 1-D 8-device run, fp32:
    same trajectory to the documented last-ulp epsilon (dropout disabled —
    the per-shard RNG fold varies with the data-axis size by design, the
    1-D precedent), and the planner's per-leaf specs asserted on the LIVE
    output arrays."""
    import ddp_tpu.models.deepnn as deepnn_mod
    monkeypatch.setattr(deepnn_mod, "DROPOUT_RATE", 0.0)
    model, params0, stats = deepnn_params
    batches = _batches()
    f_ref, l_ref, _ = _run_steps(model, params0, make_mesh(8), None,
                                 batches)
    for shape in [(2, 4), (4, 2)]:
        plan = plan_for_model("deepnn", params0, stats,
                              model_size=shape[1])
        f_tp, l_tp, state = _run_steps(model, params0,
                                       make_mesh(shape=shape), plan,
                                       batches)
        np.testing.assert_allclose(f_tp, f_ref, atol=TP_TRAJ_ATOL, rtol=0)
        assert np.allclose(l_tp, l_ref, atol=1e-5)
        # Acceptance: the plan's specs hold on the live arrays, per leaf.
        live = jax.tree_util.tree_map(lambda a: a.sharding.spec,
                                      state.params)
        assert live == plan.param_specs
        mom = jax.tree_util.tree_map(lambda a: a.sharding.spec,
                                     state.opt_state.momentum_buf)
        assert mom == plan.param_specs  # elementwise SGD preserves specs


def test_tp_accum_m1_bit_identical(deepnn_params):
    """Gradient accumulation through the tp wiring: (8,1) accum step ==
    1-D accum step bit-for-bit (the shared make_accum_scan scaffold with
    the tp core)."""
    model, params0, stats = deepnn_params
    rs = np.random.RandomState(3)
    stack = {"image": rs.randint(0, 256, (2, 32, 32, 32, 3)).astype(np.uint8),
             "label": rs.randint(0, 10, (2, 32)).astype(np.int32)}
    rng = jax.random.key(5)

    def run(mesh, plan):
        step = make_train_step(model, _SGD, _SCHED, mesh, plan=plan,
                               accum=True)
        state = init_train_state(
            jax.tree_util.tree_map(jnp.asarray, params0), {})
        if plan is not None:
            state = jax.device_put(state, state_shardings(plan, mesh))
        state, loss = step(state, shard_batch_stacked(stack, mesh), rng)
        return _flat(state.params), float(loss)

    f_ref, l_ref = run(make_mesh(8), None)
    plan = plan_for_model("deepnn", params0, stats, model_size=1)
    f_tp, l_tp = run(make_mesh(shape=(8, 1)), plan)
    assert l_tp == l_ref
    np.testing.assert_array_equal(f_tp, f_ref)


def test_tp_eval_forward_matches_1d(deepnn_params):
    """Eval-mode logits: tp (2,4) forward vs the 1-D 8-device forward —
    same predictions, logits within the matmul-decomposition epsilon (the
    row psum splits the contractions; per-row eval logits are otherwise
    independent of the mesh)."""
    model, params0, stats = deepnn_params
    imgs = np.random.default_rng(4).integers(
        0, 256, (32, 32, 32, 3)).astype(np.uint8)
    ref = np.asarray(jax.device_get(
        make_eval_forward(model, make_mesh(8))(params0, stats, imgs)))
    mesh = make_mesh(shape=(2, 4))
    plan = plan_for_model("deepnn", params0, stats, model_size=4)
    p_sh = jax.device_put(jax.tree_util.tree_map(jnp.asarray, params0),
                          state_shardings(plan, mesh).params)
    tp = np.asarray(jax.device_get(
        make_eval_forward(model, mesh, plan=plan)(p_sh, stats, imgs)))
    np.testing.assert_allclose(tp, ref, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(tp.argmax(-1), ref.argmax(-1))


# -- composition: ZeRO x tp ------------------------------------------------

def test_tp_zero_composes_and_momentum_spec_merges(deepnn_params):
    """--shard_update on a (2,4) mesh: same trajectory as the replicated
    tp update (modulo collective reduction order, the documented zero
    contract), momentum living as [m, L] over P(model, data) — the
    spec-merge of params-along-model with update-along-data — and the
    flat-buffer <-> canonical-pytree conversions agreeing with the
    replicated path's momentum."""
    from ddp_tpu.train.zero import opt_shard_to_pytree, pytree_to_opt_shard
    model, params0, stats = deepnn_params
    mesh = make_mesh(shape=(2, 4))
    plan = plan_for_model("deepnn", params0, stats, model_size=4)
    batches = _batches()
    f_rep, l_rep, st_rep = _run_steps(model, params0, mesh, plan, batches)
    f_z, l_z, st_z = _run_steps(model, params0, mesh, plan, batches,
                                zero=True)
    np.testing.assert_allclose(f_z, f_rep, atol=1e-5, rtol=0)
    assert np.allclose(l_z, l_rep, atol=1e-5)
    buf = st_z.opt_state.momentum_buf
    assert buf.sharding.spec == P(MODEL_AXIS, DATA_AXIS)
    assert buf.shape[0] == 4  # one flat row per model shard
    # Conversions: sharded buffer -> canonical pytree matches the
    # replicated-update momentum; pytree -> buffer round-trips bitwise.
    tree = opt_shard_to_pytree(st_z.params, st_z.opt_state, mesh,
                               plan=plan).momentum_buf
    np.testing.assert_allclose(
        _flat(tree), _flat(st_rep.opt_state.momentum_buf),
        atol=1e-5, rtol=0)
    back = pytree_to_opt_shard(jax.device_get(tree), mesh,
                               plan=plan).momentum_buf
    np.testing.assert_array_equal(np.asarray(jax.device_get(back)),
                                  np.asarray(jax.device_get(buf)))


# -- checkpoint portability across mesh shapes -----------------------------

def _make_trainer(model, params0, stats, mesh, plan, path, tmp, **kw):
    from ddp_tpu.data import TrainLoader, synthetic
    from ddp_tpu.train import Trainer
    train_ds, _ = synthetic(n_train=64, seed=2)
    d = data_axis_size(mesh)
    loader = TrainLoader(train_ds, 64 // d, d, augment=False, seed=0)
    kw.setdefault("save_every", 1)
    return Trainer(model, loader,
                   jax.tree_util.tree_map(jnp.asarray, params0), stats,
                   mesh=mesh, lr_schedule=_SCHED, sgd_config=_SGD,
                   snapshot_path=path, tp_plan=plan,
                   prefetch_depth=0, **kw)


def test_checkpoint_portable_across_mesh_shapes(deepnn_params, monkeypatch,
                                                tmp_path):
    """Train one epoch on (2,4), checkpoint (the save GATHERS to the
    canonical format), resume on (4,2) AND on 1-D×8: the restored state
    is bit-identical to the file on both meshes, and the continued
    training matches the never-interrupted single-mesh run at the
    trajectory epsilon (dropout-free, fixed global batch 64)."""
    import ddp_tpu.models.deepnn as deepnn_mod
    monkeypatch.setattr(deepnn_mod, "DROPOUT_RATE", 0.0)
    from ddp_tpu.train.checkpoint import load_checkpoint
    model, params0, stats = deepnn_params
    path = str(tmp_path / "tp_ck.pt")

    # Uninterrupted 2-epoch reference on the 1-D mesh.
    ref = _make_trainer(model, params0, stats, make_mesh(8), None,
                        str(tmp_path / "ref.pt"), tmp_path)
    ref.train(2)
    f_ref = _flat(ref.state.params)

    # Epoch 0 on (2,4) -> canonical checkpoint on disk.
    mesh24 = make_mesh(shape=(2, 4))
    plan24 = plan_for_model("deepnn", params0, stats, model_size=4)
    t24 = _make_trainer(model, params0, stats, mesh24, plan24, path,
                        tmp_path)
    t24.train(1)
    ckpt = load_checkpoint(path)
    assert ckpt.epoch == 0
    # The gathered save is bit-identical to the live sharded state.
    np.testing.assert_array_equal(_flat(ckpt.params),
                                  _flat(t24.state.params))

    mesh42 = make_mesh(shape=(4, 2))
    plan42 = plan_for_model("deepnn", params0, stats, model_size=2)
    for mesh, plan in [(mesh42, plan42), (make_mesh(8), None)]:
        # save_every=10**9: a resumed run must not overwrite the shared
        # fixture checkpoint before the next mesh shape restores it.
        resumed = _make_trainer(model, params0, stats, mesh, plan, path,
                                tmp_path, resume=True, save_every=10**9)
        assert resumed.start_epoch == 1
        # Restore is bit-exact THROUGH the re-shard onto the new mesh.
        np.testing.assert_array_equal(_flat(resumed.state.params),
                                      _flat(ckpt.params))
        if plan is not None:
            live = jax.tree_util.tree_map(lambda a: a.sharding.spec,
                                          resumed.state.params)
            assert live == plan.param_specs
        resumed.train(2)  # runs epoch 1 only
        np.testing.assert_allclose(_flat(resumed.state.params), f_ref,
                                   atol=1e-5, rtol=0)


def test_sharded_checkpoint_portability_matrix(deepnn_params, monkeypatch,
                                               tmp_path):
    """ISSUE 6 acceptance: a (2,4)-train SHARDED checkpoint (per-slot
    shard files, no save-time gather) restores BIT-identically onto
    (4,2), (8,1) and (2,2) meshes — and onto the plain 1-D mesh — all
    equal to the gathered baseline written by an identical run, with the
    resharding engine's measured peak host staging far below the full
    pytree (no host ever holds the gathered model; HostBytesProbe)."""
    import ddp_tpu.models.deepnn as deepnn_mod
    monkeypatch.setattr(deepnn_mod, "DROPOUT_RATE", 0.0)
    from ddp_tpu.train.checkpoint import load_checkpoint
    from ddp_tpu.train.ckpt_shard import HostBytesProbe, load_for_mesh
    model, params0, stats = deepnn_params
    mesh24 = make_mesh(shape=(2, 4))
    plan24 = plan_for_model("deepnn", params0, stats, model_size=4)
    g_path = str(tmp_path / "gathered.pt")
    s_path = str(tmp_path / "sharded.pt")

    tg = _make_trainer(model, params0, stats, mesh24, plan24, g_path,
                       tmp_path)
    tg.train(1)
    f_base = _flat(load_checkpoint(g_path).params)

    ts = _make_trainer(model, params0, stats, mesh24, plan24, s_path,
                       tmp_path, ckpt_format="sharded")
    ts.train(1)
    # The sharded set's canonical assembly equals the gathered file.
    np.testing.assert_array_equal(_flat(load_checkpoint(s_path).params),
                                  f_base)
    import os
    assert [n for n in os.listdir(tmp_path) if ".shard" in n], \
        "sharded save wrote no shard files"

    full_bytes = f_base.nbytes * 2  # params + momentum (fp32, stats empty)
    for shape in [(4, 2), (8, 1), (2, 2), None]:
        if shape is None:
            mesh, plan = make_mesh(8), None
        else:
            mesh = make_mesh(shape=shape)
            plan = plan_for_model("deepnn", params0, stats,
                                  model_size=shape[1])
        # The engine itself: bit-identity + the peak-bytes acceptance.
        probe = HostBytesProbe()
        ck = load_for_mesh(s_path, mesh,
                           param_specs=None if plan is None
                           else plan.param_specs, probe=probe)
        np.testing.assert_array_equal(_flat(ck.params), f_base)
        assert probe.current == 0  # every staging buffer released
        assert probe.peak < full_bytes / 2, \
            (f"restore onto {shape} staged {probe.peak} host bytes — "
             f"more than half the {full_bytes}-byte pytree; the engine "
             "is gathering")
        # The trainer path on top: elastic resume onto the new mesh.
        resumed = _make_trainer(model, params0, stats, mesh, plan, s_path,
                                tmp_path, resume=True, save_every=10**9)
        assert resumed.start_epoch == 1
        np.testing.assert_array_equal(_flat(resumed.state.params), f_base)
        np.testing.assert_array_equal(
            _flat(resumed.state.opt_state.momentum_buf),
            _flat(load_checkpoint(g_path).opt_state.momentum_buf))
        if plan is not None:
            live = jax.tree_util.tree_map(lambda a: a.sharding.spec,
                                          resumed.state.params)
            assert live == plan.param_specs
    # Continued training from the resharded restore matches the
    # never-interrupted 1-D reference (the established trajectory bound).
    ref = _make_trainer(model, params0, stats, make_mesh(8), None,
                        str(tmp_path / "ref.pt"), tmp_path)
    ref.train(2)
    resumed = _make_trainer(model, params0, stats, make_mesh(8), None,
                            s_path, tmp_path, resume=True,
                            save_every=10**9)
    resumed.train(2)
    np.testing.assert_allclose(_flat(resumed.state.params),
                               _flat(ref.state.params), atol=1e-5, rtol=0)


def test_tp_resident_epoch_matches_streaming(deepnn_params, tmp_path):
    """--resident composed with the tp plan: the scan-per-epoch program on
    a (2,4) mesh is bit-identical to the streaming tp step (same mesh ->
    same RNG stream; dropout ON)."""
    model, params0, stats = deepnn_params
    mesh = make_mesh(shape=(2, 4))
    plan = plan_for_model("deepnn", params0, stats, model_size=4)
    a = _make_trainer(model, params0, stats, mesh, plan,
                      str(tmp_path / "a.pt"), tmp_path,
                      device_augment=True)
    a.train(1)
    b = _make_trainer(model, params0, stats, mesh, plan,
                      str(tmp_path / "b.pt"), tmp_path, resident=True,
                      device_augment=True)
    b.train(1)
    np.testing.assert_array_equal(_flat(b.state.params),
                                  _flat(a.state.params))
    assert b.loss_history == a.loss_history
