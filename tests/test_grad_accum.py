"""Gradient accumulation (--grad_accum / make_train_step(accum=True)).

Ground truth is hand-composed from the same building blocks: A separate
forward/backwards on the micro-batches (BN stats chained in order), mean of
the gradients, one SGD update — torch's no_sync()+step-every-A semantics.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.data import TrainLoader, synthetic
from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel import make_mesh
from ddp_tpu.train import Trainer, make_train_step, shard_batch
from ddp_tpu.train.step import init_train_state, shard_batch_stacked


def _setup(n_mesh, model_name="vgg"):
    mesh = make_mesh(n_mesh)
    model = get_model(model_name)
    params, stats = model.init(jax.random.key(0))
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=1,
                              steps_per_epoch=4)
    return mesh, model, params, stats, sched


def test_accum_of_one_equals_plain_step():
    """A=1 must reproduce make_train_step exactly — same rng folds, same
    math, one micro-batch.  VGG specifically: it is dropout-free, so the
    exact-equality claim isolates the accumulation wiring (DeepNN's
    dropout draws fold the rng differently between the plain and scanned
    paths — measured 4.5e-4 rel loss difference — which is an expected
    property of the rng plumbing, not an accumulation bug)."""
    mesh, model, params, stats, sched = _setup(4)
    cfg = SGDConfig(lr=0.1)
    ds, _ = synthetic(n_train=16, seed=3)
    rng = jax.random.key(7)

    plain = make_train_step(model, cfg, sched, mesh)
    s_plain = init_train_state(*jax.tree_util.tree_map(jnp.array,
                                                       (params, stats)))
    b = shard_batch({"image": ds.images, "label": ds.labels}, mesh)
    for _ in range(2):
        s_plain, l_plain = plain(s_plain, b, rng)

    accum = make_train_step(model, cfg, sched, mesh, accum=True)
    s_acc = init_train_state(*jax.tree_util.tree_map(jnp.array,
                                                     (params, stats)))
    b1 = shard_batch_stacked({"image": ds.images[None], "label":
                              ds.labels[None]}, mesh)
    for _ in range(2):
        s_acc, l_acc = accum(s_acc, b1, rng)

    np.testing.assert_allclose(float(l_acc), float(l_plain), rtol=1e-6)
    for a, b_ in zip(jax.tree_util.tree_leaves(s_plain.params),
                     jax.tree_util.tree_leaves(s_acc.params)):
        # atol 5e-7 (was 1e-7): the plain and scanned programs compile
        # separately, and XLA may tile the bn_relu VJP's channel
        # reductions differently inside a scan body than inline —
        # measured up to 2e-7 abs on a handful of conv-kernel entries
        # after 2 steps.  Same math, different reduction order; anything
        # semantic (a missed rng fold, stats chaining) shows up orders of
        # magnitude larger (see the DeepNN note above: 4.5e-4).
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-6, atol=5e-7)


def test_accum_matches_hand_composition():
    """A=2: scanned accumulation == two manual loss_and_grads calls with
    chained BN stats, averaged grads, one SGD update."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from ddp_tpu.optim import sgd as sgd_lib
    from ddp_tpu.parallel.mesh import DATA_AXIS, replicated_sharding
    from ddp_tpu.train.step import make_loss_and_grads

    mesh, model, params, stats, sched = _setup(4)
    cfg = SGDConfig(lr=0.1)
    ds, _ = synthetic(n_train=32, seed=3)
    imgs = ds.images.reshape(2, 16, 32, 32, 3)
    labels = ds.labels.reshape(2, 16)
    rng = jax.random.key(7)

    accum = make_train_step(model, cfg, sched, mesh, accum=True)
    s_acc = init_train_state(*jax.tree_util.tree_map(jnp.array,
                                                     (params, stats)))
    batch = shard_batch_stacked({"image": imgs, "label": labels}, mesh)
    s_acc, loss_acc = accum(s_acc, batch, rng)

    # Manual composition inside one shard_map (same rng fold structure).
    lg = make_loss_and_grads(model)

    def body(params, stats, imgs, labels, rng):
        rng = jax.random.fold_in(rng, jnp.zeros((), jnp.int32))  # step 0
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        g_acc, l_acc = None, 0.0
        for k in range(2):
            mrng = jax.random.fold_in(rng, jnp.asarray(k, jnp.int32))
            loss, stats, grads = lg(params, stats, imgs[k], labels[k], mrng)
            g_acc = grads if g_acc is None else jax.tree_util.tree_map(
                jnp.add, g_acc, grads)
            l_acc = l_acc + loss
        grads = jax.tree_util.tree_map(lambda g: g / 2, g_acc)
        new_params, _ = sgd_lib.apply_updates(
            params, grads, sgd_lib.init(params), sched(jnp.zeros(())), cfg)
        return new_params, stats, l_acc / 2

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(None, DATA_AXIS), P(None, DATA_AXIS), P()),
        out_specs=(P(), P(), P()))
    rep = replicated_sharding(mesh)
    want_params, want_stats, want_loss = jax.jit(
        mapped, out_shardings=(rep, rep, rep))(
        params, stats, jnp.asarray(imgs), jnp.asarray(labels), rng)

    np.testing.assert_allclose(float(loss_acc), float(want_loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(want_params),
                    jax.tree_util.tree_leaves(s_acc.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(want_stats),
                    jax.tree_util.tree_leaves(s_acc.batch_stats)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


def test_trainer_grad_accum_end_to_end():
    """Trainer groups loader batches; ragged tail forms its own group;
    optimizer steps (= loss count = LR steps) reflect the grouping."""
    train_ds, _ = synthetic(n_train=72, seed=5)  # 4 full batches of 16 + 8
    mesh = make_mesh(2)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=2,
                         augment=False, seed=1)
    assert len(loader) == 5  # 4 full + ragged tail of 4/shard
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=1,
                              steps_per_epoch=3)
    tr = Trainer(model, loader, params, stats, mesh=mesh, lr_schedule=sched,
                 sgd_config=SGDConfig(lr=0.05), save_every=10**9,
                 snapshot_path=None, grad_accum=2)
    tr.train(1)
    # Groups: [2 full], [2 full], [ragged tail alone] -> 3 optimizer steps.
    assert len(tr.loss_history) == 3
    assert int(tr.state.step) == 3
    assert all(np.isfinite(l) for l in tr.loss_history)


@pytest.mark.extended  # accum x augment; default reprs: test_resident_matches_streaming_device_augment + test_device_augment.py unit tests
def test_accum_with_device_augment():
    """grad_accum composes with on-device augmentation: finite losses,
    correct optimizer-step count, and a trajectory distinct from the
    unaugmented one (the augmentation is actually applied per micro)."""
    train_ds, _ = synthetic(n_train=64, seed=5)
    mesh = make_mesh(2)
    model = get_model("deepnn")
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=1,
                              steps_per_epoch=2)

    def run(device_augment):
        params, stats = model.init(jax.random.key(0))
        loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=2,
                             augment=False, seed=1)
        tr = Trainer(model, loader, params, stats, mesh=mesh,
                     lr_schedule=sched, sgd_config=SGDConfig(lr=0.05),
                     save_every=10**9, snapshot_path=None, grad_accum=2,
                     device_augment=device_augment)
        tr.train(1)
        return tr

    aug, plain = run(True), run(False)
    assert len(aug.loss_history) == 2 and int(aug.state.step) == 2
    assert all(np.isfinite(l) for l in aug.loss_history)
    # The crop/flip changes the inputs, so the trajectories cannot be
    # identical (the magnitude is tiny after 2 steps — measured ~1e-6).
    assert aug.loss_history != plain.loss_history
