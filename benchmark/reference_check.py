"""``correct`` (a): one optimizer step of the system against the
configuration's plain float32 reference, on a seeded batch, in set-up.

The system's side is built by the program's own step builders with the
cell's model, precision, optimizer and schedule: the per-step program
where batches stream (``make_train_step``: the program the Trainer
dispatches), the scanned epoch program over a one-row index matrix where
they are resident (``make_train_epoch``: gather from the table in HBM,
scan, the same step body).  Augmentation is off on both sides, because
its random crops are the program's own stream and no reference can draw
them; it is input preparation, not the model's arithmetic.

The step is taken at the schedule's peak (``state.step`` set there): at
batch 0 the reference scripts' schedule gives a rate of exactly 0 and the
parameters would not move.  Compared: the loss, the new momentum buffers
(the gradient plus the decay term), the parameters' change, the running
statistics, and the decay term by itself (see ``_decay_share``).
Tolerances and their reasons: ``reference/common.py``.
"""
from __future__ import annotations

import importlib

import numpy as np


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _decay_share(buf, p) -> float:
    """<buf, p> / <p, p>.  Where a layer's output is normalised (every
    convolution here is followed by BN), the loss does not change with
    the kernel's scale, so <grad, kernel> is 0 and this is exactly the
    weight decay: a term 100 to 1000 times smaller than the gradient,
    which no tolerance on the buffer itself could see."""
    p = np.asarray(p, np.float64).ravel()
    return float(np.asarray(buf, np.float64).ravel() @ p / (p @ p))


def first_step(*, config, mix, mesh, model, sgd, schedule, sched_kw,
               compute_dtype, params_host, stats_host, images, labels,
               trainer) -> dict:
    import jax
    import jax.numpy as jnp

    from ddp_tpu.optim.sgd import SGDState
    from ddp_tpu.parallel.mesh import replicated_sharding
    from ddp_tpu.train.step import TrainState

    from .reference import common

    chips = mesh.devices.size
    n = int(mix["check_batch_per_chip"]) * chips
    if n > len(images):
        raise ValueError(f"the first-step check takes {n} samples, the "
                         f"data set has {len(images)}")
    x, y = images[:n], labels[:n]
    check_step = int(round(sched_kw["peak_frac"] * sched_kw["num_epochs"]
                           * sched_kw["steps_per_epoch"]))
    rep = replicated_sharding(mesh)
    zeros = jax.tree_util.tree_map(np.zeros_like, params_host)
    state = jax.device_put(
        TrainState(params_host, stats_host, SGDState(zeros),
                   np.asarray(check_step, np.int32)), rep)

    if mix["resident"]:
        from ddp_tpu.train.epoch import make_train_epoch, put_index_matrix
        fn = make_train_epoch(model, sgd, schedule, mesh,
                              compute_dtype=compute_dtype,
                              device_augment=False)
        idx = put_index_matrix(np.arange(n, dtype=np.int32)[None, :], mesh)
        new, loss = fn(state, trainer.resident.images,
                       trainer.resident.labels, idx, trainer.rng)
        loss = loss[0]
    else:
        from ddp_tpu.train.step import make_train_step, shard_batch
        fn = make_train_step(model, sgd, schedule, mesh,
                             compute_dtype=compute_dtype,
                             device_augment=False)
        new, loss = fn(state, shard_batch({"image": x, "label": y}, mesh),
                       trainer.rng)
    sys_loss = float(loss)
    sys_params, sys_stats, sys_buf = jax.device_get(
        (new.params, new.batch_stats, new.opt_state.momentum_buf))

    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    lr = common.triangular_lr(
        check_step, peak_lr=sgd.lr,
        schedule_epochs=sched_kw["num_epochs"],
        steps_per_epoch=sched_kw["steps_per_epoch"],
        peak_frac=sched_kw["peak_frac"])
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads, ref_params, ref_buf, ref_stats = jax.device_get(
            jax.jit(lambda p, s, b, xi, yi: common.sgd_step(
                ref.forward(config), p, s, b, xi, yi, lr=lr,
                momentum=sgd.momentum, weight_decay=sgd.weight_decay,
                n_shards=chips))(params_host, stats_host, zeros,
                                 jnp.asarray(x), jnp.asarray(y)))

    def flat(tree):
        return [(jax.tree_util.keystr(k), np.asarray(v, np.float64))
                for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]]

    def whole(tree):
        return np.concatenate([v.ravel() for _, v in flat(tree)])

    tol = common.TOLERANCE
    p0 = flat(params_host)
    sys_b, ref_b, ref_g = flat(sys_buf), flat(ref_buf), flat(ref_grads)
    by_leaf = {k: _rel_l2(a, b) for (k, a), (_, b) in zip(sys_b, ref_b)}
    # The decay term, over the leaves where it is all that <buf, p> holds
    # (the reference's own gradient is orthogonal to the leaf), summed so
    # that the large kernels, where rounding averages out, carry it.
    free = [i for i, ((_, g), (_, p)) in enumerate(zip(ref_g, p0))
            if np.any(p) and abs(_decay_share(g, p))
            < 0.01 * sgd.weight_decay]
    pp = sum(float(p0[i][1].ravel() @ p0[i][1].ravel()) for i in free)
    dots = [sum(float(b[i][1].ravel() @ p0[i][1].ravel()) for i in free)
            for b in (sys_b, ref_b)]
    errs = {
        "loss_abs": abs(sys_loss - float(ref_loss)),
        "momentum_rel": _rel_l2(whole(sys_buf), whole(ref_buf)),
        "momentum_rel_best": min(by_leaf.values()),
        "update_rel": _rel_l2(whole(sys_params) - whole(params_host),
                              whole(ref_params) - whole(params_host)),
        "stats_rel": max(_rel_l2(a, b) for (_, a), (_, b) in zip(
            flat(sys_stats), flat(ref_stats))),
        "decay_rel": (abs(dots[0] - dots[1]) / (sgd.weight_decay * pp)
                      if free else float("inf")),
    }
    worst = max(by_leaf, key=by_leaf.get)
    info = {"momentum_rel_worst_leaf": [worst, by_leaf[worst]],
            "scale_free_leaves": len(free)}
    ok = all(np.isfinite(v) and v <= tol[k] for k, v in errs.items())
    return {"ok": bool(ok), "step": check_step, "lr": lr, "batch": n,
            "loss": sys_loss, "reference_loss": float(ref_loss),
            "errors": errs, "info": info, "tolerance": tol}
