"""Weight-update sharding (ZeRO-1-style) — an optional TPU-native superset.

The reference replicates optimizer state per rank (plain DDP,
multigpu.py:89; SURVEY.md §2 checklist "ZeRO/FSDP: not built").  This module
adds the classic XLA weight-update-sharding pattern on top of the same
data-parallel semantics (cf. "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training", arXiv:2004.13336 — listed in PAPERS.md):

    per-shard backward  ->  psum_scatter(grads)     [1/R of the all-reduce]
                        ->  momentum+SGD on the local 1/R parameter slice
                        ->  all_gather(params)      [the other 1/R]

Communication volume equals the plain all-reduce (reduce-scatter +
all-gather IS how XLA lowers an all-reduce), but the momentum buffer and
the weight update shrink to 1/R per chip — the memory/compute win that
matters at scale, expressed with explicit ICI collectives over the same
1-D ``data`` mesh.  The pair is a checked invariant: the program auditor
(``python -m ddp_tpu.analysis``) requires exactly one
``reduce_scatter`` + one ``all_gather`` over ``data`` in every ZeRO
update's jaxpr — and zero of either in any non-ZeRO program.

Numerically identical to the replicated path modulo collective reduction
order (pinned by tests/test_zero.py).  BatchNorm stays per-shard by default;
``sync_bn=True`` psums the batch statistics exactly like the replicated
path's opt-in (multigpu.py:127's commented-out SyncBatchNorm).

This module holds what is ZeRO's own: the flat momentum's constructors and
converters, the local-grads core (:func:`_make_local_grads`) and the
sharded update (:func:`_make_zero_update`).  The programs are built by the
two train builders (:func:`~ddp_tpu.train.step.make_train_step`,
:func:`~ddp_tpu.train.epoch.make_train_epoch`) with ``shard_update=True``,
which compose it with every execution strategy the replicated update
supports — streaming per-step, gradient accumulation, the device-resident
scan-per-epoch — through the one wiring function
(:func:`~ddp_tpu.train.step.make_step_wiring`), so they cannot drift from
one another.

Implementation note: the wiring runs these steps under
``shard_map(..., check_vma=False)`` because the varying-axes type system
has no way (in this JAX version) to re-mark an ``all_gather`` result as
replicated; with the check off, the gradient psum is NOT auto-inserted,
which is exactly what lets us reduce-*scatter* instead.  Every collective
here is therefore explicit, and the differentiated objective is the *local*
``ce_sum/(count*R)`` whose shard-sum is the global-mean loss: the transpose
of any ``psum`` inside the forward (sync-BN statistics) then contributes
exactly the cross-shard cotangents of that summed objective, while the loss
itself is deliberately NOT psum'd inside ``jax.grad``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import sgd as sgd_lib
from ..ops.losses import cross_entropy_sum_count
from ..parallel.mesh import (DATA_AXIS, MODEL_AXIS, data_axis_size,
                             replicated_sharding)
from .step import TrainState, _as_input


def padded_size(params, axis_size: int) -> int:
    """Flat parameter count padded up to a multiple of the mesh size."""
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params))
    return n + (-n) % axis_size


def _put_flat_sharded(flat_np: np.ndarray, mesh: Mesh) -> jax.Array:
    """Host flat array (same on every process) -> device array sharded on
    ``data``.  ``make_array_from_callback`` works across processes, where a
    plain ``device_put`` to a cross-process sharding would not."""
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    return jax.make_array_from_callback(flat_np.shape, sharding,
                                        lambda idx: flat_np[idx])


def init_opt_shard(params, mesh: Mesh, plan=None) -> sgd_lib.SGDState:
    """Momentum as ONE flat global array sharded over ``data`` — each chip
    holds 1/R of it (vs. a full replica in the plain path).

    With a tp ``plan`` (2-D mesh) the buffer is ``[m, L]`` sharded
    ``P(model, data)`` — the spec-merge of params-along-``model`` with
    update-along-``data``: row j is model shard j's flat local parameter
    vector (its slices of the sharded leaves plus the replicated leaves),
    of which each data shard owns 1/d.  Each chip then holds
    ``local_params/d`` momentum — BOTH savings compose."""
    if plan is None:
        n_pad = padded_size(params, mesh.devices.size)
        return sgd_lib.SGDState(
            _put_flat_sharded(np.zeros(n_pad, np.float32), mesh))
    from ..parallel.tp.plan import local_param_count
    d = data_axis_size(mesh)
    n = local_param_count(plan)
    n_pad = n + (-n) % d
    sharding = NamedSharding(mesh, P(MODEL_AXIS, DATA_AXIS))
    zeros = np.zeros((plan.model_size, n_pad), np.float32)
    return sgd_lib.SGDState(jax.make_array_from_callback(
        zeros.shape, sharding, lambda idx: zeros[idx]))


def opt_shard_to_pytree(params, opt_state: sgd_lib.SGDState, mesh: Mesh,
                        plan=None):
    """Sharded flat momentum -> the canonical per-leaf pytree (checkpoint
    format stays identical across modes, so snapshots are interchangeable).

    COLLECTIVE under multi-host: the buffer spans other processes' chips,
    so it is resharded to replicated (an all-gather over ICI/DCN) — EVERY
    process must call this, even though only rank 0 writes the file
    (Trainer.train orders it so).  Everything stays ON DEVICE (fresh
    replicated arrays, async-dispatched): the caller can hand the result
    to the async checkpoint writer without this function having blocked
    the training loop on a device->host read.

    With a tp ``plan`` the ``[m, L]`` buffer unravels through a shard_map
    (each model shard's row is ITS local parameter layout), emerging as a
    plan-sharded per-leaf pytree; the Trainer's checkpoint gather then
    replicates it along with the params (one collective path for all
    leaves).
    """
    if plan is not None:
        p_specs = plan.param_specs

        def body(p, buf):
            flat, unravel = ravel_pytree(p)
            full = lax.all_gather(buf[0], DATA_AXIS, axis=0, tiled=True)
            return unravel(full[:flat.shape[0]])

        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, P(MODEL_AXIS, DATA_AXIS)),
            out_specs=p_specs, check_vma=False)
        return sgd_lib.SGDState(jax.jit(mapped)(params,
                                                opt_state.momentum_buf))
    flat, unravel = ravel_pytree(params)
    n = flat.shape[0]
    # The truncating slice AND the unravel reshapes run INSIDE the jit:
    # eager ops on arrays spanning other processes' devices are
    # version-sensitive under multi-host, while jitted computation on them
    # is the supported path (all device computation stays inside jit).
    tree = jax.jit(lambda x: unravel(x[:n]),
                   out_shardings=replicated_sharding(mesh))(
        opt_state.momentum_buf)
    return sgd_lib.SGDState(tree)


def pytree_to_opt_shard(momentum_pytree, mesh: Mesh,
                        plan=None) -> sgd_lib.SGDState:
    """Canonical momentum pytree -> sharded flat buffer (resume path).
    With a tp ``plan``: canonical (replicated, host or device) pytree ->
    the ``[m, L]`` ``P(model, data)`` buffer, via a shard_map in which
    each device ravels its model shard's leaf slices and keeps its own
    1/d block — the exact inverse of :func:`opt_shard_to_pytree`'s tp
    path (round-trip pinned in tests/test_tp.py)."""
    if plan is not None:
        from ..parallel.tp.plan import local_param_count, state_shardings
        d = data_axis_size(mesh)
        n = local_param_count(plan)
        n_pad = n + (-n) % d
        sharded_tree = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, momentum_pytree),
            state_shardings(plan, mesh).params)

        def body(tree):
            flat, _ = ravel_pytree(tree)
            padded = jnp.pad(flat, (0, n_pad - flat.shape[0]))
            block = lax.dynamic_slice(
                padded, (lax.axis_index(DATA_AXIS) * (n_pad // d),),
                (n_pad // d,))
            return block[None]

        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=(plan.param_specs,),
            out_specs=P(MODEL_AXIS, DATA_AXIS), check_vma=False)
        return sgd_lib.SGDState(jax.jit(mapped)(sharded_tree))
    flat, _ = ravel_pytree(momentum_pytree)
    n_pad = padded_size(momentum_pytree, mesh.devices.size)
    flat_np = np.zeros(n_pad, np.float32)
    flat_np[:flat.shape[0]] = np.asarray(flat)
    return sgd_lib.SGDState(_put_flat_sharded(flat_np, mesh))


def _make_local_grads(model, R: int, compute_dtype=None,
                      sync_bn: bool = False, tp_axis=None, tp_recipe=None):
    """Per-shard forward/backward of the collective-free LOCAL objective
    ``ce_sum/(count*R)``: its sum over the R shards is the global-mean loss
    (equal per-shard counts — the sampler padding guarantee,
    multigpu.py:153), so the psum_scatter of these local grads is exactly
    the replicated path's gradient.  Returns
    ``fn(params, stats, images, labels, rng) -> (loss, stats, grads)`` —
    the same signature and return order as
    :func:`~ddp_tpu.train.step.make_loss_and_grads`, so the two cores are
    interchangeable under :func:`~ddp_tpu.train.step.make_accum_scan`;
    ``loss`` is the psum'd global mean and ``stats`` pmean'd.

    ``tp_axis`` (tensor parallelism): R stays the DATA-axis shard count —
    the model-axis devices in one data row consume the same rows and the
    local objective must still sum to the global mean over ``data`` alone.
    "Collective-free" then means free of collectives whose transposes
    produce cross-shard cotangents: the tp forward's row-parallel psums
    over ``tp_axis`` carry identity transposes
    (parallel/tp/layers.py:psum_keepgrad), so the backward stays local per
    (data, model) device.  This core is shared by the sharded-update path
    here AND the replicated-update tp core
    (:func:`~ddp_tpu.train.step.make_loss_and_grads_tp`).

    ``tp_recipe`` (auto plans, parallel/tp/autoplan.py) overrides the
    model module's TP_RECIPE with an explicit per-layer style mapping;
    ``None`` keeps apply's default — so hand plans trace with no extra
    kwarg, byte-identically to before the auto path existed.
    """

    def local_grads(params, batch_stats, images, labels, rng):
        def local_loss_fn(params):
            from ..ops.layers import bn_sync_axis
            with bn_sync_axis(DATA_AXIS if sync_bn else None):
                logits, new_stats = model.apply(
                    params, batch_stats, _as_input(images, compute_dtype),
                    train=True, rng=rng, compute_dtype=compute_dtype,
                    **({} if tp_axis is None else {"tp_axis": tp_axis}),
                    **({} if tp_recipe is None
                       else {"tp_recipe": tp_recipe}))
            ce_sum, count = cross_entropy_sum_count(logits, labels)
            return ce_sum / (count * R), (new_stats, ce_sum, count)

        grads, (new_stats, ce_sum, count) = jax.grad(
            local_loss_fn, has_aux=True)(params)
        loss = lax.psum(ce_sum, DATA_AXIS) / lax.psum(count, DATA_AXIS)
        new_stats = jax.tree_util.tree_map(
            lambda s: lax.pmean(s, DATA_AXIS), new_stats)
        return loss, new_stats, grads

    return local_grads


def _make_zero_update(sgd_config: sgd_lib.SGDConfig,
                      lr_schedule: Callable[[jax.Array], jax.Array], R: int,
                      tp: bool = False):
    """The sharded update stage: local grads -> psum_scatter -> torch-SGD on
    the 1/R slice -> all_gather.  ``fn(state, grads, new_stats) -> state``.

    ``tp=True``: R is the DATA-axis size, params/grads are this model
    shard's local slices, and the momentum block carries the ``[1, L/d]``
    shape of the ``P(model, data)`` buffer — everything else (the flat
    ravel, the data-axis collectives, the torch SGD convention) is
    IDENTICAL, which is why the two modes compose rather than multiply.
    """
    mu, wd = sgd_config.momentum, sgd_config.weight_decay

    def zero_update(state: TrainState, grads, new_stats):
        flat_g, _ = ravel_pytree(grads)
        flat_p, unravel = ravel_pytree(state.params)
        n = flat_p.shape[0]
        n_pad = n + (-n) % R
        g_shard = lax.psum_scatter(jnp.pad(flat_g, (0, n_pad - n)),
                                   DATA_AXIS, scatter_dimension=0,
                                   tiled=True)
        p_shard = lax.dynamic_slice(
            jnp.pad(flat_p, (0, n_pad - n)),
            (lax.axis_index(DATA_AXIS) * (n_pad // R),), (n_pad // R,))
        mom = state.opt_state.momentum_buf
        if tp:
            mom = mom[0]
        # Torch SGD convention on the slice (optim/sgd.py): wd folded into
        # the gradient before the momentum trace, no decoupling.
        buf = mu * mom + g_shard + wd * p_shard
        lr_t = lr_schedule(state.step)
        new_p_shard = p_shard - lr_t * buf
        flat_new = lax.all_gather(new_p_shard, DATA_AXIS, axis=0, tiled=True)
        params = unravel(flat_new[:n])
        return TrainState(params, new_stats,
                          sgd_lib.SGDState(buf[None] if tp else buf),
                          state.step + 1)

    return zero_update
