"""Whole-epoch jitted training: one ``lax.scan`` over the epoch's batches.

The reference dispatches one forward/backward per Python loop iteration
(``Trainer._run_epoch``'s batch loop, multigpu.py:104-107), paying a host
round trip and a host->device copy of every batch.  On TPU both costs are
avoidable for a dataset the size of CIFAR-10 (~180 MB uint8 — noise next to
HBM): keep the *entire* training set resident on device
(data/resident.py), upload only the epoch's sample-index matrix (~200 KB),
and run the epoch as a single jitted ``shard_map`` program whose body is
``lax.scan`` over the shared per-step body (:func:`~ddp_tpu.train.step.make_group_step`) — the exact
same per-batch math the per-step path runs, so the two strategies are
bit-identical (pinned by tests/test_resident.py).

Per step the only host involvement is *nothing*: gather the batch by index
from the resident array, augment on device (RandomCrop+HFlip,
data/device_augment.py), normalise, forward/backward, psum, update — 98
steps, one dispatch.  This is the idiomatic-XLA expression of an epoch:
static shapes, compiler-visible loop, zero host sync (SURVEY.md §7
hard-part #4 dissolves rather than being mitigated).

The sampler semantics are untouched: the index matrix comes from the same
``DistributedSampler``-exact host samplers (data/sampler.py,
multigpu.py:153), so device r still sees precisely rank r's reference data
stream and BN statistics stay per-shard (multigpu.py:127).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import sgd as sgd_lib
from ..parallel.mesh import (DATA_AXIS, MODEL_AXIS, replicated_sharding,
                             scan_unroll)
from .step import (TrainState, make_accum_scan, make_eval_apply,
                   make_group_step, make_single_micro, make_step_wiring,
                   micro_from_table)


def make_train_epoch(model, sgd_config: sgd_lib.SGDConfig,
                     lr_schedule: Callable[[jax.Array], jax.Array],
                     mesh: Mesh, *, compute_dtype=None,
                     device_augment: bool = False, sync_bn: bool = False,
                     plan=None, accum: bool = False,
                     shard_update: bool = False):
    """Build the jitted scan-per-epoch train function over ``mesh``.

    Returns ``epoch_fn(state, images, labels, idx, rng) -> (state, losses)``
    where ``images``/``labels`` are the device-resident dataset (replicated,
    data/resident.py: ``images`` a :class:`~ddp_tpu.ops.gather.RowTable`,
    never reshaped or copied in here), ``idx`` is an int32
    ``[steps, global_batch]`` matrix of sample indices sharded on its
    batch (last) axis, and ``losses`` is
    the per-step global-mean loss vector ``[steps]`` — the loss stream the
    reference never logs (SURVEY.md §5).

    Distinct ``idx`` shapes (e.g. the ragged final batch, 50000 % 512 != 0 —
    singlegpu.py:179 semantics) compile once each and are cached by jit.
    ``plan`` (tp) runs the tensor-parallel per-step body inside the same
    scan — the resident dataset stays replicated, ``idx`` stays sharded on
    ``data`` only.

    ``accum=True`` (``--resident`` with ``--grad_accum``): ``idx`` is
    ``[G, A, global_batch]`` — G optimizer-step groups of A micro-batches.
    The outer scan runs one optimizer step per group; the inner scan
    (:func:`~ddp_tpu.train.step.make_accum_scan`) accumulates gradients
    over the group's micro-batches with BN stats chained in micro-batch
    order, and ``losses[g]`` is the mean of group g's micro-batch losses.
    Ragged groups (the epoch's remainder of full batches, and the final
    ragged batch — drop_last=False, singlegpu.py:179) arrive as separate
    calls with their own ``[1, A', B']`` shapes.  ``shard_update=True``
    (``--shard_update``) swaps in ZeRO's update, one per step or group.

    The keywords mean what they mean to
    :func:`~ddp_tpu.train.step.make_train_step`, the wiring and the RNG
    fold structure are the same, so the epoch program and the step
    program produce the same trajectory in every combination (pinned by
    tests/test_resident.py).
    """
    core, update, st_specs, st_sh, extra = make_step_wiring(
        model, sgd_config, lr_schedule, mesh, compute_dtype=compute_dtype,
        sync_bn=sync_bn, plan=plan, shard_update=shard_update)

    def _shard_body(state: TrainState, images, labels, idx, rng):
        get_micro = micro_from_table(images, labels, device_augment)
        if accum:
            # Nested unrolls multiply: BOTH scans are gated on the PRODUCT
            # G*A of inlined conv bodies, not their own lengths alone
            # (ADVICE r5).  Gating the inner scan on A only would,
            # whenever A <= 32 < G*A, fully unroll A fwd+bwd bodies INSIDE
            # a rolled while loop — exactly the pathological XLA:CPU
            # conv-in-rolled-loop shape scan_unroll exists to avoid.
            # Product-gated, the two scans are always rolled/unrolled
            # together.
            total = idx.shape[0] * idx.shape[1]
            scan = make_accum_scan(
                core, unroll_fn=lambda _a: scan_unroll(mesh, total))
            group = make_group_step(
                lambda p, s, xs, g: scan(p, s, xs, get_micro, g), update)
        else:
            total = idx.shape[0]
            group = make_group_step(make_single_micro(core, get_micro),
                                    update)
        return lax.scan(lambda st, idx_row: group(st, idx_row, rng),
                        state, idx, unroll=scan_unroll(mesh, total))

    idx_spec = P(None, None, DATA_AXIS) if accum else P(None, DATA_AXIS)
    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(st_specs, P(), P(), idx_spec, P()),
        out_specs=(st_specs, P()),
        **extra,
    )
    rep = replicated_sharding(mesh)
    return jax.jit(mapped, donate_argnums=(0,), out_shardings=(st_sh, rep))


def make_eval_epoch(model, mesh: Mesh, compute_dtype=None, plan=None):
    """Whole-test-set evaluation as one jitted scan: global (correct, total).

    The scan analogue of :func:`~ddp_tpu.train.step.make_eval_step` — same
    masked ``psum`` counters (the sharded replacement for the reference's
    redundant per-rank eval, multigpu.py:247), but the batch loop lives in
    the compiled program: ``eval_fn(params, batch_stats, images, labels,
    idx, mask) -> (correct, total)`` with ``idx``/``mask`` of shape
    ``[steps, global_batch]`` (indices padded to shape; ``mask`` zeroes the
    padding rows out of both counters).  ``plan`` (tp) shards the params
    over ``model``; the counters reduce over ``data`` only.
    """
    if plan is None:
        p_specs, s_specs, tp_axis, extra = P(), P(), None, {}
    else:
        p_specs, s_specs = plan.param_specs, plan.stats_specs
        tp_axis, extra = MODEL_AXIS, {"check_vma": False}
    apply_fn = make_eval_apply(model, compute_dtype, tp_axis=tp_axis)

    def _shard_body(params, batch_stats, images, labels, idx, mask):
        from ..ops.gather import gather_rows

        def one_step(carry, xs):
            idx_row, mask_row = xs
            logits = apply_fn(params, batch_stats,
                              gather_rows(images, idx_row))
            pred = jnp.argmax(logits, axis=-1)
            hit = (pred == labels[idx_row]).astype(jnp.float32)
            c, t = carry
            return (c + (hit * mask_row).sum(), t + mask_row.sum()), None

        # pcast-to-varying: the accumulators are per-shard (they consume the
        # sharded idx/mask), so the carry must enter the scan already marked
        # varying over ``data`` or its in/out vma types won't match.
        init = jax.lax.pcast((jnp.zeros(()), jnp.zeros(())), DATA_AXIS,
                             to="varying")
        (correct, total), _ = lax.scan(one_step, init, (idx, mask),
                                       unroll=scan_unroll(mesh,
                                                          idx.shape[0]))
        return lax.psum(correct, DATA_AXIS), lax.psum(total, DATA_AXIS)

    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(p_specs, s_specs, P(), P(), P(None, DATA_AXIS),
                  P(None, DATA_AXIS)),
        out_specs=(P(), P()),
        **extra,
    )
    rep = replicated_sharding(mesh)
    return jax.jit(mapped, out_shardings=(rep, rep))


def put_index_matrix(idx: np.ndarray, mesh: Mesh) -> jax.Array:
    """Host ``[steps, B]`` (or ``[G, A, B]`` for the accumulation epoch)
    matrix of indices or masks -> device array sharded on its LAST axis
    (the batch axis).

    Multi-host: each process passes the columns for its own replicas (the
    per-host slice the loader materialises) and the global matrix is
    assembled process-locally — the index-only analogue of
    :func:`~ddp_tpu.train.step.shard_batch`.
    """
    sharding = NamedSharding(mesh, P(*([None] * (idx.ndim - 1)), DATA_AXIS))
    idx = np.ascontiguousarray(idx)
    if jax.process_count() == 1:
        return jax.device_put(idx, sharding)
    from ..parallel.mesh import assemble_from_local  # explicit global shape
    return assemble_from_local(sharding, idx, idx.ndim - 1)
