"""The jitted SPMD train/eval steps — the heart of the framework.

The reference's ``Trainer._run_batch`` (singlegpu.py:102-108 /
multigpu.py:92-98) is: zero_grad → forward → ``F.cross_entropy`` → backward
(DDP fires a bucketed all-reduce-mean of gradients here, multigpu.py:96) →
``optimizer.step()`` → ``scheduler.step()``.  Here the whole sequence is ONE
jitted ``shard_map`` program over a 1-D ``data`` mesh:

- batch sharded on ``data``; params / momentum replicated (DDP's replicas);
- per-shard forward/backward — BatchNorm therefore uses *per-shard* batch
  statistics, exactly the reference's unsynced-BN semantics (SyncBatchNorm
  deliberately commented out at multigpu.py:127).  This is why the step uses
  ``shard_map`` rather than GSPMD-jit sharding constraints: under plain jit
  XLA computes BN statistics over the *global* batch, which would silently
  be sync-BN (SURVEY.md §7 hard-part #2);
- ``lax.pmean`` on gradients == DDP's all-reduce(mean); XLA lowers it to an
  ICI all-reduce and owns the overlap/scheduling DDP does with buckets;
- SGD + momentum update applied to the replicated params inside the same
  program (identical update per replica keeps them in lockstep, the same
  invariant DDP relies on at multigpu.py:97);
- the per-batch LR is passed in as a traced scalar so the per-step schedule
  (scheduler.step() per batch, singlegpu.py:108) never recompiles.

Every builder here is a registered audit target: ``python -m
ddp_tpu.analysis`` traces the built step and enforces its collective
shape declaratively (gradient psums on ``data`` only, donation of the
state, zero captured constants — analysis/programs.py names the
programs, analysis/jaxpr_audit.py the invariants).

Running BN buffers are ``pmean``-ed across shards before being returned —
a deliberate, documented deviation: the reference keeps per-rank buffers and
checkpoints rank 0's (multigpu.py:110); averaging is statistically at least
as good and keeps the returned state replicated.  Training-time
normalisation is unaffected (it uses batch stats).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import sgd as sgd_lib
from ..ops.losses import (LM_LOSS, DepthLogits, cross_entropy_sum_count,
                          depth_cross_entropy)
from ..parallel.mesh import (DATA_AXIS, MODEL_AXIS, assemble_from_local,
                             batch_sharding, data_axis_size, mesh_size,
                             replicated_sharding, scan_unroll)


def _as_input(x: jax.Array, compute_dtype=None) -> jax.Array:
    """Accept uint8 batches and apply ToTensor scaling (u8/255,
    singlegpu.py:158) on DEVICE: the loaders ship uint8 so each batch
    crosses the host->device link at 1/4 the bytes of fp32 — the transfer,
    not the chips, is the bottleneck on thin links.  Anything else goes
    through as it is: float images, and a token model's integer ids."""
    if x.dtype == jnp.uint8:
        return x.astype(compute_dtype or jnp.float32) / 255.0
    return x


class TrainState(NamedTuple):
    """Everything that evolves across steps, as one replicated pytree."""
    params: Any
    batch_stats: Any
    opt_state: sgd_lib.SGDState
    step: jax.Array  # int32 global batch index (drives the LR schedule)


def init_train_state(params, batch_stats) -> TrainState:
    return TrainState(params, batch_stats, sgd_lib.init(params),
                      jnp.zeros((), jnp.int32))


def make_loss_and_grads(model, compute_dtype=None, sync_bn: bool = False):
    """The forward/backward alone (no optimizer update), per shard:
    ``fn(params, batch_stats, images, labels, rng) -> (loss, stats, grads)``
    — the single core every execution strategy's step is assembled from
    (via :func:`make_single_micro` / :func:`make_accum_scan` +
    :func:`make_group_step`), so the strategies cannot drift numerically."""

    def loss_and_grads(params, batch_stats, images, labels, rng):
        def loss_fn(params):
            # sync_bn: BN statistics psum'd over the global batch — the
            # SyncBatchNorm the reference leaves commented out
            # (multigpu.py:127), as an opt-in (ops/layers.py:bn_sync_axis).
            # bn_grad_axis: this is the REPLICATED-params core, so the
            # fused bn_relu VJP must all-reduce its scale/bias cotangents
            # itself (custom_vjp opts out of shard_map's vma transpose
            # psum); the ZeRO local-grads core deliberately leaves it
            # unset.
            from ..ops.layers import bn_grad_axis, bn_sync_axis
            with bn_sync_axis(DATA_AXIS if sync_bn else None), \
                    bn_grad_axis(DATA_AXIS):
                logits, new_stats = model.apply(
                    params, batch_stats,
                    _as_input(images, compute_dtype), train=True,
                    rng=rng, compute_dtype=compute_dtype)
            # A model with more than one prediction depth: each depth's
            # global mean over its own counted positions, weighted; the
            # depths' own losses ride the model's state to the host.
            if isinstance(logits, DepthLogits):
                with jax.named_scope("lm_head"):
                    sums, counts = depth_cross_entropy(logits, labels)
                by_depth = (lax.psum(sums, DATA_AXIS)
                            / lax.psum(counts, DATA_AXIS))
                loss = jnp.dot(jnp.asarray(logits.weights, by_depth.dtype),
                               by_depth)
                return loss, {**new_stats, LM_LOSS: by_depth}
            # A token model's loss (logits [B,T,V] against per-position
            # labels, ignored positions left out of sum and count) is
            # named for the device trace; a classifier's keeps its names.
            with (jax.named_scope("lm_head") if logits.ndim == 3
                  else contextlib.nullcontext()):
                ce_sum, count = cross_entropy_sum_count(logits, labels)
            # Global mean: psum(sum)/psum(count).  Equal per-shard counts
            # (DistributedSampler padding guarantee, multigpu.py:153) make
            # this identical to DDP's mean-of-rank-means.
            loss = (lax.psum(ce_sum, DATA_AXIS)
                    / lax.psum(count, DATA_AXIS))
            return loss, new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # NO explicit gradient collective: differentiating w.r.t. the
        # replicated (in_specs=P()) params makes shard_map's autodiff
        # insert the psum over ``data`` itself (the transpose of
        # replication — vma semantics).  That auto-psum of the global-mean
        # loss IS DDP's bucketed all-reduce(mean) (multigpu.py:96); an
        # explicit pmean there would double-count by the mesh size
        # (tests/test_train_step.py pins this numerically).
        new_stats = jax.tree_util.tree_map(_reduce_state_leaf, new_stats,
                                           batch_stats)
        return loss, new_stats, grads

    return loss_and_grads


def _reduce_state_leaf(new: jax.Array, old: jax.Array) -> jax.Array:
    """A model-state leaf over the replicas: running statistics (float)
    are averaged; counters (integer: a token model's routing counts) grow
    by the SUM of what the replicas added this step."""
    if jnp.issubdtype(new.dtype, jnp.integer):
        return old + lax.psum(new - old, DATA_AXIS)
    return lax.pmean(new, DATA_AXIS)


def make_loss_and_grads_tp(model, data_size: int, compute_dtype=None,
                           sync_bn: bool = False, tp_recipe=None):
    """The tensor-parallel replicated-update gradient core: same signature
    and contract as :func:`make_loss_and_grads`, for a 2-D (data × model)
    mesh with params sharded per the tp plan (parallel/tp/plan.py).

    Built zero-style rather than by differentiating the psum'd loss: the
    per-shard backward differentiates the collective-free LOCAL objective
    ``ce_sum/(count*d)`` (train/zero.py:_make_local_grads, here with the
    model's ``tp_axis`` forward — whose only collectives, the row-parallel
    psums, carry identity transposes), then the grads are EXPLICITLY
    ``psum``-ed over ``data`` only.  The sum of the local objectives over
    the d data shards is the global-mean loss, so that psum IS the DDP
    all-reduce, and no collective is ever differentiated.
    Model-sharded leaves get their own slice's gradient (their data-axis
    replicas agree; no ``model``-axis gradient collective exists — axis
    correctness is the whole game, tests/test_tp.py pins it bitwise at
    m=1).  ``tp_recipe`` overrides the model module's TP_RECIPE with an
    explicit per-layer mapping (auto plans, parallel/tp/autoplan.py)."""
    from .zero import _make_local_grads
    local_grads = _make_local_grads(model, data_size, compute_dtype,
                                    sync_bn, tp_axis=MODEL_AXIS,
                                    tp_recipe=tp_recipe)

    def loss_and_grads(params, batch_stats, images, labels, rng):
        loss, new_stats, grads = local_grads(params, batch_stats, images,
                                             labels, rng)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, DATA_AXIS), grads)
        return loss, new_stats, grads

    return loss_and_grads


def _micro_from_batch(device_augment: bool):
    """``get_micro`` for streaming paths: the micro-batch IS the scanned
    ``{"image", "label"}`` dict, optionally device-augmented."""

    def get_micro(aug_rng, micro):
        images = micro["image"]
        if device_augment:
            from ..data.device_augment import random_crop_flip
            images = random_crop_flip(aug_rng, images)
        return images, micro["label"]

    return get_micro


def micro_from_table(images, labels, device_augment: bool):
    """``get_micro`` for device-resident paths: the scanned value is an
    index row into the HBM-resident dataset (``images`` a
    :class:`~ddp_tpu.ops.gather.RowTable`; Pallas DMA gather,
    ops/gather.py; gather+crop+flip under device augmentation)."""

    def get_micro(aug_rng, idx_row):
        if device_augment:
            from ..data.device_augment import gather_crop_flip
            return gather_crop_flip(aug_rng, images, idx_row), labels[idx_row]
        from ..ops.gather import gather_rows
        return gather_rows(images, idx_row), labels[idx_row]

    return get_micro


def make_single_micro(loss_and_grads, get_micro):
    """Adapt a per-micro core to :func:`make_group_step`'s ``group_grads``
    signature for the non-accumulating paths: one micro-batch IS the whole
    optimizer step.  ``fold_in(rng, 1)`` is the augmentation stream — every
    batch provider (streaming dict, resident index row) draws from the same
    key, so per-step and resident paths augment bit-identically."""

    def group_grads(params, stats, xs, rng):
        images, labels = get_micro(jax.random.fold_in(rng, 1), xs)
        loss, new_stats, grads = loss_and_grads(params, stats, images,
                                                labels, rng)
        return new_stats, grads, loss

    return group_grads


def make_accum_scan(loss_and_grads, unroll_fn=None):
    """The shared micro-batch accumulation scaffold — ONE implementation of
    the inner scan that every ``grad_accum`` variant uses (streaming /
    resident x replicated / sharded update), so the accumulation semantics
    (RNG fold structure, BN-stats chaining, gradient averaging) cannot
    drift between flag combinations.

    ``loss_and_grads(params, stats, images, labels, rng) -> (loss, stats,
    grads)`` is the per-micro forward/backward
    (:func:`make_loss_and_grads` or the zero path's local-grads core);
    ``unroll_fn(length) -> unroll`` is the scan-unroll policy for the
    inner scan (callers pass ``lambda n: scan_unroll(mesh, n)`` —
    :func:`~ddp_tpu.parallel.mesh.scan_unroll` — so the
    CPU-backend cap lives in one place).
    Returns ``accum(params, stats, xs, get_micro, rng) -> (new_stats,
    grads, loss)`` where ``xs`` is the scanned micro-batch stack (any
    pytree with leading axis A), ``get_micro(aug_rng, micro_xs) ->
    (images, labels)`` materialises one micro-batch, and ``rng`` is the
    per-optimizer-step key (already step- and axis-folded).  ``grads`` and
    ``loss`` are the micro-batch means; BN stats chain through the
    micro-batches in order (each forward normalises with its own
    micro-batch statistics, exactly like torch under accumulation).
    """

    def accum(params, stats0, xs, get_micro, rng):
        def one_micro(carry, micro):
            stats, gsum, lsum, k = carry
            mrng = jax.random.fold_in(rng, k)
            images, labels = get_micro(jax.random.fold_in(mrng, 1), micro)
            loss, stats, grads = loss_and_grads(params, stats, images,
                                                labels, mrng)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (stats, gsum, lsum + loss, k + 1), None

        a = jax.tree_util.tree_leaves(xs)[0].shape[0]
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (new_stats, gsum, lsum, _), _ = lax.scan(
            one_micro, (stats0, zeros, jnp.zeros(()),
                        jnp.zeros((), jnp.int32)), xs,
            unroll=unroll_fn(a) if unroll_fn is not None else 1)
        grads = jax.tree_util.tree_map(lambda g: g / a, gsum)
        return new_stats, grads, lsum / a

    return accum


def make_group_update(sgd_config: sgd_lib.SGDConfig,
                      lr_schedule: Callable[[jax.Array], jax.Array]):
    """The replicated SGD update stage: ``update(state, grads, new_stats)
    -> state`` at ``lr_schedule(state.step)`` — signature-compatible with
    the zero path's sharded update (train/zero.py:_make_zero_update), so
    :func:`make_group_step` composes with either."""

    def update(state: TrainState, grads, new_stats) -> TrainState:
        lr_t = lr_schedule(state.step)
        with jax.named_scope("update"):
            params, opt_state = sgd_lib.apply_updates(
                state.params, grads, state.opt_state, lr_t, sgd_config)
        return TrainState(params, new_stats, opt_state, state.step + 1)

    return update


def make_group_step(group_grads, update):
    """ONE shared per-optimizer-step body for every execution strategy
    (streaming / resident x plain / accumulation x replicated / sharded
    update): fold the per-step RNG (by step counter, then by shard index —
    the fold structure every trajectory-equality test depends on), compute
    the group's gradients, apply the update.

    ``group_grads(params, stats, xs, rng) -> (new_stats, grads, loss)``
    computes the optimizer step's gradient from ``xs`` (a batch dict, a
    micro-batch stack, or an index row/group — it closes over its own
    materialisation); ``update(state, grads, new_stats) -> state`` is
    :func:`make_group_update` or the zero path's sharded update.  Returns
    ``step(state, xs, rng) -> (state, loss)``.
    """

    def group_step(state: TrainState, xs, rng):
        rng = jax.random.fold_in(rng, state.step)
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        new_stats, grads, loss = group_grads(state.params, state.batch_stats,
                                             xs, rng)
        return update(state, grads, new_stats), loss

    return group_step


def make_step_wiring(model, sgd_config: sgd_lib.SGDConfig,
                     lr_schedule: Callable[[jax.Array], jax.Array],
                     mesh: Mesh, *, compute_dtype=None, sync_bn: bool = False,
                     plan=None, shard_update: bool = False):
    """``(loss core, update stage, state specs, state shardings, extra
    shard_map kwargs)`` for a train builder — the tp delta and the ZeRO
    delta in ONE place, shared by :func:`make_train_step` and the epoch
    builder (train/epoch.py).  The batch specs are UNCHANGED in every
    case (split on ``data``, replicated over ``model``).

    With a plan the state specs follow its per-leaf PartitionSpecs and
    ``check_vma=False`` because the TP program's collectives are all
    explicit with their own transposes.  A TRIVIAL plan (no column/row
    layer — an auto plan that searched its way to pure data parallelism,
    parallel/tp/autoplan.py) wires exactly the plain path: the program it
    implies IS the 1-D one, and models without a ``tp_axis`` forward must
    still run under it.

    ``shard_update`` (ZeRO-1, train/zero.py) swaps the update stage for
    the sharded one and the momentum's spec for the flat buffer's
    (``P(data)``; ``P(model, data)`` under a plan — here ANY plan, trivial
    or not, because the state's constructors
    (:func:`~ddp_tpu.train.zero.init_opt_shard`) lay the buffer out by
    ``plan is None`` alone).  ``check_vma=False`` because an
    ``all_gather`` result cannot be re-marked replicated, and with the
    check off no gradient psum is auto-inserted, which is what lets the
    update reduce-*scatter* instead (train/zero.py's implementation note).
    """
    from ..parallel.tp.plan import (is_trivial, recipe_override,
                                    state_shardings, state_specs)
    rep = replicated_sharding(mesh)
    # Two loss cores, chosen here and nowhere else.  make_loss_and_grads
    # differentiates the psum'd global-mean loss and lets shard_map's
    # transpose insert the gradient psum: the replicated 1-D program.
    # zero._make_local_grads differentiates the collective-free LOCAL
    # objective and leaves the reduction to its caller: ZeRO (a
    # reduce-scatter in the update) and TP (an explicit psum over
    # ``data``).  They lower to different programs, so merging them is a
    # change to measure, not a refactor (ROADMAP C3).
    if shard_update:
        from .zero import _make_local_grads, _make_zero_update
        tp = plan is not None
        # Without a plan the axis-extent product, not mesh.devices.size:
        # the auto-plan search prices this wiring on a deviceless
        # AbstractMesh (parallel/mesh.py:abstract_mesh).
        R = data_axis_size(mesh) if tp else mesh_size(mesh)
        core = _make_local_grads(
            model, R, compute_dtype, sync_bn,
            tp_axis=MODEL_AXIS if tp else None,
            tp_recipe=recipe_override(plan) if tp else None)
        update = _make_zero_update(sgd_config, lr_schedule, R, tp=tp)
        if tp:
            specs = state_specs(plan, zero=True)
            shardings = state_shardings(plan, mesh, zero=True)
        else:
            flat = P(DATA_AXIS)
            specs = TrainState(P(), P(), sgd_lib.SGDState(flat), P())
            shardings = TrainState(
                rep, rep, sgd_lib.SGDState(NamedSharding(mesh, flat)), rep)
        return core, update, specs, shardings, {"check_vma": False}
    update = make_group_update(sgd_config, lr_schedule)
    if plan is None or is_trivial(plan):
        core = make_loss_and_grads(model, compute_dtype=compute_dtype,
                                   sync_bn=sync_bn)
        return core, update, P(), rep, {}
    core = make_loss_and_grads_tp(model, data_axis_size(mesh),
                                  compute_dtype=compute_dtype,
                                  sync_bn=sync_bn,
                                  tp_recipe=recipe_override(plan))
    return (core, update, state_specs(plan), state_shardings(plan, mesh),
            {"check_vma": False})


def make_train_step(model, sgd_config: sgd_lib.SGDConfig,
                    lr_schedule: Callable[[jax.Array], jax.Array],
                    mesh: Mesh, *, compute_dtype=None,
                    device_augment: bool = False, sync_bn: bool = False,
                    plan=None, accum: bool = False,
                    shard_update: bool = False):
    """Build the jitted SPMD train step for ``model`` over ``mesh``.

    Returns ``step_fn(state, batch, rng) -> (state, loss)`` where ``batch``
    is ``{"image": u8|f32[B,H,W,C], "label": i32[B]}`` with B divisible by
    the mesh size, globally sharded on ``data``.  ``rng`` feeds dropout
    (DeepNN, singlegpu.py:36) and, with ``device_augment=True``, the
    on-device RandomCrop+HFlip (data/device_augment.py) — in that mode the
    loader must be built with ``augment=False``.  ``sync_bn=True`` syncs
    BN statistics across shards (multigpu.py:127's commented-out option).

    ``plan`` (a :class:`~ddp_tpu.parallel.tp.plan.TPPlan`, 2-D mesh) runs
    the tensor-parallel variant: params/momentum sharded per the plan's
    specs over ``model``, batch still split over ``data`` only, gradients
    reduced over ``data`` only (:func:`make_loss_and_grads_tp`); the state
    must be ``device_put`` onto ``state_shardings(plan, mesh)``.

    ``accum=True`` is gradient accumulation (``--grad_accum``: torch's
    no_sync()+step-every-A, TPU-shaped): ``batch`` arrays are ``[A, B,
    ...]`` — A micro-batches of global batch B, sharded on the batch
    (second) axis (:func:`shard_batch_stacked`).  A ``lax.scan``
    (:func:`make_accum_scan`) runs the same forward/backward per
    micro-batch, averaging gradients, and ONE update at lr(step) follows;
    ``loss`` is the mean of the micro-batch global-mean losses.  Distinct
    A values (a ragged tail group) compile once each.

    ``shard_update=True`` is the ZeRO-1 weight update (``--shard_update``,
    train/zero.py): one reduce-scatter + SGD on the local 1/R slice + one
    all-gather.  ``state.opt_state.momentum_buf`` must come from
    :func:`~ddp_tpu.train.zero.init_opt_shard` /
    :func:`~ddp_tpu.train.zero.pytree_to_opt_shard`; under a ``plan`` it
    composes (params along ``model``, the update along ``data``) — pass
    the plan to the momentum constructors too.

    The three choices are independent and share every piece
    (:func:`make_step_wiring`, :func:`make_group_step`), so the semantics
    cannot drift between flag combinations.
    """
    core, update, st_specs, st_sh, extra = make_step_wiring(
        model, sgd_config, lr_schedule, mesh, compute_dtype=compute_dtype,
        sync_bn=sync_bn, plan=plan, shard_update=shard_update)
    get_micro = _micro_from_batch(device_augment)
    if accum:
        scan = make_accum_scan(core,
                               unroll_fn=lambda n: scan_unroll(mesh, n))
        _shard_body = make_group_step(
            lambda p, s, xs, rng: scan(p, s, xs, get_micro, rng), update)
    else:
        _shard_body = make_group_step(make_single_micro(core, get_micro),
                                      update)
    batch_spec = P(None, DATA_AXIS) if accum else P(DATA_AXIS)

    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(st_specs, {"image": batch_spec, "label": batch_spec}, P()),
        out_specs=(st_specs, P()),
        **extra,
    )
    return jax.jit(mapped, donate_argnums=(0,),
                   out_shardings=(st_sh, replicated_sharding(mesh)))


def make_eval_apply(model, compute_dtype=None, tp_axis=None,
                    tp_recipe=None):
    """The per-shard eval-mode forward — ``fn(params, batch_stats, images)
    -> logits`` with BN in running-stats mode (``model.eval()`` semantics,
    singlegpu.py:189) and the on-device uint8 ToTensor scaling.

    This is the ONE eval forward in the codebase: :func:`make_eval_step`
    (training-loop evaluation) and :func:`make_eval_forward` (the serving
    engine's logits program, ddp_tpu/serve/) both trace exactly this
    function, so served predictions cannot drift from ``evaluate()``.
    ``tp_axis`` threads the tensor-parallel forward through (model-sharded
    params, row-parallel psums over that axis — parallel/tp/);
    ``tp_recipe`` overrides the module's TP_RECIPE for auto plans.
    """

    def apply_fn(params, batch_stats, images):
        logits, _ = model.apply(params, batch_stats,
                                _as_input(images, compute_dtype),
                                train=False, compute_dtype=compute_dtype,
                                **({} if tp_axis is None
                                   else {"tp_axis": tp_axis}),
                                **({} if tp_recipe is None
                                   else {"tp_recipe": tp_recipe}))
        return logits

    return apply_fn


def _eval_wiring(plan):
    """``(param specs, stats specs, tp_axis, tp_recipe, shard_map extras)``
    for the two eval-side builders — the same plan/trivial-plan decision
    :func:`make_step_wiring` makes for the train side."""
    from ..parallel.tp.plan import is_trivial, recipe_override
    if plan is None or is_trivial(plan):
        return P(), P(), None, None, {}
    return (plan.param_specs, plan.stats_specs, MODEL_AXIS,
            recipe_override(plan), {"check_vma": False})


def make_eval_forward(model, mesh: Mesh, compute_dtype=None,
                      on_trace: Callable[[], None] = None, plan=None):
    """Jitted sharded eval forward returning the LOGITS themselves:
    ``forward(params, batch_stats, images[B,H,W,C]) -> logits[B,C]`` with
    the batch sharded on ``data`` and per-row results gathered — the
    program the serving engine (ddp_tpu/serve/engine.py) compiles per
    padded batch bucket, and the test surface for logit-level parity with
    :func:`make_eval_step` (both trace :func:`make_eval_apply`).

    ``on_trace`` (optional) is called at TRACE time — i.e. exactly once
    per compiled executable, never on a cache hit — which is how the
    serve engine *proves* its compiled-program count stays bounded at the
    bucket-set size (tests/test_serve.py).

    Numerics note: per-row logits are independent of the other rows in
    eval mode (BN uses running stats), and on this CPU backend they are
    bit-identical across mesh sizes at matched per-shard row counts; XLA
    may still pick a differently-rounded kernel strategy for a much
    larger per-shard batch shape, so bit-for-bit comparisons must compare
    matching bucket shapes (the contract tests/test_serve.py pins).

    ``plan`` (tp) shards the params over ``model``; the logits come out
    sharded on ``data`` exactly as in the 1-D case (each model shard holds
    the full post-psum logits for its data rows).
    """
    p_specs, s_specs, tp_axis, tp_recipe, extra = _eval_wiring(plan)
    apply_fn = make_eval_apply(model, compute_dtype, tp_axis=tp_axis,
                               tp_recipe=tp_recipe)

    def _shard_body(params, batch_stats, images):
        if on_trace is not None:
            on_trace()  # Python side effect: runs only while tracing
        return apply_fn(params, batch_stats, images)

    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(p_specs, s_specs, P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        **extra,
    )
    return jax.jit(mapped,
                   out_shardings=NamedSharding(mesh, P(DATA_AXIS)))


def make_eval_step(model, mesh: Mesh, compute_dtype=None, plan=None):
    """Sharded evaluation step: global (correct, total) via ``psum``.

    The reference redundantly evaluates the full test set on every rank
    (multigpu.py:247, SURVEY.md §3.5); here each shard scores its slice and
    the counters are summed over ICI — same result, 1/N the work.  ``mask``
    zeroes the padding rows that keep shapes static (test set size need not
    divide the mesh).  The forward is :func:`make_eval_apply` — the same
    function the serving engine's logits program traces.  ``plan`` (tp)
    shards the params over ``model``; the counters still reduce over
    ``data`` only (every model shard computes the same post-psum logits).
    """
    p_specs, s_specs, tp_axis, tp_recipe, extra = _eval_wiring(plan)
    apply_fn = make_eval_apply(model, compute_dtype, tp_axis=tp_axis,
                               tp_recipe=tp_recipe)

    def _shard_body(params, batch_stats, batch):
        logits = apply_fn(params, batch_stats, batch["image"])
        pred = jnp.argmax(logits, axis=-1)
        maskf = batch["mask"].astype(jnp.float32)
        correct = ((pred == batch["label"]).astype(jnp.float32) * maskf).sum()
        total = maskf.sum()
        return (lax.psum(correct, DATA_AXIS), lax.psum(total, DATA_AXIS))

    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(p_specs, s_specs,
                  {"image": P(DATA_AXIS), "label": P(DATA_AXIS),
                   "mask": P(DATA_AXIS)}),
        out_specs=(P(), P()),
        **extra,
    )
    rep = replicated_sharding(mesh)
    return jax.jit(mapped, out_shardings=(rep, rep))


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    """Host numpy batch -> global device array sharded on ``data``.

    Single-host: a plain ``device_put`` split.  Multi-host: each process
    holds only its local slice (the per-host shard the sampler produced) and
    the global array is assembled from process-local data — the analogue of
    each DDP rank feeding its own DistributedSampler shard.
    """
    sharding = batch_sharding(mesh)
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    return {k: assemble_from_local(sharding, v, 0)
            for k, v in batch.items()}


def shard_batch_stacked(batch: dict, mesh: Mesh) -> dict:
    """Like :func:`shard_batch` for ``[A, B, ...]`` micro-batch stacks
    (``make_train_step(accum=True)``): sharded on the batch (second) axis."""
    sharding = NamedSharding(mesh, P(None, DATA_AXIS))
    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    return {k: assemble_from_local(sharding, v, 1)
            for k, v in batch.items()}
