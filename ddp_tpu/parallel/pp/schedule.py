"""GPipe / 1F1B microbatch schedules as per-stage jitted programs.

The 3-D mesh ``(data, model, stage)`` is a stack of s identical 2-D
(data × model) submeshes (:func:`stage_submesh`).  Each stage owns the
param/momentum subtrees of its PP_BLOCKS range and runs its OWN jitted
shard_map programs over its submesh — forward, backward (recompute-style:
the backward re-runs the stage forward under ``jax.vjp``, so no residual
crosses a stage boundary), a fused forward+backward on the last stage
(where the loss lives), and one SGD update per stage.  Activations and
cotangents cross stages as explicit ``jax.device_put`` transfers onto the
neighbour submesh — MPMD handoff, not a collective, so the staged
programs' jaxprs stay 2-D and the static auditor's collective invariants
apply per stage (analysis/jaxpr_audit.py).

Numerics are the tensor-parallel replicated-update core's, cut at block
boundaries: every stage differentiates its slice of the collective-free
LOCAL objective ``ce_sum/(count*d)`` (train/zero.py:_make_local_grads),
param grads are psum'd over ``data`` inside the owning stage's program,
and per-stage ``gsum``/``lsum`` accumulate in micro-batch order 0..A-1
from zeros — exactly :func:`~ddp_tpu.train.step.make_accum_scan`'s
accumulation, which is why (d,m,s) is bit-compatible with the (d,m)
accum step (tests/test_pp.py pins it) and why GPipe and 1F1B agree
bitwise (same per-stage accumulation order; 1F1B only changes WHEN work
is enqueued, bounding in-flight activations at min(s,A) instead of A).

RNG discipline is the shared fold structure: per-step key folded by step
then by ``axis_index(data)`` inside every stage's shard_map, per-micro
``mrng = fold_in(rng, k)``, augmentation stream ``fold_in(mrng, 1)`` —
so dropout/augmentation draw the same bits as the unstaged program no
matter which stage they land in.
"""
from __future__ import annotations

import importlib
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...optim import sgd as sgd_lib
from ...ops.losses import cross_entropy_sum_count
from ..mesh import (DATA_AXIS, MODEL_AXIS, STAGE_AXIS, data_axis_size,
                    stage_axis_size)
from .partition import (StagePlan, _MODULE_FOR, merge_subtrees,
                        predicted_bubble, stage_subtree)


def stage_submesh(mesh: Mesh, k: int) -> Mesh:
    """Stage ``k``'s 2-D (data × model) submesh — the device plane at
    stage coordinate k.  Rows keep their data coordinates, so
    ``axis_index(data)`` (and therefore every RNG fold) agrees with the
    full mesh."""
    if STAGE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}; a pipeline needs the "
            f"'{STAGE_AXIS}' axis (make_mesh(shape=(d, m, s)))")
    idx = mesh.axis_names.index(STAGE_AXIS)
    s = stage_axis_size(mesh)
    if not 0 <= k < s:
        raise ValueError(f"stage {k} out of range for stage axis size {s}")
    devs = np.take(mesh.devices, k, axis=idx)
    return Mesh(devs, tuple(n for n in mesh.axis_names if n != STAGE_AXIS))


def schedule_ops(kind: str, num_micro: int, num_stages: int):
    """The enqueue order: a list of ``("F", j, k)`` / ``("B", j, k)`` /
    ``("FB", k)`` ops (stage j, micro k; the last stage always runs the
    fused FB).  Both schedules respect the same dependencies — F(j,k)
    after F(j-1,k), B(j,k) after B(j+1,k)/FB(k), per-stage micros in
    order — so they are numerically interchangeable; they differ in how
    long forward activations stay alive (GPipe: all A per stage; 1F1B:
    min(s, A))."""
    a, s = int(num_micro), int(num_stages)
    if kind not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {kind!r}; "
                         "expected 'gpipe' or '1f1b'")
    if s < 2:
        raise ValueError(f"a pipeline schedule needs s>=2 stages, got {s}")
    if kind == "gpipe":
        ops = [("F", j, k) for k in range(a) for j in range(s - 1)]
        for k in range(a):
            ops.append(("FB", k))
            ops.extend(("B", j, k) for j in range(s - 2, -1, -1))
        return ops
    # 1F1B: per-stage local sequences (warmup forwards, then strict
    # backward/forward alternation), merged into one dependency-
    # respecting enqueue order.
    local = []
    for j in range(s - 1):
        warm = min(a, s - 1 - j)
        seq = [("F", j, f) for f in range(warm)]
        fw, bw = warm, 0
        while bw < a:
            seq.append(("B", j, bw))
            bw += 1
            if fw < a:
                seq.append(("F", j, fw))
                fw += 1
        local.append(seq)
    local.append([("FB", k) for k in range(a)])

    done = set()

    def ready(op):
        if op[0] == "F":
            _, j, k = op
            return j == 0 or ("F", j - 1, k) in done
        if op[0] == "FB":
            return ("F", s - 2, op[1]) in done
        _, j, k = op
        return (("FB", k) if j == s - 2 else ("B", j + 1, k)) in done

    ptr = [0] * s
    ops = []
    total = sum(len(q) for q in local)
    while len(ops) < total:
        progressed = False
        for j in range(s):
            if ptr[j] < len(local[j]) and ready(local[j][ptr[j]]):
                op = local[j][ptr[j]]
                ops.append(op)
                done.add(op)
                ptr[j] += 1
                progressed = True
        if not progressed:  # pragma: no cover - schedule bug backstop
            raise RuntimeError("1F1B schedule deadlocked; per-stage "
                               f"pointers {ptr}")
    return ops


def _apply_blocks_for(model_name: str):
    mod = importlib.import_module(
        f"ddp_tpu.models.{_MODULE_FOR.get(model_name, model_name)}")
    fn = getattr(mod, "apply_blocks", None)
    if fn is None:
        raise ValueError(
            f"model {model_name!r} has no apply_blocks; pipeline stages "
            "need the block-range forward (see models/deepnn.py)")
    return fn


def _specs_like(tree, spec_tree):
    if spec_tree is not None:
        return spec_tree
    return jax.tree_util.tree_map(lambda _: P(), tree)


def place_state(state, mesh: Mesh, pp_plan: StagePlan, tp_plan=None):
    """Place a (host or replicated) TrainState onto its pipeline layout:
    each stage's param/momentum subtree lands on that stage's submesh
    with the tp plan's per-leaf specs (P() without a non-trivial plan).
    The step counter and batch_stats stay as they are — the canonical
    checkpoint format is unchanged, which is what makes any (d,m,s)
    snapshot restore onto any (d',m',s')."""
    from ..tp.plan import is_trivial
    use_tp = tp_plan is not None and not is_trivial(tp_plan)
    params_parts, mom_parts = [], []
    for k in range(pp_plan.num_stages):
        sub = stage_submesh(mesh, k)
        spec_sub = (stage_subtree(pp_plan, k, tp_plan.param_specs)
                    if use_tp else None)
        p_sub = stage_subtree(pp_plan, k, state.params)
        m_sub = stage_subtree(pp_plan, k, state.opt_state.momentum_buf)
        shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(sub, s), _specs_like(p_sub, spec_sub))
        params_parts.append(jax.device_put(p_sub, shard))
        mom_parts.append(jax.device_put(m_sub, shard))
    from ...train.step import TrainState
    return TrainState(merge_subtrees(params_parts), state.batch_stats,
                      sgd_lib.SGDState(merge_subtrees(mom_parts)),
                      state.step)


def pp_shard_fn(pp_plan: StagePlan):
    """``shard_fn(batch, mesh)`` for the prefetch stream: the stacked
    ``[A, B, ...]`` images land on stage 0's submesh (split on ``data``),
    the labels on the last stage's (where the loss lives) — the pipeline
    reuses the grad-accum group stream as its microbatch injector."""

    def shard(batch: dict, mesh: Mesh) -> dict:
        sub0 = stage_submesh(mesh, 0)
        sublast = stage_submesh(mesh, pp_plan.num_stages - 1)
        return {
            "image": jax.device_put(
                batch["image"], NamedSharding(sub0, P(None, DATA_AXIS))),
            "label": jax.device_put(
                batch["label"], NamedSharding(sublast,
                                              P(None, DATA_AXIS))),
        }

    return shard


def eval_params_for(state, pp_plan: StagePlan, tp_plan, eval_mesh: Mesh):
    """Gather the stage-scattered params/stats back onto ONE 2-D mesh for
    evaluation: host round-trip (stages live on disjoint device sets), then
    the tp placement evaluate() expects on ``eval_mesh``."""
    from ..tp.plan import is_trivial
    params, stats = jax.device_get((state.params, state.batch_stats))
    if tp_plan is not None and not is_trivial(tp_plan):
        shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(eval_mesh, s), tp_plan.param_specs)
        return jax.device_put(params, shard), stats
    rep = NamedSharding(eval_mesh, P())
    return (jax.tree_util.tree_map(lambda x: jax.device_put(x, rep),
                                   params), stats)


class _PPStep:
    """The pipeline train step: ``step_fn(state, batch, rng) -> (state,
    loss)``, signature-compatible with
    :func:`~ddp_tpu.train.step.make_train_step`'s ``accum=True`` step —
    ``batch`` is the stacked ``{"image": [A,B,...], "label": [A,B]}``
    group placed by :func:`pp_shard_fn`.  Per-stage programs compile lazily on first use
    and re-trace per distinct A, exactly like the accum step."""

    def __init__(self, model_name: str, sgd_config, lr_schedule, mesh,
                 pp_plan: StagePlan, *, compute_dtype=None,
                 device_augment: bool = False, tp_plan=None,
                 schedule: str = "1f1b", tracer=None):
        from ..tp.plan import is_trivial, recipe_override
        if pp_plan.num_stages < 2:
            raise ValueError("make_pp_step needs s>=2 pipeline stages; "
                             "run s=1 through the standard step builders")
        if stage_axis_size(mesh) != pp_plan.num_stages:
            raise ValueError(
                f"stage plan has {pp_plan.num_stages} stages but the mesh "
                f"stage axis is {stage_axis_size(mesh)}")
        self.mesh = mesh
        self.plan = pp_plan
        self.schedule = schedule
        self.tracer = tracer
        self._sgd = sgd_config
        self._lr = lr_schedule
        self._cd = compute_dtype
        self._augment = device_augment
        self._apply_blocks = _apply_blocks_for(model_name)
        use_tp = tp_plan is not None and not is_trivial(tp_plan)
        self._tp_axis = MODEL_AXIS if use_tp else None
        self._tp_recipe = recipe_override(tp_plan) if use_tp else None
        self._tp_plan = tp_plan if use_tp else None
        self._R = data_axis_size(mesh)
        self.s = pp_plan.num_stages
        self.subs = [stage_submesh(mesh, k) for k in range(self.s)]
        self._progs: Optional[dict] = None   # built on first call
        self._updates: Dict[int, list] = {}  # per-A update programs
        self._ops: Dict[int, list] = {}      # per-A schedule op lists
        self._timed_for: set = set()         # A values already timed
        self.bubble: Optional[dict] = None   # last timed-step stats
        self.peak_inflight = 0

    # -- per-stage forward bodies ---------------------------------------

    def _stage_forward(self, k_stage: int):
        lo, hi = self.plan.stages[k_stage]
        apply_blocks = self._apply_blocks
        cd, tp_axis, tp_recipe = self._cd, self._tp_axis, self._tp_recipe

        def fwd(params, x, mrng):
            out, _ = apply_blocks(
                params, {}, x, blocks=(lo, hi), train=True, rng=mrng,
                compute_dtype=cd,
                **({} if tp_axis is None else {"tp_axis": tp_axis}),
                **({} if tp_recipe is None else {"tp_recipe": tp_recipe}))
            return out

        return fwd

    def _fold(self, rng, step, k):
        rng = jax.random.fold_in(rng, step)
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        return jax.random.fold_in(rng, k)

    def _micro_images(self, images, mrng, k):
        from ...train.step import _as_input
        x = lax.dynamic_index_in_dim(images, k, keepdims=False)
        if self._augment:
            from ...data.device_augment import random_crop_flip
            x = random_crop_flip(jax.random.fold_in(mrng, 1), x)
        return _as_input(x, self._cd)

    # -- program construction -------------------------------------------

    def _build(self, state):
        plan, subs, s = self.plan, self.subs, self.s
        specs, shards = [], []
        for k in range(s):
            p_sub = stage_subtree(plan, k, state.params)
            spec_sub = _specs_like(
                p_sub, (stage_subtree(plan, k, self._tp_plan.param_specs)
                        if self._tp_plan is not None else None))
            specs.append(spec_sub)
            shards.append(jax.tree_util.tree_map(
                lambda sp, _k=k: NamedSharding(subs[_k], sp), spec_sub))
        extra = {"check_vma": False}
        R = self._R
        progs: dict = {"specs": specs, "shards": shards,
                       "zeros": [], "fwd": {}, "bwd": {}}

        for k in range(s):
            progs["zeros"].append(jax.jit(
                lambda tree_shape=jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    stage_subtree(plan, k, state.params)):
                jax.tree_util.tree_map(
                    lambda sd: jnp.zeros(sd.shape, sd.dtype), tree_shape),
                out_shardings=shards[k]))

        def act_spec():
            return P(DATA_AXIS)

        # forward: stage 0 (slices + prepares the micro) and middles
        for j in range(s - 1):
            fwd_blocks = self._stage_forward(j)
            first = (j == 0)

            def body(params, x, rng, step, k, _fwd=fwd_blocks,
                     _first=first):
                mrng = self._fold(rng, step, k)
                xin = self._micro_images(x, mrng, k) if _first else x
                return _fwd(params, xin, mrng)

            in_x = P(None, DATA_AXIS) if first else act_spec()
            mapped = jax.shard_map(
                body, mesh=subs[j],
                in_specs=(specs[j], in_x, P(), P(), P()),
                out_specs=act_spec(), **extra)
            progs["fwd"][j] = jax.jit(
                mapped, out_shardings=NamedSharding(subs[j], act_spec()))

        # fused forward+backward on the last stage (loss + gsum/lsum)
        fwd_last = self._stage_forward(s - 1)

        def fb_body(params, gsum, lsum, x, labels, rng, step, k):
            mrng = self._fold(rng, step, k)
            y = lax.dynamic_index_in_dim(labels, k, keepdims=False)

            def local_obj(p, xin):
                logits = fwd_last(p, xin, mrng)
                ce_sum, count = cross_entropy_sum_count(logits, y)
                return ce_sum / (count * R), (ce_sum, count)

            (gp, gx), (ce_sum, count) = jax.grad(
                local_obj, argnums=(0, 1), has_aux=True)(params, x)
            loss = (lax.psum(ce_sum, DATA_AXIS)
                    / lax.psum(count, DATA_AXIS))
            gsum = jax.tree_util.tree_map(
                lambda a, g: a + lax.psum(g, DATA_AXIS), gsum, gp)
            return gsum, lsum + loss, gx

        mapped = jax.shard_map(
            fb_body, mesh=subs[s - 1],
            in_specs=(specs[s - 1], specs[s - 1], P(), act_spec(),
                      P(None, DATA_AXIS), P(), P(), P()),
            out_specs=(specs[s - 1], P(), act_spec()), **extra)
        progs["fb"] = jax.jit(
            mapped, donate_argnums=(1, 2),
            out_shardings=(shards[s - 1],
                           NamedSharding(subs[s - 1], P()),
                           NamedSharding(subs[s - 1], act_spec())))

        # backward: middles take the saved input activation and the
        # cotangent from the next stage; stage 0 re-slices its micro and
        # differentiates w.r.t. params ONLY (the input cotangent is dead,
        # preserving the stem elision the auditor counts on).
        for j in range(s - 2, -1, -1):
            fwd_blocks = self._stage_forward(j)
            first = (j == 0)

            def bwd_body(params, gsum, x, g_out, rng, step, k,
                         _fwd=fwd_blocks, _first=first):
                mrng = self._fold(rng, step, k)
                # analysis: divergence-ok(_first is a trace-time stage constant, identical on every host)
                if _first:
                    xin = self._micro_images(x, mrng, k)
                    _, vjp = jax.vjp(lambda p: _fwd(p, xin, mrng), params)
                    (gp,) = vjp(g_out)
                    gsum = jax.tree_util.tree_map(
                        lambda a, g: a + lax.psum(g, DATA_AXIS), gsum, gp)
                    return gsum
                _, vjp = jax.vjp(lambda p, xi: _fwd(p, xi, mrng),
                                 params, x)
                gp, gx = vjp(g_out)
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + lax.psum(g, DATA_AXIS), gsum, gp)
                return gsum, gx

            in_x = P(None, DATA_AXIS) if first else act_spec()
            out_specs = (specs[j] if first else (specs[j], act_spec()))
            out_sh = (shards[j] if first
                      else (shards[j], NamedSharding(subs[j], act_spec())))
            mapped = jax.shard_map(
                bwd_body, mesh=subs[j],
                in_specs=(specs[j], specs[j], in_x, act_spec(),
                          P(), P(), P()),
                out_specs=out_specs, **extra)
            progs["bwd"][j] = jax.jit(mapped, donate_argnums=(1,),
                                      out_shardings=out_sh)
        self._progs = progs

    def _update_programs(self, a: int):
        progs = self._progs
        out = []
        for k in range(self.s):
            def upd_body(params, mom, gsum, step, _a=float(a)):
                grads = jax.tree_util.tree_map(lambda g: g / _a, gsum)
                lr_t = self._lr(step)
                return sgd_lib.apply_updates(params, grads,
                                             sgd_lib.SGDState(mom),
                                             lr_t, self._sgd)

            mapped = jax.shard_map(
                upd_body, mesh=self.subs[k],
                in_specs=(progs["specs"][k], progs["specs"][k],
                          progs["specs"][k], P()),
                out_specs=(progs["specs"][k],
                           sgd_lib.SGDState(progs["specs"][k])),
                check_vma=False)
            # donate params+momentum only: gsum has no same-shaped OUTPUT
            # to alias into (grads/a is an intermediate), so donating it
            # would just trip the unusable-donation warning.
            out.append(jax.jit(
                mapped, donate_argnums=(0, 1),
                out_shardings=(progs["shards"][k],
                               sgd_lib.SGDState(progs["shards"][k]))))
        return out

    # -- the step --------------------------------------------------------

    def __call__(self, state, batch, rng):
        from ...train.step import TrainState
        if self._progs is None:
            self._build(state)
        progs = self._progs
        s, subs, plan = self.s, self.subs, self.plan
        a = int(jax.tree_util.tree_leaves(batch)[0].shape[0])
        if a not in self._ops:
            self._ops[a] = schedule_ops(self.schedule, a, s)
            self._updates[a] = self._update_programs(a)
        ops = self._ops[a]
        timed = a not in self._timed_for and self.tracer is not None
        host_step = int(state.step)
        step32 = np.int32(host_step)
        rngs = [jax.device_put(rng, NamedSharding(sub, P()))
                for sub in subs]

        p_sub = [stage_subtree(plan, k, state.params) for k in range(s)]
        m_sub = [stage_subtree(plan, k, state.opt_state.momentum_buf)
                 for k in range(s)]
        gsum = [progs["zeros"][k]() for k in range(s)]
        lsum = jax.device_put(jnp.zeros((), jnp.float32),
                              NamedSharding(subs[-1], P()))
        images, labels = batch["image"], batch["label"]

        act_in: dict = {}   # (stage, micro) -> saved input activation
        g_out: dict = {}    # (stage, micro) -> incoming cotangent
        durations = []      # (op, seconds) when timed
        inflight_peak = 0

        def run(op):
            nonlocal lsum, inflight_peak
            if op[0] == "F":
                _, j, k = op
                x = images if j == 0 else act_in[(j, k)]
                act = progs["fwd"][j](p_sub[j], x, rngs[j], step32,
                                      np.int32(k))
                act_in[(j + 1, k)] = jax.device_put(
                    act, NamedSharding(subs[j + 1], P(DATA_AXIS)))
                return (act_in[(j + 1, k)],)
            if op[0] == "FB":
                k = op[1]
                gsum[s - 1], lsum, gx = progs["fb"](
                    p_sub[s - 1], gsum[s - 1], lsum,
                    act_in.pop((s - 1, k)), labels, rngs[s - 1], step32,
                    np.int32(k))
                g_out[(s - 2, k)] = jax.device_put(
                    gx, NamedSharding(subs[s - 2], P(DATA_AXIS)))
                return (lsum, g_out[(s - 2, k)])
            _, j, k = op
            if j == 0:
                gsum[0] = progs["bwd"][0](
                    p_sub[0], gsum[0], images, g_out.pop((0, k)),
                    rngs[0], step32, np.int32(k))
                return (jax.tree_util.tree_leaves(gsum[0])[0],)
            gsum[j], gx = progs["bwd"][j](
                p_sub[j], gsum[j], act_in.pop((j, k)),
                g_out.pop((j, k)), rngs[j], step32, np.int32(k))
            g_out[(j - 1, k)] = jax.device_put(
                gx, NamedSharding(subs[j - 1], P(DATA_AXIS)))
            return (g_out[(j - 1, k)],)

        for op in ops:
            if timed:
                t0 = time.perf_counter()
                outs = run(op)
                jax.block_until_ready(outs)
                durations.append((op, time.perf_counter() - t0))
            else:
                run(op)
            inflight_peak = max(inflight_peak, len(act_in))

        upd = self._updates[a]
        new_p, new_m = [], []
        for k in range(s):
            pk, mk = upd[k](p_sub[k], m_sub[k], gsum[k], step32)
            new_p.append(pk)
            new_m.append(mk.momentum_buf)
        loss_host = np.float32(jax.device_get(lsum)) / np.float32(a)
        new_state = TrainState(
            merge_subtrees(new_p), state.batch_stats,
            sgd_lib.SGDState(merge_subtrees(new_m)),
            state.step + 1)
        self.peak_inflight = max(self.peak_inflight, inflight_peak)
        if timed:
            self._timed_for.add(a)
            self._record_bubble(a, durations, inflight_peak, host_step)
        return new_state, jnp.float32(loss_host)

    # -- bubble accounting ----------------------------------------------

    def _record_bubble(self, a, durations, inflight_peak, host_step):
        """Reconstruct the schedule makespan from the measured per-program
        durations (dependency-aware critical path over the op DAG) and
        derive the MEASURED bubble fraction — what fraction of the s-stage
        pipeline's makespan the stages sat idle — next to the static
        (s-1)/(A+s-1) prediction.  Emitted as the ``pp_bubble`` span so
        the flight recorder / metrics pipeline can plot it."""
        s = self.s
        dur = {op: d for op, d in durations}

        def stage_of(op):
            return s - 1 if op[0] == "FB" else op[1]

        done: Dict[tuple, float] = {}
        free = [0.0] * s
        busy = [0.0] * s
        for op, d in durations:
            deps = []
            if op[0] == "F" and op[1] > 0:
                deps.append(("F", op[1] - 1, op[2]))
            elif op[0] == "FB":
                deps.append(("F", s - 2, op[1]))
            elif op[0] == "B":
                _, j, k = op
                deps.append(("FB", k) if j == s - 2 else ("B", j + 1, k))
            j = stage_of(op)
            start = max([free[j]] + [done[dep] for dep in deps
                                     if dep in done])
            done[op] = start + d
            free[j] = done[op]
            busy[j] += d
        makespan = max(free) if free else 0.0
        total_busy = sum(busy)
        measured = (1.0 - total_busy / (s * makespan)) if makespan else 0.0
        self.bubble = {
            "schedule": self.schedule,
            "num_stages": s,
            "num_micro": a,
            "bubble_measured": float(measured),
            "bubble_predicted": float(predicted_bubble(s, a)),
            "makespan_s": float(makespan),
            "peak_inflight_acts": int(inflight_peak),
        }
        if self.tracer is not None:
            bubble_s = (s * makespan - total_busy) / s
            self.tracer.add_span("pp_bubble", time.monotonic() - bubble_s,
                                 bubble_s, step=host_step)


def make_pp_step(model_name: str, sgd_config, lr_schedule, mesh: Mesh,
                 pp_plan: StagePlan, *, compute_dtype=None,
                 device_augment: bool = False, tp_plan=None,
                 schedule: str = "1f1b", tracer=None) -> Callable:
    """Build the pipeline train step over ``mesh``'s (d, m, s) shape —
    see :class:`_PPStep`.  Returns ``step_fn(state, batch, rng) ->
    (state, loss)``; ``state`` must be laid out by :func:`place_state`,
    ``batch`` by :func:`pp_shard_fn`'s stream."""
    return _PPStep(model_name, sgd_config, lr_schedule, mesh, pp_plan,
                   compute_dtype=compute_dtype,
                   device_augment=device_augment, tp_plan=tp_plan,
                   schedule=schedule, tracer=tracer)
