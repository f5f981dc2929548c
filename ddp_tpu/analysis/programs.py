"""The audited-program registry: every SPMD program family, buildable on
a virtual mesh, with its declarative invariants.

One entry per (program family x mesh regime): the 1-D data-parallel
train/accum/ZeRO steps, their (d, m) tensor-parallel variants, the
evaluation step, and the serve forward — the complete set of programs a
chip run executes (train/step.py, train/zero.py, serve/engine.py).  Each
entry builds the REAL head builder's jitted function plus abstract
(``ShapeDtypeStruct``) example arguments, so auditing traces the exact
program the trainer runs, never a reimplementation — and tracing abstract
args costs no device memory and no XLA compile.

The registry is tiny on purpose: entries are (name, kind, zero, tp,
build), invariants derive from (kind, zero, plan) in
``jaxpr_audit.audit_collectives``.  ``kind``:

- ``update``  — optimizer steps: data-axis grad reduction required, full
  state donation required, ZeRO pair iff ``zero``.
- ``forward`` — the serve logits program: collective-free off (and, here,
  on) the data axis.
- ``eval``    — the counter-psum evaluation step.
- ``audit``   — the drift-audit fingerprint program (resilience/drift.py):
  psum-over-data only, params NOT donated (they are the live train state),
  payload budgeted tiny (2 x n_leaves x 4 bytes — the SDC audit must stay
  cheap enough to run every K steps, BENCH_r10.json).
- ``pp_*``    — the staged pipeline programs (parallel/pp/schedule.py),
  registered only under a 3-entry ``--mesh-shape`` with s>1: one
  forward/backward per non-last stage, the fused last-stage FB, one
  update per stage — each audited against its EXACT per-stage
  psum-over-model budget and required to stay 2-D (activation handoffs
  are device transfers, never collectives).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

DEFAULT_MODEL = "deepnn"
DEFAULT_MESH_2D = (2, 4)
_BATCH = 32      # global rows per step for the audit trace
_ACCUM = 2       # micro-batches for the accum variants
_LM_T = 32       # sequence length the LM train-step audit traces
_LM_SLOTS = 8    # KV-cache slots the decode audit traces
_LM_BUCKET = 16  # padded prompt bucket the prefill audit traces


class BuiltProgram(NamedTuple):
    name: str
    kind: str                 # "update" | "forward" | "eval" | "pp_*"
    zero: bool
    fn: Any                   # the jitted callable (head builder output)
    args: Tuple               # abstract example args for make_jaxpr/lower
    plan: Optional[Any]       # TPPlan when tensor-parallel, else None
    # Exact psum-over-model budget for the pp_* kinds (the per-stage slice
    # of expected_collectives — parallel/pp/partition.stage_model_psums);
    # None everywhere else (the TPPlan drives the budget instead).
    model_psum_budget: Optional[int] = None


class ProgramSpec(NamedTuple):
    name: str
    kind: str
    zero: bool
    tp: bool
    build: Callable[["_Ctx", str], BuiltProgram]
    # Which workload family the entry belongs to: "image" (the CIFAR
    # classifier programs), "lm" (the tinylm decoder: LM train step +
    # the KV-cache serving programs), or None (workload-agnostic, e.g.
    # the drift audit — a params fingerprint prices identically).
    workload: Optional[str] = "image"


class _Ctx(NamedTuple):
    """Shared build context: model + meshes + abstract state, built once
    per audit run (model init is the only concrete computation).
    ``mesh3d``/``pp_plan`` exist only under a 3-entry ``--mesh-shape``
    with s>1 AND a model that declares PP_BLOCKS — the staged programs
    are registered exactly then."""
    model: Any
    mesh1d: Any
    mesh2d: Any
    plan: Optional[Any]
    params: Any
    stats: Any
    model_name: str = DEFAULT_MODEL
    mesh3d: Any = None
    pp_plan: Optional[Any] = None
    workload: str = "image"


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def _batch(stacked: bool = False):
    shape = (_ACCUM, _BATCH) if stacked else (_BATCH,)
    return {"image": jax.ShapeDtypeStruct(shape + (32, 32, 3), jnp.uint8),
            "label": jax.ShapeDtypeStruct(shape, jnp.int32)}


def _eval_batch():
    b = _batch()
    b["mask"] = jax.ShapeDtypeStruct((_BATCH,), jnp.bool_)
    return b


def _rng():
    return _sds(jax.random.key(0))


def _sgd():
    from ..optim import SGDConfig, triangular_lr
    return SGDConfig(lr=0.1), functools.partial(
        triangular_lr, base_lr=0.1, num_epochs=2, steps_per_epoch=4)


def _train_state(ctx: _Ctx, mesh, *, zero: bool, plan):
    from ..train.step import init_train_state
    state = init_train_state(ctx.params, ctx.stats)
    if zero:
        from ..train.zero import init_opt_shard
        state = state._replace(
            opt_state=init_opt_shard(state.params, mesh, plan=plan))
    return _sds(state)


def _build_step(ctx: _Ctx, name: str, *, accum: bool, zero: bool,
                tp: bool) -> BuiltProgram:
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    cfg, sched = _sgd()
    from ..train.step import make_train_step
    fn = make_train_step(ctx.model, cfg, sched, mesh, plan=plan,
                         accum=accum, shard_update=zero)
    state = _train_state(ctx, mesh, zero=zero, plan=plan)
    return BuiltProgram(name, "update", zero, fn,
                        (state, _batch(stacked=accum), _rng()), plan)


def _build_eval(ctx: _Ctx, name: str, *, tp: bool) -> BuiltProgram:
    from ..train.step import make_eval_step
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    fn = make_eval_step(ctx.model, mesh, plan=plan)
    return BuiltProgram(name, "eval", False, fn,
                        (_sds(ctx.params), _sds(ctx.stats), _eval_batch()),
                        plan)


def _build_forward(ctx: _Ctx, name: str, *, tp: bool) -> BuiltProgram:
    from ..train.step import make_eval_forward
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    fn = make_eval_forward(ctx.model, mesh, plan=plan)
    images = jax.ShapeDtypeStruct((_BATCH, 32, 32, 3), jnp.uint8)
    return BuiltProgram(name, "forward", False, fn,
                        (_sds(ctx.params), _sds(ctx.stats), images), plan)


def _build_drift(ctx: _Ctx, name: str) -> BuiltProgram:
    from ..resilience.drift import make_drift_audit
    fn = make_drift_audit(ctx.mesh1d)
    return BuiltProgram(name, "audit", False, fn, (_sds(ctx.params),), None)


def auto_plan_path(model_name: str, mesh_2d: Tuple[int, int]) -> str:
    """Repo-root path of the COMMITTED searched plan for a (model, mesh)
    pair — ``plans/<model>_<d>x<m>.autoplan.json``, written by
    ``python -m ddp_tpu.parallel.tp --search --out``.  The golden plan
    CI audits and trains against lives at this path for the default
    (deepnn, (2,4)) context."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    d, m = int(mesh_2d[0]), int(mesh_2d[1])
    return os.path.join(root, "plans",
                        f"{model_name}_{d}x{m}.autoplan.json")


def _ctx_mesh_2d(ctx: _Ctx) -> Tuple[int, int]:
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
    shape = dict(ctx.mesh2d.shape)
    return int(shape[DATA_AXIS]), int(shape[MODEL_AXIS])


def _auto_doc(ctx: _Ctx) -> Optional[dict]:
    """The committed searched plan doc for this context's (model, mesh),
    or None when no plan is committed.  A file that EXISTS but fails
    validation or names a different model/mesh raises — a corrupt
    committed plan must fail the audit, not silently vanish from it."""
    path = auto_plan_path(ctx.model_name, _ctx_mesh_2d(ctx))
    if not os.path.exists(path):
        return None
    from ..parallel.tp.autoplan import read_plan_doc
    doc = read_plan_doc(path)
    if doc["model"] != ctx.model_name or \
            tuple(doc["mesh_shape"]) != _ctx_mesh_2d(ctx):
        raise ValueError(
            f"{path} names model {doc['model']!r} mesh "
            f"{doc['mesh_shape']} but its filename claims "
            f"({ctx.model_name!r}, {_ctx_mesh_2d(ctx)})")
    return doc


def _build_auto(ctx: _Ctx, name: str) -> BuiltProgram:
    """The train step under the committed searched plan — the auto-plan
    twin of ``train_step@tp``, built through the same head builders so
    the strict auditor checks the exact program ``--auto_plan`` runs.
    The doc drives the recipe AND the ZeRO choice (the BuiltProgram's
    ``zero`` comes from the doc, not the registry row)."""
    doc = _auto_doc(ctx)
    assert doc is not None  # build_programs skips the entry otherwise
    from ..parallel.tp.autoplan import plan_from_doc
    plan = plan_from_doc(doc, ctx.params, ctx.stats)
    zero = bool(doc.get("zero"))
    cfg, sched = _sgd()
    from ..train.step import make_train_step
    fn = make_train_step(ctx.model, cfg, sched, ctx.mesh2d, plan=plan,
                         shard_update=zero)
    state = _train_state(ctx, ctx.mesh2d, zero=zero, plan=plan)
    return BuiltProgram(name, "update", zero, fn,
                        (state, _batch(), _rng()), plan)


def _pp_names(pp_plan) -> List[str]:
    """Registry names of the staged programs a context with this stage
    plan registers — one forward/backward per non-last stage, the fused
    forward+backward on the last, one update per stage."""
    s = pp_plan.num_stages
    return ([f"pp_fwd_s{j}@pp" for j in range(s - 1)]
            + ["pp_fb@pp"]
            + [f"pp_bwd_s{j}@pp" for j in range(s - 1)]
            + [f"pp_update_s{k}@pp" for k in range(s)])


def _pp_programs(ctx: _Ctx) -> List[BuiltProgram]:
    """The pipeline stage programs, built through the REAL schedule
    (parallel/pp/schedule._PPStep) over the context's 3-D mesh — the
    exact per-stage jitted shard_map programs a (d, m, s) train step
    dispatches, traced with abstract args.  Each carries its exact
    psum-over-model budget (``stage_model_psums``); activation handoffs
    are device transfers OUTSIDE these programs, so every staged jaxpr
    must stay 2-D — the stage-axis invariant jaxpr_audit enforces."""
    from ..parallel.pp.partition import stage_model_psums, stage_subtree
    from ..parallel.pp.schedule import _PPStep
    cfg, sched = _sgd()
    step = _PPStep(ctx.model_name, cfg, sched, ctx.mesh3d, ctx.pp_plan,
                   tp_plan=ctx.plan, schedule="1f1b")
    state = _train_state(ctx, ctx.mesh3d, zero=False, plan=None)
    step._build(state)
    progs = step._progs
    updates = step._update_programs(_ACCUM)
    plan, s = ctx.pp_plan, ctx.pp_plan.num_stages
    p_sub = [stage_subtree(plan, k, state.params) for k in range(s)]
    imgs, labels = (_batch(stacked=True)["image"],
                    _batch(stacked=True)["label"])
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    lsum = jax.ShapeDtypeStruct((), jnp.float32)
    rng = _rng()

    def budget(k, role):
        return stage_model_psums(plan, ctx.plan, k, role=role)

    # Activation ShapeDtypeStructs, chained through the real forwards.
    acts, x = {}, imgs
    for j in range(s - 1):
        acts[j + 1] = jax.eval_shape(progs["fwd"][j], p_sub[j], x, rng,
                                     i32, i32)
        x = acts[j + 1]

    out: List[BuiltProgram] = []
    for j in range(s - 1):
        xin = imgs if j == 0 else acts[j]
        out.append(BuiltProgram(
            f"pp_fwd_s{j}@pp", "pp_forward", False, progs["fwd"][j],
            (p_sub[j], xin, rng, i32, i32), None,
            model_psum_budget=budget(j, "forward")))
    out.append(BuiltProgram(
        "pp_fb@pp", "pp_fwdbwd", False, progs["fb"],
        (p_sub[s - 1], p_sub[s - 1], lsum, acts[s - 1], labels, rng,
         i32, i32), None, model_psum_budget=budget(s - 1, "fwdbwd")))
    for j in range(s - 1):
        xin = imgs if j == 0 else acts[j]
        out.append(BuiltProgram(
            f"pp_bwd_s{j}@pp", "pp_backward", False, progs["bwd"][j],
            (p_sub[j], p_sub[j], xin, acts[j + 1], rng, i32, i32), None,
            model_psum_budget=budget(j, "backward")))
    for k in range(s):
        out.append(BuiltProgram(
            f"pp_update_s{k}@pp", "pp_update", False, updates[k],
            (p_sub[k], p_sub[k], p_sub[k], i32), None,
            model_psum_budget=budget(k, "update")))
    return out


def _lm_module():
    from ..models import transformer as tfm
    return tfm


def _lm_cache_sds(slots: int):
    tfm = _lm_module()
    return jax.ShapeDtypeStruct(
        (int(tfm.N_LAYERS), slots, int(tfm.T_MAX), int(tfm.N_HEADS),
         int(tfm.HEAD_DIM)), jnp.float32)


def _build_lm_step(ctx: _Ctx, name: str, *, tp: bool) -> BuiltProgram:
    """The LM optimizer step (train/lm.py) — same invariants as the
    classifier update: psum-over-data on grads, full state donation,
    exactly the plan's model-psum count under TP."""
    from ..train.lm import make_lm_train_step
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    cfg, sched = _sgd()
    fn = make_lm_train_step(ctx.model, cfg, sched, mesh, plan=plan)
    state = _train_state(ctx, mesh, zero=False, plan=plan)
    tokens = jax.ShapeDtypeStruct((_BATCH, _LM_T), jnp.int32)
    return BuiltProgram(name, "update", False, fn,
                        (state, tokens, _rng()), plan)


def _build_lm_prefill(ctx: _Ctx, name: str, *, tp: bool) -> BuiltProgram:
    """The serve prompt prefill (serve/kvcache.py): forward-kind — no
    data collectives ever; exactly the plan's forward model psums under
    TP (attention heads sharded, same rows as the train forward)."""
    from ..serve.kvcache import make_lm_prefill
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    fn = make_lm_prefill(_lm_module(), mesh, plan=plan)
    tokens = jax.ShapeDtypeStruct((_LM_BUCKET,), jnp.int32)
    return BuiltProgram(name, "forward", False, fn,
                        (_sds(ctx.params), tokens), plan)


def _build_lm_decode(ctx: _Ctx, name: str, *, tp: bool) -> BuiltProgram:
    """The single-token decode step over the slot-sharded KV cache —
    the ONE executable a serving run decodes every token with."""
    from ..serve.kvcache import make_lm_decode
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    plan = ctx.plan if tp else None
    fn = make_lm_decode(_lm_module(), mesh, plan=plan)
    vec = jax.ShapeDtypeStruct((_LM_SLOTS,), jnp.int32)
    cache = _lm_cache_sds(_LM_SLOTS)
    return BuiltProgram(name, "forward", False, fn,
                        (_sds(ctx.params), vec, vec, cache, cache), plan)


def _build_lm_cache_write(ctx: _Ctx, name: str, *, tp: bool
                          ) -> BuiltProgram:
    """The KV-cache slot scatter: pure ownership arithmetic, audited
    COLLECTIVE-FREE (the BuiltProgram carries no plan even under TP, so
    any psum — model or data — fails the audit)."""
    from ..serve.kvcache import make_cache_write
    tfm = _lm_module()
    mesh = ctx.mesh2d if tp else ctx.mesh1d
    fn = make_cache_write(mesh, ctx.plan if tp else None)
    cache = _lm_cache_sds(_LM_SLOTS)
    kv_new = jax.ShapeDtypeStruct(
        (int(tfm.N_LAYERS), _LM_BUCKET, int(tfm.N_HEADS),
         int(tfm.HEAD_DIM)), jnp.float32)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    return BuiltProgram(name, "forward", False, fn,
                        (cache, cache, kv_new, kv_new, slot), None)


def _spec(name, kind, *, zero=False, tp=False, accum=False,
          auto=False, workload: Optional[str] = "image",
          builder=None) -> ProgramSpec:
    if builder is not None:
        build = functools.partial(builder, tp=tp)
    elif auto:
        build = _build_auto
    elif kind == "update":
        build = functools.partial(_build_step, accum=accum, zero=zero,
                                  tp=tp)
    elif kind == "eval":
        build = functools.partial(_build_eval, tp=tp)
    elif kind == "audit":
        build = _build_drift
    else:
        build = functools.partial(_build_forward, tp=tp)
    return ProgramSpec(name, kind, zero, tp, build, workload)


# The default registry — all of it traces in seconds; names are stable
# CLI/JSON keys (``--programs`` selects by them).
REGISTRY: Tuple[ProgramSpec, ...] = (
    _spec("train_step@dp8", "update"),
    _spec("train_step_accum@dp8", "update", accum=True),
    _spec("train_step_zero@dp8", "update", zero=True),
    _spec("train_step_zero_accum@dp8", "update", zero=True, accum=True),
    _spec("train_step@tp", "update", tp=True),
    _spec("train_step_accum@tp", "update", tp=True, accum=True),
    _spec("train_step_zero@tp", "update", zero=True, tp=True),
    # The searched plan (plans/<model>_<d>x<m>.autoplan.json) as a
    # first-class audited program: present only when a plan is committed
    # for the context's (model, mesh).
    _spec("train_step@auto", "update", auto=True),
    _spec("eval_step@dp8", "eval"),
    _spec("eval_step@tp", "eval", tp=True),
    _spec("serve_forward@dp8", "forward"),
    _spec("serve_forward@tp", "forward", tp=True),
    _spec("drift_audit@dp8", "audit", workload=None),
    # The tinylm decoder workload (--model tinylm): the LM train step
    # plus the generative serving programs (serve/kvcache.py), priced
    # and audited like every other entry.
    _spec("lm_train_step@dp8", "update", workload="lm",
          builder=_build_lm_step),
    _spec("lm_train_step@tp", "update", tp=True, workload="lm",
          builder=_build_lm_step),
    _spec("lm_prefill@dp8", "forward", workload="lm",
          builder=_build_lm_prefill),
    _spec("lm_prefill@tp", "forward", tp=True, workload="lm",
          builder=_build_lm_prefill),
    _spec("lm_decode@dp8", "forward", workload="lm",
          builder=_build_lm_decode),
    _spec("lm_decode@tp", "forward", tp=True, workload="lm",
          builder=_build_lm_decode),
    _spec("lm_cache_write@dp8", "forward", workload="lm",
          builder=_build_lm_cache_write),
    _spec("lm_cache_write@tp", "forward", tp=True, workload="lm",
          builder=_build_lm_cache_write),
)


def program_names(workload: Optional[str] = None) -> List[str]:
    """All registry names; with ``workload`` given, only the entries
    that build for that workload (workload-``None`` specs — the
    model-agnostic programs — always apply)."""
    if workload is None:
        return [s.name for s in REGISTRY]
    return [s.name for s in REGISTRY
            if s.workload is None or s.workload == workload]


def build_context(model_name: str = DEFAULT_MODEL,
                  mesh_2d: Tuple[int, ...] = DEFAULT_MESH_2D) -> _Ctx:
    """Meshes + model + plan, shared by every registry build.  The 1-D
    mesh spans d*m devices so both regimes audit the same device budget
    (CI: the (2,4)x8 virtual mesh).  A 3-entry shape (d, m, s) with s>1
    additionally builds the (data × model × stage) mesh and the stage
    plan, registering the staged pipeline programs (``pp_*@pp``) — the
    backend then needs d*m*s virtual devices."""
    from ..models import get_model
    from ..models import transformer as tfm
    from ..parallel.mesh import make_mesh
    d, m = int(mesh_2d[0]), int(mesh_2d[1])
    s = int(mesh_2d[2]) if len(mesh_2d) > 2 else 1
    workload = "lm" if model_name == tfm.LM_NAME else "image"
    model = get_model(model_name)
    params, stats = model.init(jax.random.key(0))
    mesh1d = make_mesh(d * m)
    mesh2d = make_mesh(shape=(d, m))
    plan = None
    if m > 1:
        from ..parallel.tp.plan import plan_for_model
        try:
            plan = plan_for_model(model_name, params, stats, model_size=m)
        except ValueError:
            plan = None  # model without a recipe: tp entries are skipped
    mesh3d, pp_plan = None, None
    if s > 1:
        from ..parallel.pp.partition import plan_stages
        try:
            pp_plan = plan_stages(model_name, s, model_size=m,
                                  params=params, batch_stats=stats)
            mesh3d = make_mesh(shape=(d, m, s))
        except ValueError:
            pp_plan = None  # no PP_BLOCKS / infeasible cut: pp skipped
    return _Ctx(model, mesh1d, mesh2d, plan, params, stats, model_name,
                mesh3d, pp_plan, workload)


def build_programs(ctx: _Ctx, names=None) -> List[BuiltProgram]:
    """Build the selected registry entries (default: every entry the
    context supports — tp entries are skipped when the model has no
    TP_RECIPE/plan, the staged ``pp_*@pp`` entries exist only under a
    3-D context with a stage plan)."""
    pp_names = _pp_names(ctx.pp_plan) if ctx.pp_plan is not None else []
    known = set(program_names()) | set(pp_names)
    wanted = set(names) if names else None
    unknown = (wanted or set()) - known
    if unknown:
        raise ValueError(f"unknown program(s) {sorted(unknown)}; "
                         f"registry has {program_names() + pp_names}")
    out = []
    for spec in REGISTRY:
        if wanted is not None and spec.name not in wanted:
            continue
        if spec.workload is not None and spec.workload != ctx.workload:
            continue
        if spec.tp and ctx.plan is None:
            continue
        if spec.name.endswith("@auto") and _auto_doc(ctx) is None:
            continue
        out.append(spec.build(ctx, spec.name))
    if pp_names and (wanted is None or wanted & set(pp_names)):
        built = _pp_programs(ctx)
        out.extend(p for p in built
                   if wanted is None or p.name in wanted)
    return out
