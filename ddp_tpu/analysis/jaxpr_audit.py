"""Jaxpr-level auditors: collective inventory + invariants, constant
capture, donation.

Everything here works on the TRACED program — ``jax.make_jaxpr`` /
``jit.lower()`` only, no XLA compile, no execution — so the whole default
registry audits in seconds on one CPU.  The walker recurses through every
equation parameter that holds a sub-jaxpr (``pjit``, ``shard_map``,
``scan``, ``custom_vjp_call_jaxpr``, ``cond`` branches ...), which is
where all the interesting equations live: a jitted shard_map program's
top level is a single ``pjit`` equation.

Primitive-name facts this encodes: ``lax.pmean`` lowers to ``psum`` + div,
so gradient pmeans inventory as ``psum``; the psum equation carries its
axis names in ``params["axes"]``, while ``all_gather`` / ``reduce_scatter``
/ ``ppermute`` carry ``params["axis_name"]``; ``lax.psum_scatter`` is the
``reduce_scatter`` primitive.  Positional (int) axes are filtered out —
only NAMED mesh axes are collective traffic.
"""
from __future__ import annotations

import collections
import warnings
from typing import Dict, Iterator, List, Tuple

import jax
import numpy as np

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, STAGE_AXIS
from .findings import Finding, make_finding

# Named-axis communication primitives.  axis_index is deliberately absent
# (it reads coordinates, moves no data); pmean is absent because it never
# survives tracing (psum + div).
COLLECTIVE_PRIMITIVES = ("psum", "pmin", "pmax", "all_gather",
                        "reduce_scatter", "ppermute", "all_to_all",
                        "pbroadcast")



def primitive_name(eqn) -> str:
    """The equation's primitive, with the vma type system's variants
    folded into the collective they lower to: under ``check_vma=True`` a
    ``lax.psum`` of a varying value traces as ``psum_invariant`` (and
    ``all_gather`` as ``all_gather_invariant``) — same traffic."""
    return eqn.primitive.name.removesuffix("_invariant")


MIB = 2 ** 20
LARGE_CONST_BYTES = 1 * MIB     # constant-capture bloat threshold
LARGE_INPUT_BYTES = 1 * MIB     # donation-required input threshold


def trace_jaxpr(fn, args):
    """Closed jaxpr of ``fn(*args)`` — abstract tracing only (args are
    ShapeDtypeStructs), so no compile and no device memory."""
    return jax.make_jaxpr(fn)(*args)


def _sub_jaxprs(params: dict) -> Iterator:
    """Every jaxpr nested in one equation's params, whatever key or
    wrapper (ClosedJaxpr vs raw Jaxpr, single vs tuple-of-branches)."""
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every equation, descending into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _axes_of(eqn) -> Tuple[str, ...]:
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def collective_inventory(closed_jaxpr) -> Dict[Tuple[str, Tuple[str, ...]],
                                               int]:
    """``{(primitive, named axes): count}`` over the whole program."""
    inv: collections.Counter = collections.Counter()
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if primitive_name(eqn) in COLLECTIVE_PRIMITIVES:
            inv[(primitive_name(eqn), _axes_of(eqn))] += 1
    return dict(inv)


def inventory_as_json(inv: Dict) -> List[dict]:
    return [{"primitive": prim, "axes": list(axes), "count": n}
            for (prim, axes), n in sorted(inv.items())]


def _count(inv: Dict, prim: str, axis: str) -> int:
    """Occurrences of ``prim`` whose axis set is exactly ``(axis,)``."""
    return sum(n for (p, axes), n in inv.items()
               if p == prim and axes == (axis,))


def audit_collectives(name: str, kind: str, inv: Dict,
                      plan=None, zero: bool = False,
                      model_psum_budget=None) -> List[Finding]:
    """Check one program's collective inventory against its declarative
    invariants.

    ``kind``: ``update`` (an optimizer step: backward exists, gradients
    must be reduced over ``data``), ``forward`` (a serve/logits program:
    collective-free off the data axis — and in this codebase entirely
    collective-free, the logits gather is an out_sharding, not a
    collective), ``eval`` (the counter-psum evaluation step), or
    ``audit`` (the drift-audit fingerprint program — only the generic
    invariants apply: data-axis psums allowed, everything else banned).
    The staged pipeline programs (parallel/pp/schedule.py) add
    ``pp_forward`` (a stage forward: NO data-axis collectives — it only
    computes an activation), ``pp_backward`` / ``pp_fwdbwd`` (stage
    backward / fused last-stage forward+backward: the per-stage gsum
    reduction must psum over ``data``), and ``pp_update`` (per-stage SGD:
    collective-free on EVERY axis — the grads arrive pre-reduced, a psum
    here would double-count the data axis).
    ``plan`` (a TPPlan) switches on the model-axis budget from
    ``expected_collectives`` — the printed plan table's numbers; without a
    plan, ANY model-axis traffic is a wrong-axis collective.
    ``model_psum_budget`` (the pp entries) pins the model-psum count to an
    EXACT per-stage number instead (``pp/partition.stage_model_psums``)
    and takes precedence over ``plan``.  ``zero`` allows (and requires)
    the ZeRO update's single ``reduce_scatter``/``all_gather`` pair over
    ``data``.

    The stage axis never appears here at all: stage handoff is an
    explicit device transfer between per-stage 2-D programs, so ANY
    collective over ``stage`` is an error regardless of kind.
    """
    out: List[Finding] = []

    def err(check: str, detail: str) -> None:
        out.append(make_finding("error", check, name, detail))

    # -- axis whitelist: nothing may touch an axis we don't know ---------
    known = {DATA_AXIS, MODEL_AXIS}
    for (prim, axes), n in sorted(inv.items()):
        if STAGE_AXIS in axes:
            err("collective-axis",
                f"{prim} over '{STAGE_AXIS}' x{n} — stage handoff is an "
                "explicit device transfer between per-stage programs "
                "(parallel/pp/schedule.py), never a collective; every "
                "staged jaxpr must stay 2-D (data × model)")
        stray = [a for a in axes if a not in known and a != STAGE_AXIS]
        if stray:
            err("collective-axis",
                f"{prim} over unknown axis {stray} (x{n})")

    # -- model-axis budget ----------------------------------------------
    model_psums = _count(inv, "psum", MODEL_AXIS)
    if model_psum_budget is not None:
        if model_psums != int(model_psum_budget):
            err("collective-count",
                f"psum over '{MODEL_AXIS}' x{model_psums}, the stage plan "
                f"expects exactly x{int(model_psum_budget)} for this "
                "stage program (stage_model_psums) — a stage cut moved a "
                "TP layer's collective, or a reduction landed on the "
                "wrong axis")
    elif plan is not None:
        from ..parallel.tp.plan import (expected_collectives,
                                        format_collective_table)
        backward = kind == "update"
        exp = expected_collectives(plan, backward=backward)
        if model_psums != exp["psum_model"]:
            err("collective-count",
                f"psum over '{MODEL_AXIS}' x{model_psums}, plan expects "
                f"x{exp['psum_model']} (fwd {exp['psum_model_fwd']} + bwd "
                f"{exp['psum_model_bwd']}) — a TP layer collective is "
                "missing or duplicated, or a gradient reduction landed on "
                "the wrong axis; the plan's per-layer unit table:\n"
                + format_collective_table(plan, backward=backward))
    elif model_psums:
        err("collective-axis",
            f"psum over '{MODEL_AXIS}' x{model_psums} in a program with "
            f"no tensor-parallel plan — gradient/loss reductions belong "
            f"on '{DATA_AXIS}'")

    # -- zero model-axis gathers, anywhere, ever -------------------------
    model_gathers = _count(inv, "all_gather", MODEL_AXIS)
    if model_gathers:
        err("model-gather",
            f"all_gather over '{MODEL_AXIS}' x{model_gathers} — a "
            "model-axis gather rematerializes the sharded weights (the "
            "perf cliff TP exists to avoid); hot paths must stay "
            "gather-free on the model axis")

    # -- per-kind data-axis shape ----------------------------------------
    data_psums = _count(inv, "psum", DATA_AXIS)
    data_coll = sum(n for (p, axes), n in inv.items() if DATA_AXIS in axes)
    if kind == "update" and data_psums == 0:
        err("collective-count",
            f"no psum over '{DATA_AXIS}' in an update program — the "
            "gradient/loss all-reduce is missing; shards would train on "
            "their local batches only and silently diverge")
    if kind == "forward" and data_coll:
        err("collective-count",
            f"{data_coll} data-axis collective(s) in a serve forward "
            "— per-row logits are independent; the batch gather is "
            "an output sharding, not a collective, so this program "
            "must be collective-free on the data axis")
    if kind == "pp_forward" and data_coll:
        err("collective-count",
            f"{data_coll} data-axis collective(s) in a pipeline stage "
            "forward — a stage forward only computes its activation "
            "shard; nothing is reduced until the backward's gsum psum")
    if kind in ("pp_backward", "pp_fwdbwd") and data_psums == 0:
        err("collective-count",
            f"no psum over '{DATA_AXIS}' in a pipeline stage backward — "
            "the per-stage gsum reduction is missing; the stage's data "
            "shards would accumulate local gradients only and silently "
            "diverge")
    if kind == "pp_update" and data_coll:
        err("collective-count",
            f"{data_coll} data-axis collective(s) in a per-stage update "
            "— the stage's grads arrive pre-reduced from the backward "
            "programs; a reduction here double-counts the data axis")

    # -- ZeRO pair -------------------------------------------------------
    rs_data = _count(inv, "reduce_scatter", DATA_AXIS)
    ag_data = _count(inv, "all_gather", DATA_AXIS)
    if zero:
        if rs_data != 1 or ag_data != 1:
            err("collective-count",
                f"ZeRO update must show exactly one reduce_scatter and "
                f"one all_gather over '{DATA_AXIS}' (the flat-buffer "
                f"grad-shard/param-gather pair); saw reduce_scatter "
                f"x{rs_data}, all_gather x{ag_data}")
    else:
        if rs_data:
            err("collective-count",
                f"reduce_scatter over '{DATA_AXIS}' x{rs_data} in a "
                "non-ZeRO program")
        if ag_data:
            err("collective-count",
                f"all_gather over '{DATA_AXIS}' x{ag_data} in a "
                "non-ZeRO program")

    # -- primitives this codebase never emits ----------------------------
    for prim in ("ppermute", "all_to_all", "pmin", "pmax", "pbroadcast"):
        n = sum(c for (p, _), c in inv.items() if p == prim)
        if n:
            err("collective-axis",
                f"unexpected {prim} x{n} — no registered program family "
                "uses this collective; likely a wrong primitive choice")
    return out


def _is_weak(c) -> bool:
    """jax Arrays carry weak_type on their aval; raw np values are always
    strongly typed; bare Python numbers are weak (and normally never
    reach consts — they inline as literals)."""
    aval = getattr(c, "aval", None)
    if aval is not None:
        return bool(getattr(aval, "weak_type", False))
    if hasattr(c, "weak_type"):
        return bool(c.weak_type)
    return isinstance(c, (bool, int, float, complex))


def _const_bytes(c) -> int:
    try:
        return int(np.asarray(c).nbytes)
    except Exception:
        return 0


def audit_constants(name: str, closed_jaxpr) -> List[Finding]:
    """Constant-capture scan over the closed jaxpr.

    Every registered head program traces with ZERO consts (weak-typed
    Python scalar closures fold in as inline literals and true data flows
    through arguments), so ANY captured const is drift.  Graded:

    - >1 MiB — ``error``: closure-captured bulk data bloats every
      executable and can never be donated or sharded; pass it as an
      argument.
    - size-1 non-weak-typed — ``warning`` (``scalar-closure``): a
      ``np.float32(x)`` / shape-(1,) hyperparameter closure.  Unlike a
      captured Python scalar (weak-typed, folds into the program
      unchanged), it pins a dtype, and the call-site habit it indicates —
      wrapping step-varying hyperparameters in np — retraces per distinct
      value.
    - anything else — ``warning``: a captured host array that should be
      an argument."""
    out: List[Finding] = []
    for c in closed_jaxpr.consts:
        nbytes = _const_bytes(c)
        shape = tuple(np.shape(c))
        if nbytes > LARGE_CONST_BYTES:
            out.append(make_finding(
                "error", "constant-capture", name,
                f"captured constant {shape} "
                f"({nbytes / MIB:.1f} MiB) baked into the jaxpr — pass it "
                "as an argument (donatable, shardable) instead of closing "
                "over it"))
        elif int(np.size(c)) == 1 and not _is_weak(c):
            out.append(make_finding(
                "warning", "scalar-closure", name,
                f"non-weak-typed scalar constant {shape} (dtype "
                f"{np.asarray(c).dtype}) closed into the program — a "
                "Python scalar folds in weak-typed; a np scalar closure "
                "usually means a hyperparameter that will retrace per "
                "value"))
        else:
            out.append(make_finding(
                "warning", "constant-capture", name,
                f"captured constant {shape} "
                f"({nbytes} B) — head programs trace const-free; pass "
                "captured arrays as arguments"))
    return out


def audit_donation(name: str, kind: str, fn, args) -> List[Finding]:
    """Donation check for update programs: every input buffer >= 1 MiB
    must be donated, or the step permanently holds two copies of the
    state (params + momentum are the overwhelming majority of live HBM in
    data-parallel training — the reuse ``donate_argnums=(0,)`` exists
    for).  Forward/eval programs are exempt: their params are shared
    across calls and must NOT be donated.  The staged ``pp_*`` programs
    are exempt too: their params persist across the whole microbatch
    schedule (donating them in any one program would kill the others),
    gsum IS donated where it can alias (the backward/FB accumulators),
    and the per-stage update deliberately leaves gsum undonated — its
    outputs already alias params+momentum, so a third donation has no
    buffer to reuse (see schedule._update_programs)."""
    if kind != "update":
        return []
    try:
        with warnings.catch_warnings():
            # Lowering abstract (uncommitted) args trips jax's
            # "donated buffers were not usable" advisory; donation is
            # what we are here to READ, not a property of these fake
            # inputs.
            warnings.simplefilter("ignore")
            lowered = fn.lower(*args)
        infos = jax.tree_util.tree_leaves(lowered.args_info)
    except Exception as e:  # introspection, never a crash
        return [make_finding(
            "warning", "donation", name,
            f"could not lower for donation introspection: {e!r}")]
    out: List[Finding] = []
    undonated = [i for i in infos
                 if not i.donated and _aval_bytes(i) >= LARGE_INPUT_BYTES]
    for info in undonated:
        aval = getattr(info, "aval", None) or getattr(info, "_aval", None)
        out.append(make_finding(
            "error", "donation", name,
            f"large input buffer {aval} "
            f"({_aval_bytes(info) / MIB:.1f} MiB) is not donated — the "
            "update holds a dead copy of it across steps; add it to "
            "donate_argnums"))
    return out


def _aval_bytes(info) -> int:
    aval = getattr(info, "aval", None) or getattr(info, "_aval", None)
    if aval is None:
        return 0
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
