"""Static cost model: per-program FLOP / byte / collective-volume
accounting over traced jaxprs.

PR 7's auditor proves a program is *shaped* right (collectives on the
right axes, no captured consts); this pass says how *big* it is — the
quantities the measured-cost auto-sharding planner (ROADMAP 5, arXiv
2004.13336) and pipeline stage partitioning (ROADMAP 1, Mesh-TensorFlow's
named-axis cost reasoning, arXiv 1811.02084) take as inputs.  Everything
works on the abstract trace: no XLA compile, no device memory.

Accounting rules, per equation (depth-first through ``pjit`` /
``shard_map`` / ``custom_*`` sub-jaxprs, so the unit is the PER-DEVICE
program — the shard_map body's shapes are per-shard, which is the unit a
step's wall-clock is set by):

- ``conv_general_dilated`` — ``2 * prod(out_shape) * (kernel_in_feat *
  prod(kernel_spatial))`` from the equation's own ConvDimensionNumbers.
  The formula is direction-agnostic: forward, input-gradient and
  weight-gradient convs all carry their contraction in the rhs spec, so
  autodiff's transpose convs account exactly.
- ``dot_general`` — ``2 * B * M * N * K`` from the equation's
  dimension_numbers (batch dims B, contraction K, remaining M x N).
- reductions (``reduce_sum`` ...) — one flop per INPUT element.
- data movement (reshape/broadcast/slice/convert/...) — zero flops.
- everything else — one flop per output element (``elementwise``).
- collectives (``jaxpr_audit.COLLECTIVE_PRIMITIVES``) — zero flops, but
  counted with their per-device payload (operand bytes) per named axis:
  the volume term a ring all-reduce's time is linear in.
- ``scan`` multiplies its body by ``length``; ``cond`` takes the most
  expensive branch; ``while`` counts one trip and flags the program as
  having an unknown trip count.

``bytes`` is operand+result bytes summed over leaf equations — a proxy
for memory traffic (every buffer assumed touched once per use, no cache
modeling), the roofline denominator next to flops.

Budgets: ``make_budgets`` snapshots the per-program table into the
``BUDGETS.json`` schema; ``check_budgets`` diffs a fresh table against it
and emits ``budget`` error findings on regressions past the tolerance —
the CI gate that turns "this PR made the train step 30% more expensive"
into a red build instead of archaeology.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .findings import Finding, make_finding
from .jaxpr_audit import (COLLECTIVE_PRIMITIVES, MIB, _sub_jaxprs,
                          primitive_name)

# Pure data-movement / metadata primitives: zero flops (bytes still count).
_ZERO_FLOP = frozenset((
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "pad", "rev", "iota", "convert_element_type", "bitcast_convert_type",
    "copy", "device_put", "sharding_constraint", "stop_gradient",
    "gather", "scatter", "split", "axis_index", "pvary",
))

_REDUCE = frozenset(("reduce_sum", "reduce_max", "reduce_min",
                     "reduce_prod", "reduce_and", "reduce_or",
                     "argmax", "argmin"))

# The budget file's per-program metrics, in check order.
BUDGET_METRICS = ("flops", "bytes", "peak_live_bytes",
                  "collective_payload_bytes")
DEFAULT_TOLERANCE_PCT = 10.0

FLOP_CLASSES = ("conv", "dot", "elementwise", "reduce")


class Cost:
    """One program's (or sub-jaxpr's) cost rollup.  Mutable accumulator;
    ``+`` and ``scaled`` return new instances."""

    __slots__ = ("flops", "bytes", "by_class", "collectives",
                 "unknown_trip_loops")

    def __init__(self) -> None:
        self.flops = 0
        self.bytes = 0
        self.by_class: Dict[str, int] = {c: 0 for c in FLOP_CLASSES}
        # {(primitive, axes): [count, payload_bytes]}
        self.collectives: Dict[Tuple[str, Tuple[str, ...]], List[int]] = {}
        self.unknown_trip_loops = 0

    def _merge(self, other: "Cost", k: int = 1) -> "Cost":
        self.flops += other.flops * k
        self.bytes += other.bytes * k
        for c in FLOP_CLASSES:
            self.by_class[c] += other.by_class[c] * k
        for key, (n, b) in other.collectives.items():
            cur = self.collectives.setdefault(key, [0, 0])
            cur[0] += n * k
            cur[1] += b * k
        self.unknown_trip_loops += other.unknown_trip_loops
        return self

    def __add__(self, other: "Cost") -> "Cost":
        return Cost()._merge(self)._merge(other)

    def scaled(self, k: int) -> "Cost":
        return Cost()._merge(self, k)

    @property
    def collective_count(self) -> int:
        return sum(n for n, _ in self.collectives.values())

    @property
    def collective_payload_bytes(self) -> int:
        return sum(b for _, b in self.collectives.values())

    def as_json(self) -> dict:
        return {
            "flops": int(self.flops),
            "bytes": int(self.bytes),
            "flops_by_class": {c: int(v) for c, v in self.by_class.items()},
            "collectives": [
                {"primitive": p, "axes": list(a),
                 "count": int(n), "payload_bytes": int(b)}
                for (p, a), (n, b) in sorted(self.collectives.items())],
            "collective_count": int(self.collective_count),
            "collective_payload_bytes": int(self.collective_payload_bytes),
            "unknown_trip_loops": int(self.unknown_trip_loops),
        }

    def budget_row(self) -> dict:
        return {"flops": int(self.flops), "bytes": int(self.bytes),
                "collective_count": int(self.collective_count),
                "collective_payload_bytes":
                    int(self.collective_payload_bytes)}


def _dtype_bytes(dtype) -> int:
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:     # extended dtypes (prng keys): count the backing
        return int(getattr(dtype, "itemsize", 4))


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * _dtype_bytes(dtype)


def _var_bytes(v) -> int:
    if hasattr(v, "val"):             # Literal: inlined scalar, no buffer
        return 0
    return aval_bytes(getattr(v, "aval", None))


def _out_elems(eqn) -> int:
    return sum(int(np.prod(v.aval.shape, dtype=np.int64))
               for v in eqn.outvars if hasattr(v, "aval"))


def _in_elems(eqn) -> int:
    return sum(int(np.prod(v.aval.shape, dtype=np.int64))
               for v in eqn.invars
               if not hasattr(v, "val") and hasattr(v, "aval"))


def _conv_flops(eqn) -> int:
    """2 * output elements * contraction size, from the equation's own
    ConvDimensionNumbers — exact for fwd, dgrad and wgrad convs alike
    (grouped convs: the kernel's in_feat dim is already cin/groups)."""
    dn = eqn.params["dimension_numbers"]
    rhs_shape = eqn.invars[1].aval.shape
    rhs_spec = dn.rhs_spec              # (out_feat, in_feat, *spatial)
    contraction = rhs_shape[rhs_spec[1]]
    for d in rhs_spec[2:]:
        contraction *= rhs_shape[d]
    out = int(np.prod(eqn.outvars[0].aval.shape, dtype=np.int64))
    return 2 * out * int(contraction)


def _dot_flops(eqn) -> int:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    k = int(np.prod([lhs[d] for d in lc], dtype=np.int64)) if lc else 1
    b = int(np.prod([lhs[d] for d in lb], dtype=np.int64)) if lb else 1
    m = int(np.prod([lhs[d] for d in range(len(lhs))
                     if d not in set(lc) | set(lb)], dtype=np.int64))
    n = int(np.prod([rhs[d] for d in range(len(rhs))
                     if d not in set(rc) | set(rb)], dtype=np.int64))
    return 2 * b * m * n * k


def _collective_axes(eqn) -> Tuple[str, ...]:
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def cost_of_eqn(eqn) -> Cost:
    name = primitive_name(eqn)
    if name == "scan":
        body = cost_of_jaxpr(eqn.params["jaxpr"].jaxpr)
        return body.scaled(int(eqn.params["length"]))
    if name == "while":
        c = Cost()
        for sub in _sub_jaxprs(eqn.params):     # cond + body, one trip
            c._merge(cost_of_jaxpr(sub))
        c.unknown_trip_loops += 1
        return c
    if name == "cond":
        branches = [cost_of_jaxpr(sub) for sub in _sub_jaxprs(eqn.params)]
        return max(branches, key=lambda c: (c.flops, c.bytes),
                   default=Cost())
    subs = list(_sub_jaxprs(eqn.params))
    if subs:                                    # pjit / shard_map / custom_*
        c = Cost()
        for sub in subs:
            c._merge(cost_of_jaxpr(sub))
        return c

    c = Cost()
    c.bytes = sum(_var_bytes(v) for v in eqn.invars) + \
        sum(_var_bytes(v) for v in eqn.outvars)
    if name == "conv_general_dilated":
        c.flops = _conv_flops(eqn)
        c.by_class["conv"] = c.flops
    elif name == "dot_general":
        c.flops = _dot_flops(eqn)
        c.by_class["dot"] = c.flops
    elif name in COLLECTIVE_PRIMITIVES:
        payload = sum(_var_bytes(v) for v in eqn.invars)
        c.collectives[(name, _collective_axes(eqn))] = [1, payload]
    elif name in _REDUCE:
        c.flops = _in_elems(eqn)
        c.by_class["reduce"] = c.flops
    elif name not in _ZERO_FLOP:
        c.flops = _out_elems(eqn)
        c.by_class["elementwise"] = c.flops
    return c


def cost_of_jaxpr(jaxpr) -> Cost:
    total = Cost()
    for eqn in jaxpr.eqns:
        total._merge(cost_of_eqn(eqn))
    return total


def program_cost(closed_jaxpr) -> Cost:
    """Per-device cost of one traced program (the shard_map body's
    per-shard shapes are what the walk sees)."""
    return cost_of_jaxpr(closed_jaxpr.jaxpr)


def _fmt(n: float, unit: str = "") -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= scale:
            return f"{n / scale:.2f}{suffix}{unit}"
    return f"{n:.0f}{unit}"


def cost_summary(cost: Cost, peak_live: Optional[int] = None) -> str:
    """One human line per program for the findings table."""
    dominant = ", ".join(
        f"{c} {100.0 * v / max(cost.flops, 1):.0f}%"
        for c, v in sorted(cost.by_class.items(), key=lambda kv: -kv[1])
        if v)
    parts = [f"flops {_fmt(cost.flops)} ({dominant or 'none'})",
             f"bytes {cost.bytes / MIB:.1f} MiB"]
    if peak_live is not None:
        parts.append(f"peak-live {peak_live / MIB:.1f} MiB")
    parts.append(
        f"collectives x{cost.collective_count}, "
        f"{cost.collective_payload_bytes / MIB:.2f} MiB payload"
        if cost.collective_count else "collective-free")
    if cost.unknown_trip_loops:
        parts.append(f"{cost.unknown_trip_loops} unknown-trip loop(s), "
                     "counted as one iteration")
    return " | ".join(parts)


# ---------------------------------------------------------------------------
# Per-layer forward costs (the plan table's predicted-cost column).
# ---------------------------------------------------------------------------

def layer_forward_costs(model, plan, params, batch_stats,
                        *, image_shape=(32, 32, 3)) -> Optional[Dict[str,
                                                                     int]]:
    """``{recipe layer path: forward flops per image}`` by tracing the
    UNSHARDED forward at batch 1 and matching its conv/dot equations
    positionally to the recipe — valid exactly when the counts align
    (deepnn: 4 convs + 2 dots = 6 recipe layers, in network order).
    Returns None when they don't (a model whose recipe doesn't map 1:1
    onto heavy ops gets no cost column rather than a wrong one)."""
    import jax
    import jax.numpy as jnp

    from .jaxpr_audit import iter_eqns

    def _sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.result_type(x)), tree)

    x = jax.ShapeDtypeStruct((1,) + tuple(image_shape), jnp.float32)
    closed = jax.make_jaxpr(
        lambda p, s, im: model.apply(p, s, im, train=False)[0])(
            _sds(params), _sds(batch_stats), x)
    heavy = [e for e in iter_eqns(closed.jaxpr)
             if e.primitive.name in ("conv_general_dilated", "dot_general")]
    if len(heavy) != len(plan.layers):
        return None
    out: Dict[str, int] = {}
    for (path, _style), eqn in zip(plan.layers, heavy):
        out[path] = (_conv_flops(eqn)
                     if eqn.primitive.name == "conv_general_dilated"
                     else _dot_flops(eqn))
    return out


# ---------------------------------------------------------------------------
# Budgets: BUDGETS.json make / check.
# ---------------------------------------------------------------------------

def make_budgets(table: Dict[str, dict], model: str,
                 mesh_shape: Tuple[int, int],
                 tolerance_pct: float = DEFAULT_TOLERANCE_PCT) -> dict:
    """The BUDGETS.json document for one (model, mesh) audit: the current
    per-program metrics become the ceilings future runs diff against."""
    return {
        "model": model,
        "mesh_shape": list(mesh_shape),
        "tolerance_pct": tolerance_pct,
        "programs": {
            name: {m: int(row[m]) for m in BUDGET_METRICS if m in row}
            for name, row in sorted(table.items())},
    }


def check_budgets(table: Dict[str, dict], budgets: dict, model: str,
                  mesh_shape: Tuple[int, int],
                  partial: bool = False) -> List[Finding]:
    """Diff a fresh cost table against a budget file.

    Applicability first: budgets are per (model, mesh shape); a run on a
    different model or mesh gets one ``info`` finding and no gate (the
    numbers aren't comparable).  Then, per budgeted program x metric: a
    value past ``budget * (1 + tolerance_pct/100)`` is an ``error`` (the
    CI regression gate); a program missing on either side is a
    ``warning`` pointing at ``--write-budgets`` re-baselining —
    suppressed under ``partial`` (a ``--programs`` subset run legally
    builds only part of the registry)."""
    out: List[Finding] = []
    b_model = budgets.get("model")
    b_mesh = list(budgets.get("mesh_shape") or ())
    if b_model != model or b_mesh != list(mesh_shape):
        return [make_finding(
            "info", "budget", "budgets",
            f"budget file is for {b_model!r} on mesh {b_mesh}, this audit "
            f"is {model!r} on {list(mesh_shape)} — budget gate skipped "
            "(not comparable)")]
    tol = float(budgets.get("tolerance_pct", DEFAULT_TOLERANCE_PCT))
    programs = budgets.get("programs", {})
    for name, brow in sorted(programs.items()):
        row = table.get(name)
        if row is None:
            if not partial:
                out.append(make_finding(
                    "warning", "budget", name,
                    "budgeted program was not built in this audit — "
                    "stale budget entry; re-baseline with "
                    "--write-budgets"))
            continue
        for metric in BUDGET_METRICS:
            if metric not in brow or metric not in row:
                continue
            cur, limit = int(row[metric]), int(brow[metric])
            ceiling = limit * (1.0 + tol / 100.0)
            if cur > ceiling:
                pct = 100.0 * (cur - limit) / max(limit, 1)
                out.append(make_finding(
                    "error", "budget", name,
                    f"{metric} {_fmt(cur)} exceeds budget {_fmt(limit)} "
                    f"by {pct:.1f}% (tolerance {tol:.0f}%) — an intended "
                    "cost change must re-baseline BUDGETS.json with "
                    "--write-budgets; an unintended one is a regression"))
    for name in sorted(set(table) - set(programs)):
        out.append(make_finding(
            "warning", "budget", name,
            "program has no budget entry — add one with --write-budgets"))
    return out
