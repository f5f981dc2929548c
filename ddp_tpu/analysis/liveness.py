"""Donation-aware buffer-liveness: a static peak-live-bytes estimate per
traced program.

The claim "TP shards the model ÷m" or "ZeRO drops the optimizer state"
is usually folklore backed by an OOM that did or didn't happen.  This
pass turns it into a number the tests assert: walk the per-shard program
body in equation order, carrying the live-buffer set, and report the
peak.

Mechanics:

- **Find the body.**  A registry program traces as one top-level ``pjit``
  equation wrapping one ``shard_map`` equation wrapping the per-shard
  body.  The walk descends single-equation wrappers, carrying each
  input's DONATED flag through by variable identity — the flags live on
  the ``pjit`` equation's ``donated_invars`` param, exactly what
  ``jax.jit(..., donate_argnums=...)`` recorded at trace time.
- **Linear scan.**  Inputs are live at entry.  At each equation the
  candidate peak is (current live set) + (its outputs) + (its internal
  transient); afterwards every buffer whose last use this was is freed —
  but a NON-donated input can never be freed (the caller still owns it:
  that is precisely what donation buys), and program outputs survive to
  the end.  Unused outputs (including dropped ones) cost their bytes at
  the producing equation only.
- **Internal transients.**  A sub-jaxpr-bearing equation (the nested
  ``pjit`` of a fused layer, a ``scan`` body, a ``custom_vjp`` branch)
  can allocate above its boundary: its transient is
  ``max(0, sub_peak - sub_inputs - sub_outputs)``, computed recursively
  with the sub-inputs pinned (the caller's buffers are already counted).
  ``cond`` takes the worst branch.

The estimate is a lower bound on real HBM (XLA may fuse away transients
— good — or materialize layouts we don't see — bad), but it is ORDER
faithful: the same accounting applied to two programs ranks their memory
appetite, which is what the TP-vs-1D and ZeRO-vs-nonZeRO assertions in
tests/test_analysis.py consume.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from .costmodel import _var_bytes

# Single-equation wrappers the body finder descends through.
_WRAPPER_PRIMITIVES = ("jit", "shard_map", "closed_call", "core_call",
                       "remat", "checkpoint")


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _sub_jaxpr_of(eqn):
    for key in ("jaxpr", "call_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        return sub.jaxpr if hasattr(sub, "jaxpr") else sub
    return None


def find_body(closed_jaxpr) -> Tuple[object, List[bool]]:
    """(per-shard body jaxpr, donated flag per body invar).

    Descends single-equation pjit/shard_map wrappers; a ``pjit``
    equation's ``donated_invars`` ORs into the flags, and flags follow
    variables by identity across each boundary (an inner input is donated
    iff the outer variable feeding it is)."""
    jaxpr = closed_jaxpr.jaxpr
    donated = [False] * len(jaxpr.invars)
    while len(jaxpr.eqns) == 1:
        eqn = jaxpr.eqns[0]
        if eqn.primitive.name not in _WRAPPER_PRIMITIVES:
            break
        inner = _sub_jaxpr_of(eqn)
        if inner is None:
            break
        flag_of = {v: d for v, d in zip(jaxpr.invars, donated)}
        new = []
        pjit_flags = eqn.params.get("donated_invars")
        for i, v in enumerate(eqn.invars):
            d = (not _is_literal(v)) and flag_of.get(v, False)
            if pjit_flags is not None and i < len(pjit_flags):
                d = d or bool(pjit_flags[i])
            new.append(d)
        jaxpr, donated = inner, new
    return jaxpr, donated


def _peak_of(jaxpr, donated: List[bool]) -> int:
    """Peak live bytes of one jaxpr body under the linear-scan rules."""
    n = len(jaxpr.eqns)
    last_use: Dict[object, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i

    live: Dict[object, int] = {}
    for v in list(jaxpr.constvars):
        live[v] = _var_bytes(v)
        last_use[v] = n                      # consts owned by the caller
    for v, d in zip(jaxpr.invars, donated):
        live[v] = _var_bytes(v)
        if not d:
            last_use[v] = n                  # non-donated: never freeable
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = n                  # outputs survive the program

    cur = sum(live.values())
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        out_bytes = sum(_var_bytes(v) for v in eqn.outvars)
        peak = max(peak, cur + out_bytes + _internal_transient(eqn))
        for v in eqn.outvars:
            if not _is_drop(v):
                live[v] = _var_bytes(v)
                cur += live[v]
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_literal(v):
                continue
            if v in live and last_use.get(v, i) <= i:
                cur -= live.pop(v)
    return peak


def _internal_transient(eqn) -> int:
    """Bytes a sub-jaxpr-bearing equation can allocate above its own
    input/output boundary (already counted by the caller)."""
    from .jaxpr_audit import _sub_jaxprs
    subs = list(_sub_jaxprs(eqn.params))
    if not subs:
        return 0
    extras = []
    for sub in subs:
        if hasattr(sub, "jaxpr"):              # ClosedJaxpr -> raw Jaxpr
            sub = sub.jaxpr
        boundary = (sum(_var_bytes(v) for v in sub.invars)
                    + sum(_var_bytes(v) for v in sub.outvars))
        sub_peak = _peak_of(sub, [False] * len(sub.invars))
        extras.append(max(0, sub_peak - boundary))
    if eqn.primitive.name == "cond":
        return max(extras)
    return sum(extras)


def liveness_of(closed_jaxpr) -> dict:
    """The per-program liveness report: ``peak_live_bytes`` plus the
    boundary decomposition (input/donated-input/output bytes) the
    memory-win assertions read.  ``donated_input_bytes`` is the state the
    update owns and recycles — params + momentum, the leaves TP shards ÷m
    — so TP-vs-1D compares it directly."""
    body, donated = find_body(closed_jaxpr)
    input_bytes = sum(_var_bytes(v) for v in body.invars)
    donated_bytes = sum(_var_bytes(v)
                        for v, d in zip(body.invars, donated) if d)
    output_bytes = sum(_var_bytes(v) for v in body.outvars
                       if not _is_literal(v))
    return {
        "peak_live_bytes": int(_peak_of(body, donated)),
        "input_bytes": int(input_bytes),
        "donated_input_bytes": int(donated_bytes),
        "output_bytes": int(output_bytes),
        "body_eqns": len(body.eqns),
    }
