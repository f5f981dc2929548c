"""Static candidate pricing for the auto-sharding search.

The glue between the search engine (parallel/tp/autoplan.py) and this
package's static-analysis stack: a candidate = (mesh shape, per-layer
recipe, ZeRO on/off), and pricing one means

1. tracing the REAL train-step builder (train/step.py / train/zero.py)
   for that candidate on a deviceless :func:`~ddp_tpu.parallel.mesh.
   abstract_mesh` — ``jax.make_jaxpr`` over abstract state, so a CPU box
   explores v4-128 shapes without owning a chip and without one XLA
   compile;
2. pricing the traced jaxpr through the counted cost model
   (``costmodel``) with the CALIBRATED per-op-class coefficients
   (``bench.py --calibrate_cost``) — the same additive no-overlap model
   the efficiency ledger audits against measurement (obs/ledger.py), so
   the search optimizes a quantity the runtime continuously checks;
3. reading the donation-aware liveness walk (``liveness``) for the
   per-shard peak-HBM estimate — the search's memory-budget pruning
   signal;
4. running the jaxpr collective auditor (``jaxpr_audit``) against the
   candidate plan's ``expected_collectives`` arithmetic — a candidate
   whose traced program violates its own plan's invariants is pruned,
   never emitted.

The prediction prices ONE shard's body (the cost model's unit).  All
candidates in a search share the same total device budget, so per-shard
cost ranks them exactly as per-step wall-clock does on a real pod; on a
virtual CPU mesh the shards serialize, scaling every candidate by the
same factor — the ranking survives (measured ~= n_dev x predicted,
BENCH_r12's ledger ``pred_scale``).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

COEFFICIENT_KEYS = ("conv_s_per_flop", "dot_s_per_flop",
                    "elementwise_s_per_byte",
                    "collective_s_per_payload_byte")


def coefficients_from(doc: dict) -> Dict[str, float]:
    """Extract the four calibrated coefficients from any carrier: a
    ``--calibrate_cost`` record, an auto-plan doc (both nest them under
    ``"coefficients"``), or a bare coefficient mapping."""
    coeffs = doc.get("coefficients", doc)
    missing = [k for k in COEFFICIENT_KEYS if k not in coeffs]
    if missing:
        raise ValueError(
            f"coefficient source is missing {missing}; expected the "
            f"keys {list(COEFFICIENT_KEYS)} (a bench.py --calibrate_cost "
            "record, an auto-plan JSON, or a bare mapping)")
    return {k: float(coeffs[k]) for k in COEFFICIENT_KEYS}


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def _abstract_state(params, stats, mesh_shape, *, zero: bool, plan):
    """The candidate step's ``TrainState`` as ShapeDtypeStructs — the
    ZeRO momentum layouts rebuilt abstractly, because the real
    constructors (train/zero.py:init_opt_shard) materialise device
    arrays a deviceless mesh cannot hold."""
    from ..optim import sgd as sgd_lib
    from ..train.step import TrainState, init_train_state
    if not zero:
        return jax.eval_shape(init_train_state, params, stats)
    d, m = mesh_shape
    if plan is not None:
        from ..parallel.tp.plan import local_param_count
        n = local_param_count(plan)
        n_pad = n + (-n) % d
        mom = jax.ShapeDtypeStruct((plan.model_size, n_pad), jnp.float32)
    else:
        from ..train.zero import padded_size
        n_pad = padded_size(params, d * m)
        mom = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
    return TrainState(params=_sds(params), batch_stats=_sds(stats),
                      opt_state=sgd_lib.SGDState(mom),
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def trace_candidate(model_name: str, mesh_shape: Tuple[int, int], *,
                    recipe: Optional[Dict[str, str]] = None,
                    stem: Optional[str] = None, zero: bool = False,
                    global_batch: int = 32, input_hw=(32, 32, 3)):
    """Trace the real train step for one candidate on an abstract mesh.

    Returns ``(closed_jaxpr, plan)`` where ``plan`` is ``None`` for the
    pure data-parallel program (no recipe at m=1, or a trivial
    all-replicated recipe — train/step.py wires the plain core for those
    anyway, so pricing the plain program is pricing the truth).

    Raises ``ValueError`` for an infeasible candidate — a sharded
    dimension that does not divide the model axis (tp/plan.py's
    divisibility rules) or a batch that does not divide the data axis.
    """
    from ..models import get_model
    from ..parallel.mesh import abstract_mesh
    from ..parallel.tp.plan import is_trivial, plan_for_model
    from .jaxpr_audit import trace_jaxpr
    d, m = int(mesh_shape[0]), int(mesh_shape[1])
    if global_batch % d:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"the {d}-way data axis")
    model = get_model(model_name)
    params, stats = jax.eval_shape(model.init, jax.random.key(0))
    plan = None
    if recipe is not None:
        plan = plan_for_model(model_name, params, stats, model_size=m,
                              recipe=recipe, stem=stem)
        if is_trivial(plan):
            plan = None
    elif m > 1:
        plan = plan_for_model(model_name, params, stats, model_size=m)
    mesh = abstract_mesh((d, m))
    from ..optim import SGDConfig, triangular_lr
    cfg = SGDConfig(lr=0.1)
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                              steps_per_epoch=4)
    from ..train.step import make_train_step
    fn = make_train_step(model, cfg, sched, mesh, plan=plan,
                         shard_update=zero)
    state = _abstract_state(params, stats, (d, m), zero=zero, plan=plan)
    batch = {"image": jax.ShapeDtypeStruct((global_batch,) + tuple(input_hw),
                                           jnp.uint8),
             "label": jax.ShapeDtypeStruct((global_batch,), jnp.int32)}
    rng = _sds(jax.random.key(0))
    return trace_jaxpr(fn, (state, batch, rng)), plan


def price_closed(closed, coefficients: Dict[str, float]) -> dict:
    """One traced program -> the search objective row: additive
    predicted ms (per shard) plus the raw static metrics the budget gate
    and the memory pruning read."""
    from .costmodel import program_cost
    from .liveness import liveness_of
    cost = program_cost(closed)
    live = liveness_of(closed)
    pred_s = (cost.by_class["conv"] * coefficients["conv_s_per_flop"]
              + cost.by_class["dot"] * coefficients["dot_s_per_flop"]
              + cost.bytes * coefficients["elementwise_s_per_byte"]
              + cost.collective_payload_bytes
              * coefficients["collective_s_per_payload_byte"])
    return {
        "predicted_ms": round(pred_s * 1e3, 6),
        "flops": int(cost.flops),
        "bytes": int(cost.bytes),
        "collective_payload_bytes": int(cost.collective_payload_bytes),
        "peak_live_bytes": int(live["peak_live_bytes"]),
    }


def audit_candidate(name: str, closed, *, plan, zero: bool) -> List[str]:
    """The strict collective auditor on one candidate trace: the plan's
    ``expected_collectives`` arithmetic, the axis whitelist, the ZeRO
    pair — exactly what ``python -m ddp_tpu.analysis --strict`` enforces
    on registered programs.  Returns the error details (empty = clean);
    the search prunes any candidate with a non-empty list."""
    from .jaxpr_audit import audit_collectives, collective_inventory
    inv = collective_inventory(closed)
    findings = audit_collectives(name, "update", inv, plan=plan, zero=zero)
    return [f"{f.check}: {f.detail}" for f in findings
            if f.severity == "error"]


def model_flops_per_step(model_name: str, global_batch: int = 32,
                         input_hw=(32, 32, 3)) -> Optional[int]:
    """Counted-jaxpr FLOPs of ONE unsharded train step at
    ``global_batch`` rows — the numerator MFU reporting shares with the
    search (obs/live.py).  ``None`` when the model cannot be traced."""
    try:
        closed, _ = trace_candidate(model_name, (1, 1),
                                    global_batch=global_batch,
                                    input_hw=input_hw)
        from .costmodel import program_cost
        return int(program_cost(closed).flops)
    except Exception:  # noqa: BLE001 — reporting-only, never fatal
        return None
