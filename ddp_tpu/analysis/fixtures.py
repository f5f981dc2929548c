"""Seeded-faulty fixtures — one per detector, each a known-bad program or
source text the matching auditor MUST flag (and the head registry must
not).  They are the auditor's own regression suite: ``python -m
ddp_tpu.analysis --fixture <name>`` exits nonzero under ``--strict`` for
every name here, and tests/test_analysis.py pins each detector to its
fixture so a refactor that silently blinds a check fails CI.

The jaxpr fixtures trace tiny hand-written shard_map programs (the same
``jax.shard_map``/``make_jaxpr`` path the registry uses) on the
(2, 4) = data x model virtual mesh; the source-text fixtures are inline
Python the AST passes scan.  Nothing here executes on a device.
"""
from __future__ import annotations

import textwrap
from typing import Callable, Dict, List

from .findings import Finding

_MESH_2D = (2, 4)


def _mesh():
    from ..parallel.mesh import make_mesh
    return make_mesh(shape=_MESH_2D)


def _trace(fn, *args):
    import jax
    return jax.make_jaxpr(fn)(*args)


# ---------------------------------------------------------------------------
# jaxpr fixtures
# ---------------------------------------------------------------------------

def wrong_axis_psum() -> List[Finding]:
    """An 'update' whose gradient reduction lands on ``model`` instead of
    ``data`` — each data shard trains on its local batch only."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
    from .jaxpr_audit import audit_collectives, collective_inventory

    mesh = _mesh()

    def _body(w, x):
        g = jnp.mean(x, axis=0) * w
        return w - 0.1 * lax.psum(g, MODEL_AXIS)       # wrong axis

    # check_vma=False, as the tensor-parallel steps are built: with the
    # check on, jax's own vma typing rejects this program at trace time.
    fn = jax.jit(jax.shard_map(
        _body, mesh=mesh, in_specs=(P(), P(DATA_AXIS)), out_specs=P(),
        check_vma=False))
    w = jax.ShapeDtypeStruct((16,), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    inv = collective_inventory(_trace(fn, w, x))
    return audit_collectives("fixture:wrong_axis_psum", "update", inv)


def model_axis_all_gather() -> List[Finding]:
    """A hot-path ``all_gather`` over ``model`` — rematerializes the
    sharded weights every step, the cliff TP exists to avoid."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
    from .jaxpr_audit import audit_collectives, collective_inventory

    mesh = _mesh()

    def _body(w, x):
        full_w = lax.all_gather(w, MODEL_AXIS, tiled=True)  # the gather
        loss = jnp.sum(x @ full_w)
        return w - 0.1 * lax.psum(loss, DATA_AXIS) * jnp.ones_like(w)

    fn = jax.jit(jax.shard_map(
        _body, mesh=mesh, in_specs=(P(MODEL_AXIS), P(DATA_AXIS)),
        out_specs=P(MODEL_AXIS)))
    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    inv = collective_inventory(_trace(fn, w, x))
    return audit_collectives("fixture:model_axis_all_gather", "update", inv)


def captured_constant() -> List[Finding]:
    """An ~8 MiB array closed over instead of passed as an argument —
    baked into every executable, never donatable or shardable."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .jaxpr_audit import audit_constants

    table = jnp.asarray(np.ones((1024, 2048), np.float32))   # 8 MiB

    def _body(x):
        return x @ table

    x = jax.ShapeDtypeStruct((4, 1024), jnp.float32)
    return audit_constants("fixture:captured_constant", _trace(_body, x))


def missing_donation() -> List[Finding]:
    """An update step whose 4 MiB state buffer is not donated — the step
    permanently holds a dead second copy of the state in HBM."""
    import jax
    import jax.numpy as jnp

    from .jaxpr_audit import audit_donation

    def _body(w, g):
        return w - 0.1 * g

    fn = jax.jit(_body)      # donate_argnums deliberately absent
    w = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)      # 4 MiB
    g = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    return audit_donation("fixture:missing_donation", "update", fn, (w, g))


def budget_buster() -> List[Finding]:
    """A program ~30,000x over its flop budget — the cost-regression
    gate (``costmodel.check_budgets``) must flag it."""
    import jax
    import jax.numpy as jnp

    from .costmodel import check_budgets, program_cost

    def _body(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cost = program_cost(_trace(_body, a, b))     # 2*256^3 = 33.6 MFLOP
    table = {"fixture_matmul": cost.budget_row()}
    budgets = {"model": "fixture", "mesh_shape": [1, 1],
               "tolerance_pct": 10.0,
               "programs": {"fixture_matmul": {"flops": 1000}}}
    return check_budgets(table, budgets, "fixture", (1, 1))


def scalar_closure() -> List[Finding]:
    """A strongly-typed np hyperparameter closed into the program — it
    retraces per distinct value (warning-level: slow, not wrong).  Shape
    (1,) rather than 0-d because jax inlines literalable 0-d scalars;
    the np-wrapped-hyperparameter habit is what the check targets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .jaxpr_audit import audit_constants

    lr = np.full((1,), 0.1, np.float32)

    def _body(w):
        return w * (1.0 - lr)

    w = jax.ShapeDtypeStruct((8,), jnp.float32)
    return audit_constants("fixture:scalar_closure", _trace(_body, w))


# ---------------------------------------------------------------------------
# source-text fixtures
# ---------------------------------------------------------------------------

_HOT_LOOP_DEVICE_GET = textwrap.dedent("""\
    import jax

    def run_epoch(trainer, batches):
        losses = []
        for batch in batches:
            state, loss = trainer.train_step(trainer.state, batch)
            losses.append(float(loss))        # implicit per-step sync
            host = jax.device_get(state)      # explicit per-step sync
        return losses, host
    """)


def hot_loop_device_get() -> List[Finding]:
    """``jax.device_get`` (and a ``float()`` on the step's loss) inside
    the epoch loop — one device->host round trip per iteration."""
    from .hostsync import scan_source
    return scan_source("fixture:hot_loop_device_get.py",
                       _HOT_LOOP_DEVICE_GET)


_LOCK_FREE_SHARED_ATTR = textwrap.dedent("""\
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0                    # shared, never guarded
            self._thread = threading.Thread(target=self._run, daemon=True)

        def _run(self):
            while True:
                self.count += 1               # worker-side write, no lock

        def snapshot(self):
            return self.count                 # caller-side read, no lock
    """)


def lock_free_shared_attr() -> List[Finding]:
    """A counter mutated by the spawned thread and read by the caller
    with no lock and no annotation — the data-race shape the lockset
    lint exists to catch."""
    from .lockset import lint_source
    return lint_source("fixture:lock_free_shared_attr.py",
                       _LOCK_FREE_SHARED_ATTR)


_RANK_GATED_COLLECTIVE = textwrap.dedent("""\
    import jax
    from jax import lax

    def flush_epoch(stats):
        if jax.process_index() == 0:      # host-local rank check
            return lax.psum(stats, "data")
        return stats
    """)


def rank_gated_collective() -> List[Finding]:
    """A ``psum`` only rank 0 reaches — the other hosts never enter the
    collective and the pod hangs; the divergence lint's canonical
    finding."""
    from .divergence import scan_source
    return scan_source("fixture:rank_gated_collective.py",
                       _RANK_GATED_COLLECTIVE)


# ---------------------------------------------------------------------------

FIXTURES: Dict[str, Callable[[], List[Finding]]] = {
    "wrong_axis_psum": wrong_axis_psum,
    "model_axis_all_gather": model_axis_all_gather,
    "captured_constant": captured_constant,
    "missing_donation": missing_donation,
    "hot_loop_device_get": hot_loop_device_get,
    "lock_free_shared_attr": lock_free_shared_attr,
    "budget_buster": budget_buster,
    "rank_gated_collective": rank_gated_collective,
    "scalar_closure": scalar_closure,
}

# Every fixture a --strict run must fail on (scalar_closure is the one
# deliberate warning-severity fixture: reported, not fatal).
ERROR_FIXTURES = tuple(n for n in FIXTURES if n != "scalar_closure")


def fixture_names() -> List[str]:
    return list(FIXTURES)


def run_fixture(name: str) -> List[Finding]:
    if name not in FIXTURES:
        raise ValueError(f"unknown fixture {name!r}; "
                         f"have {fixture_names()}")
    return FIXTURES[name]()
