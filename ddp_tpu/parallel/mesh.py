"""Device mesh + shardings — the TPU-native data-parallel substrate.

The reference's entire distribution layer (NCCL process group at
multigpu.py:24-33, ``DDP(model, device_ids=[gpu_id])`` at multigpu.py:89,
one process per GPU via ``mp.spawn`` at multigpu.py:262-263) collapses here
into a 1-D ``jax.sharding.Mesh`` over all chips plus two ``NamedSharding``s:
batches split along the ``data`` axis, params/optimizer state replicated.
XLA lowers the gradient ``pmean`` inside the jitted train step to an
all-reduce over ICI (DCN across slices) — there is no NCCL-like library to
manage and no per-rank process fan-out; one process per *host* drives all
its local chips SPMD.

The default mesh stays 1-D for parity with the reference (DP is the only
parallelism it has — SURVEY.md §2 checklist); ``make_mesh(shape=(d, m))``
adds the promised second ``model`` axis (tensor-model parallelism,
ddp_tpu/parallel/tp/) without touching any 1-D caller: batches stay split
along ``data`` only (replicated over ``model``), and the per-leaf parameter
shardings come from the tp planner's PartitionSpecs rather than blanket
replication.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
STAGE_AXIS = "stage"

_AXIS_ORDER = (DATA_AXIS, MODEL_AXIS, STAGE_AXIS)


def _check_shape(shape) -> Tuple[int, ...]:
    """Validate a requested (d[, m[, s]]) mesh shape; returns it as ints.

    The three named axes, in fixed order, are ``data`` (batch shards),
    ``model`` (tensor-parallel) and ``stage`` (pipeline-parallel); errors
    name all three so a malformed ``--mesh_shape`` points straight at the
    contract rather than at an unpacking traceback."""
    dims = tuple(shape)
    if not 1 <= len(dims) <= 3:
        raise ValueError(
            f"mesh shape wants 1-3 axes (data[, model[, stage]]), got "
            f"{len(dims)} entries: {shape!r}")
    try:
        dims = tuple(int(v) for v in dims)
    except (TypeError, ValueError):
        raise ValueError(
            f"mesh shape entries must be integers "
            f"(data[, model[, stage]]), got {shape!r}") from None
    if any(v < 1 for v in dims):
        raise ValueError(
            f"mesh shape axes (data, model, stage) must all be positive, "
            f"got {shape!r}")
    return dims


def make_mesh(num_devices: Optional[int] = None,
              devices: Optional[list] = None,
              shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Device mesh: 1-D data-parallel by default, 2-D (data × model) or
    3-D (data × model × stage) on request.

    ``make_mesh(1)`` is the singlegpu.py path, ``make_mesh()`` the
    multigpu.py path — the reference's one structural diff (SURVEY.md §1)
    expressed as a mesh shape.  ``make_mesh(shape=(d, m))`` builds the
    tensor-parallel 2-D mesh with named ``(data, model)`` axes over the
    first ``d*m`` devices; ``shape=(d, 1)`` is a genuine 2-D mesh (the
    tp code paths run, trivially) — the 1-D default is untouched.
    ``shape=(d, m, s)`` with s>1 grows the third ``stage`` axis for
    pipeline parallelism (parallel/pp/); ``(d, m, 1)`` collapses to the
    identical 2-D mesh so a trailing-1 stage axis is bit-compatible with
    the tp path by construction.
    """
    if devices is None:
        devices = jax.devices()
    if shape is not None:
        if num_devices is not None:
            raise ValueError("pass num_devices or shape, not both")
        dims = _check_shape(shape)
        if len(dims) == 1:
            return make_mesh(num_devices=dims[0], devices=devices)
        if len(dims) == 3 and dims[2] == 1:
            dims = dims[:2]  # (d, m, 1) IS the 2-D mesh — bit-compat anchor
        n = int(np.prod(dims))
        if n > len(devices):
            raise ValueError(
                f"mesh shape {'x'.join(map(str, dims))} "
                f"(data x model{' x stage' if len(dims) == 3 else ''}) "
                f"needs {n} devices, have {len(devices)}")
        return Mesh(np.asarray(devices[:n]).reshape(dims),
                    _AXIS_ORDER[:len(dims)])
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def abstract_mesh(shape: Tuple[int, ...]):
    """A deviceless ``(data, model[, stage])`` AbstractMesh — the auto-plan
    search's substrate (parallel/tp/autoplan.py): ``jax.make_jaxpr`` traces
    the REAL step builders against it for ANY mesh shape, so a laptop/CI
    CPU box can price v4-128 layouts without owning a single chip.  Only
    tracing works on it — no ``device_put``, no execution."""
    dims = _check_shape(shape)
    if len(dims) == 1:
        dims = (dims[0], 1)
    if len(dims) == 3 and dims[2] == 1:
        dims = dims[:2]
    return jax.sharding.AbstractMesh(dims, _AXIS_ORDER[:len(dims)])


def mesh_size(mesh) -> int:
    """Total device count of a mesh, via its axis extents — unlike
    ``mesh.devices.size`` this also works on a deviceless
    :func:`abstract_mesh`."""
    return int(np.prod([int(v) for v in dict(mesh.shape).values()]))


def data_axis_size(mesh: Mesh) -> int:
    """Number of batch shards — the ``data`` axis extent.  THE divisor for
    every piece of batch math: on a 2-D mesh the batch is split over
    ``data`` only (replicated over ``model``), so ``mesh.devices.size``
    overcounts by the model-axis factor."""
    return int(dict(mesh.shape).get(DATA_AXIS, 1))


def model_axis_size(mesh: Mesh) -> int:
    """Model-axis extent (1 on the default 1-D mesh)."""
    return int(dict(mesh.shape).get(MODEL_AXIS, 1))


def stage_axis_size(mesh: Mesh) -> int:
    """Stage-axis extent (1 on 1-D/2-D meshes — no pipeline)."""
    return int(dict(mesh.shape).get(STAGE_AXIS, 1))


_SCAN_UNROLL_CAP = 32


def scan_unroll(mesh: Optional[Mesh] = None, length: Optional[int] = None):
    """Unroll factor for ``lax.scan`` loops whose body contains model
    compute (epoch scans, micro-batch accumulation): full unroll on the
    CPU backend for short scans, rolled scan everywhere else.

    XLA:CPU compiles convolutions inside while-loop bodies to a naive
    serial fallback instead of its fast runtime kernels: the identical
    8-step DeepNN train epoch measured 20.7 s rolled vs 0.6 s fully
    unrolled on this image's jaxlib (and the unrolled program also
    *compiles* 5x faster, 4.9 s vs 25.6 s — compiling conv-in-loop is
    itself pathological).  Only a full unroll helps; ``unroll=4`` still
    leaves a while loop and stays slow.  The CPU backend normally runs
    the virtual-device test mesh and the driver's multi-chip dryrun,
    whose epochs are a few steps; ``length`` (the static scan length,
    known at trace time) caps the policy so a genuinely long CPU scan —
    a real 98-step CIFAR epoch on a CPU-only box — keeps the rolled
    program instead of compiling 98 inlined fwd+bwd bodies.  On TPU the
    rolled scan is always right: compile time stays independent of epoch
    length.
    """
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    if platform != "cpu":
        return 1
    if length is not None and length > _SCAN_UNROLL_CAP:
        return 1
    return True


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (batch) axis split across ``data`` — the analogue of
    ``DistributedSampler`` handing each rank its shard (multigpu.py:153)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated — params/opt-state, like DDP's per-rank replicas
    kept in lockstep (multigpu.py:89, 97)."""
    return NamedSharding(mesh, P())


def local_replica_ids(mesh: Mesh) -> list:
    """``data``-axis positions of THIS process's devices, in mesh order —
    the replica ids this process feeds (loaders' ``local_replicas``) and
    the one definition the per-process assembly order hangs on
    (:func:`assemble_from_local` assumes ascending mesh order).  Asymmetric
    topologies make the blocks unequal, so every consumer must derive
    them from the mesh like this rather than from range arithmetic on a
    uniform per-host count.

    On a 2-D (data × model) mesh a "replica" is a data-axis ROW (its
    ``model``-axis devices all consume the same batch shard), so the ids
    are the distinct data coordinates this process owns devices in — NOT
    flat device positions, which would overcount by the model-axis factor
    (the regression tests/test_tp.py pins)."""
    pid = jax.process_index()
    if mesh.devices.ndim == 1:
        return [i for i, d in enumerate(mesh.devices.flat)
                if d.process_index == pid]
    data_dim = mesh.axis_names.index(DATA_AXIS)
    rows = np.moveaxis(mesh.devices, data_dim, 0)
    return [i for i in range(rows.shape[0])
            if any(d.process_index == pid for d in rows[i].flat)]


def assemble_from_local(sharding: NamedSharding, v, axis: int) -> jax.Array:
    """``jax.make_array_from_process_local_data`` with the global shape made
    EXPLICIT along the sharded ``axis``: the library's inference assumes
    every process contributes equal-sized blocks and fails on asymmetric
    host->replica topologies (e.g. a 2/1/1 split of a 4-device mesh),
    which real pods can have even though the reference's mp.spawn fan-out
    never does (multigpu.py:262-263).  Each of this process's addressable
    mesh devices holds the same per-replica extent, so the global extent is
    ``local_extent / n_local * n_total``.

    Shard counts are AXIS-AWARE: they come from the spec's entry for
    ``axis`` (distinct shard positions along the mesh axes that actually
    split it), not from raw device counts — on a 2-D (data × model) mesh a
    ``P(data)`` batch is replicated over ``model``, so counting devices
    would inflate both the local and the global block count by the
    model-axis factor (regression-pinned in tests/test_tp.py)."""
    if len(sharding.addressable_devices) == 0:
        raise ValueError(
            f"process {jax.process_index()} owns no devices of this mesh; "
            "it cannot contribute process-local data (every participating "
            "process must hold at least one mesh device)")
    mesh = sharding.mesh
    entry = sharding.spec[axis] if axis < len(sharding.spec) else None
    names = ((entry,) if isinstance(entry, str) else tuple(entry or ()))
    dims = [mesh.axis_names.index(n) for n in names]
    shape_d = dict(mesh.shape)
    n_total = int(np.prod([shape_d[n] for n in names])) if names else 1
    pid = jax.process_index()
    local = {tuple(np.asarray(pos)[dims])
             for pos in np.ndindex(mesh.devices.shape)
             if mesh.devices[pos].process_index == pid}
    n_local = len(local)
    shape = list(v.shape)
    if shape[axis] % n_local:
        raise ValueError(
            f"process-local extent {shape[axis]} along axis {axis} is not "
            f"divisible by this process's {n_local} mesh devices — each "
            "local device must hold an equal block")
    shape[axis] = shape[axis] // n_local * n_total
    return jax.make_array_from_process_local_data(sharding, v, tuple(shape))


def process_min_mib(mesh: Mesh, value_bytes: Optional[int]) -> Optional[int]:
    """Global minimum byte count over processes, asymmetric-topology-safe;
    ``None`` anywhere (or everywhere) means "no limit" and wins.

    ``multihost_utils.process_allgather`` reshapes ``jax.devices()`` into
    ``(process_count, local_device_count)`` and so breaks on unequal
    per-host device counts; this instead places each process's value on its
    own mesh devices and jit-reduces with a replicated output every process
    can read.  The value crosses the device in MiB, not bytes: without
    x64 enabled JAX canonicalizes int64 to int32, where real HBM byte
    capacities (2^34...) overflow — 16 GiB wraps to exactly 0 — while MiB
    counts stay int32-exact up to 2 TiB.  Returns ceil-MiB bytes: the
    guard's comparison tolerance is far coarser than 1 MiB either way, but
    flooring would turn a reported sub-MiB capacity into 0 bytes and flip
    the resident-HBM guard from advisory into an unconditional error
    (unreachable for real HBM sizes; ADVICE r4).

    Every participating process must own at least one mesh device — a
    deviceless process cannot contribute to (or read) the collective and
    gets :func:`assemble_from_local`'s explicit error; such topologies
    are unsupported throughout (an SPMD program over the mesh has no
    work for that process)."""
    import jax.numpy as jnp
    mib = -1 if value_bytes is None else -(-value_bytes // 2 ** 20)
    vals = assemble_from_local(
        batch_sharding(mesh),
        np.full(len(local_replica_ids(mesh)), mib, np.int32), 0)
    gmin = int(jax.jit(jnp.min,
                       out_shardings=replicated_sharding(mesh))(vals))
    return None if gmin < 0 else gmin * 2 ** 20


def local_batch_slice(global_batch: int, mesh: Mesh) -> int:
    """Per-host slice of a global batch (multi-host data feeding).

    Batch math uses the ``data`` axis size ONLY: on a 2-D (data × model)
    mesh the batch is split over ``data`` and replicated over ``model``,
    so dividing by the raw device count would shrink every shard by the
    model-axis factor (and reject batches a (2,4) mesh handles fine —
    the regression tests/test_tp.py pins both)."""
    n_shards = data_axis_size(mesh)
    if global_batch % n_shards:
        raise ValueError(
            f"global batch {global_batch} not divisible by the mesh's "
            f"{n_shards}-way data axis")
    per_shard = global_batch // n_shards
    return per_shard * len(local_replica_ids(mesh))
