"""Batched row gather — the resident data path's hot op, as a Pallas kernel.

``table[idx]`` for a [M, ...] uint8 dataset table is the core of the
HBM-resident input path (train/epoch.py): every step gathers its batch by
index from the resident array.  XLA:TPU lowers that advanced-indexing
gather to a generic per-row gather; this kernel instead drives one DMA per
row through the Pallas pipeline with scalar-prefetched indices (the
index_map reads ``idx`` before the body runs, so block fetches
double-buffer).  What either form costs on the chip: not measured on this
stack.

Non-TPU backends (the CPU test mesh) use the plain XLA gather — identical
values, so every numerical test covers both paths' semantics.  On TPU the
kernel either compiles or the run fails; ``python -m ddp_tpu.ops.gather``
checks it against ``table[idx]`` on whatever devices the process sees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_LANE = 128


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _copy_kernel(idx_ref, in_ref, out_ref):
    del idx_ref  # consumed by the index_map, not the body
    out_ref[...] = in_ref[...]


def _pallas_row_gather(table2d: jax.Array, idx: jax.Array) -> jax.Array:
    """[M, D] (D % 128 == 0), int32 [N] -> [N, D] == table2d[idx]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = table2d.shape
    n = idx.shape[0]
    sub = d // _LANE
    t3 = table2d.reshape(m, sub, _LANE)
    # Block (1, sub, LANE): the last two dims equal the array dims, which
    # satisfies the Mosaic block-shape constraint for any D % 128 == 0.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, sub, _LANE),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, sub, _LANE),
                               lambda i, idx_ref: (i, 0, 0)),
    )
    # Inside shard_map (check_vma=True) the output's varying-axes type must
    # be declared: the gathered rows vary wherever the indices or the table
    # do (the idx matrix is sharded on ``data``; the table is replicated).
    vma = frozenset(jax.typeof(idx).vma) | frozenset(jax.typeof(table2d).vma)
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, sub, _LANE), table2d.dtype,
                                       vma=vma),
    )(idx, t3)
    return out.reshape(n, d)


def gather_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` along axis 0, via the Pallas DMA kernel when the row
    byte-count allows (TPU, row size a multiple of 128 elements), else the
    plain XLA gather.  Values are identical either way."""
    n = idx.shape[0]
    row_shape = table.shape[1:]
    d = 1
    for s in row_shape:
        d *= s
    if _use_pallas() and d % _LANE == 0:
        # Clamp like XLA's gather does: an out-of-range block index in the
        # Pallas index_map would be undefined behaviour (OOB DMA), not the
        # clamped read the fallback path gives.
        idx = jnp.clip(idx.astype(jnp.int32), 0, table.shape[0] - 1)
        flat = _pallas_row_gather(table.reshape(table.shape[0], d), idx)
        return flat.reshape((n,) + row_shape)
    return table[idx]


def _self_check() -> None:
    """``python -m ddp_tpu.ops.gather``: the compiled gather against
    ``table[idx]`` on one device and inside ``shard_map`` over every
    visible device, at the resident path's shapes (uint8 CIFAR rows).
    Raises on any mismatch; on TPU also if the program holds no Mosaic
    kernel (i.e. it silently took the XLA gather)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    mesh = make_mesh()
    print(device_line(mesh), flush=True)
    n_dev = mesh.devices.size
    rng = np.random.default_rng(0)
    table = rng.integers(0, 256, (2048, 32, 32, 3), dtype=np.uint8)
    idx = rng.integers(0, 2048, 128 * n_dev).astype(np.int32)

    single = jax.jit(gather_rows)
    np.testing.assert_array_equal(
        np.asarray(single(table, idx[:128])), table[idx[:128]])
    sharded = jax.jit(jax.shard_map(
        gather_rows, mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS)))
    t_rep = jax.device_put(table, NamedSharding(mesh, P()))
    i_sh = jax.device_put(idx, NamedSharding(mesh, P(DATA_AXIS)))
    np.testing.assert_array_equal(np.asarray(sharded(t_rep, i_sh)),
                                  table[idx])
    pallas = _use_pallas()
    if pallas:
        for fn, args in ((single, (table, idx[:128])),
                         (sharded, (t_rep, i_sh))):
            if "tpu_custom_call" not in fn.lower(*args).compile().as_text():
                raise RuntimeError("gather_rows compiled without the "
                                   "Pallas kernel on TPU")
    print(f"gather: ok kernel={'pallas' if pallas else 'xla'} single=128 rows "
          f"shard_map={n_dev}x128 rows == table[idx]", flush=True)


if __name__ == "__main__":
    _self_check()
