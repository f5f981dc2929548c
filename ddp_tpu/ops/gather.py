"""Batched row gather — the resident data path's hot op, as a Pallas kernel.

``table[idx]`` for a [M, ...] uint8 dataset table is the core of the
HBM-resident input path (train/epoch.py): every step gathers its batch by
index from the resident array.  XLA:TPU lowers that advanced-indexing
gather to a generic per-row gather; this kernel instead drives one DMA per
row through the Pallas pipeline with scalar-prefetched indices (the
index_map reads ``idx`` before the body runs, so block fetches
double-buffer).

The table lives in the kernel's layout.  The kernel's ``BlockSpec`` takes
rows as ``[D/128, 128]`` tiles, and on the TPU's tiled layouts a reshape
of ``[M, 32, 32, 3]`` into ``[M, 24, 128]`` moves every byte of the table;
inside a scanned epoch XLA does not hoist it, so a table stored in its
logical shape was copied whole on every step (87% of the device's time on
a 1.9 GB table: PERF.md section 6).  :class:`RowTable` therefore reshapes
the rows ONCE, on the host where it is a free view, and carries the
logical row shape as static pytree data; :func:`gather_rows` reads the
stored array as it is and reshapes only the gathered batch.

Non-TPU backends (the CPU test mesh) hold the same layout and gather with
``data[idx]`` — identical values, so every numerical test covers both
paths' semantics.  On TPU the kernel either compiles or the run fails;
``python -m ddp_tpu.ops.gather`` checks it against ``table[idx]`` on
whatever devices the process sees, and that no instruction of a scanned
gather writes the whole table.
"""
from __future__ import annotations

import math
import re
from typing import List, Tuple

import jax
import jax.numpy as jnp

_LANE = 128


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@jax.tree_util.register_pytree_node_class
class RowTable:
    """A ``[M, *row_shape]`` table held as the row gather reads it.

    ``data`` is ``[M, D/128, 128]`` where a row's element count ``D`` is a
    multiple of 128 (the Pallas kernel's block), else the rows as they
    came (the XLA gather takes any shape).  One pytree leaf (``data``)
    plus the static ``row_shape``, so a table passes through ``jit``,
    ``.lower``, ``device_put`` and ``shard_map``'s ``P()`` prefix like the
    bare array it replaces.
    """

    def __init__(self, data, row_shape: Tuple[int, ...]):
        self.data = data
        self.row_shape = tuple(row_shape)

    @classmethod
    def from_rows(cls, rows) -> "RowTable":
        """``rows`` ``[M, ...]`` (NumPy: a free view of contiguous rows;
        a device array: one copy, so not inside a loop)."""
        row_shape = tuple(rows.shape[1:])
        d = math.prod(row_shape)
        if d % _LANE == 0:
            rows = rows.reshape(rows.shape[0], d // _LANE, _LANE)
        return cls(rows, row_shape)

    def tree_flatten(self):
        return (self.data,), self.row_shape

    @classmethod
    def tree_unflatten(cls, row_shape, children):
        return cls(children[0], row_shape)


def _copy_kernel(idx_ref, in_ref, out_ref):
    del idx_ref  # consumed by the index_map, not the body
    out_ref[...] = in_ref[...]


def _pallas_row_gather(table3d: jax.Array, idx: jax.Array) -> jax.Array:
    """[M, S, 128], int32 [N] -> [N, S, 128] == table3d[idx]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _m, sub, lane = table3d.shape
    n = idx.shape[0]
    # Block (1, sub, LANE): the last two dims equal the array dims, which
    # satisfies the Mosaic block-shape constraint for any D % 128 == 0.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, sub, lane),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, sub, lane),
                               lambda i, idx_ref: (i, 0, 0)),
    )
    # Inside shard_map (check_vma=True) the output's varying-axes type must
    # be declared: the gathered rows vary wherever the indices or the table
    # do (the idx matrix is sharded on ``data``; the table is replicated).
    vma = frozenset(jax.typeof(idx).vma) | frozenset(jax.typeof(table3d).vma)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, sub, lane), table3d.dtype,
                                       vma=vma),
    )(idx, table3d)


def gather_rows(table: RowTable, idx: jax.Array) -> jax.Array:
    """``rows[idx]`` along axis 0 of the table's logical ``[M, *row_shape]``
    rows, via the Pallas DMA kernel when the row's element count allows
    (TPU, a multiple of 128), else the plain XLA gather.  Values are
    identical either way, an index outside ``[0, M)`` clamped to the
    nearest row.  The table is read as stored; only the gathered batch is
    reshaped."""
    data = table.data
    # Clamp, on either path: an out-of-range block index in the Pallas
    # index_map would be undefined behaviour (an OOB DMA); XLA's gather
    # clamps too but wraps a negative index first, and the two paths must
    # give the same rows.
    idx = jnp.clip(idx.astype(jnp.int32), 0, data.shape[0] - 1)
    if _use_pallas() and math.prod(table.row_shape) % _LANE == 0:
        rows = _pallas_row_gather(data, idx)
    else:
        rows = data[idx]
    return rows.reshape((idx.shape[0],) + table.row_shape)


# Opcodes whose result only names bytes that another instruction wrote.
_NO_WRITE = frozenset({"parameter", "get-tuple-element", "tuple", "bitcast",
                       "while", "conditional", "call"})
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([\w\-]+)\(")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def whole_table_writes(hlo_text: str, rows: int, row_elems: int) -> List[str]:
    """The instructions of a compiled module that write a whole copy of a
    ``rows``-row table: a result with ``rows`` first and at least
    ``row_elems`` elements behind it (the benchmark's
    ``trace_reduce.table_seconds`` rule, here on the program's text).  A
    table kept in the gather's layout has none: the gather writes a batch,
    and a ``while`` or a tuple only carries the table."""
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) in _NO_WRITE:
            continue
        for dims in _SHAPE.findall(m.group(1)):
            dims = [int(d) for d in dims.split(",") if d]
            if (len(dims) > 1 and dims[0] == rows
                    and math.prod(dims[1:]) >= row_elems):
                found.append(line.strip()[:200])
                break
    return found


def _self_check() -> None:
    """``python -m ddp_tpu.ops.gather``: the compiled gather against
    ``table[idx]`` on one device, inside ``shard_map`` over every visible
    device, and inside a scan (the resident epoch's shape of program), at
    the resident path's shapes (uint8 CIFAR rows).  Raises on any
    mismatch; on TPU also if a program holds no Mosaic kernel (i.e. it
    silently took the XLA gather) or if the scanned one writes the whole
    table (the table left the gather's layout)."""
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    mesh = make_mesh()
    print(device_line(mesh), flush=True)
    n_dev = mesh.devices.size
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 256, (2048, 32, 32, 3), dtype=np.uint8)
    idx = rng.integers(0, 2048, 128 * n_dev).astype(np.int32)
    idx[:2] = -1, 2048 + 5  # clamped like XLA's gather, not an OOB DMA
    want = rows[np.clip(idx, 0, 2047)]
    host = RowTable.from_rows(rows)
    table = jax.device_put(host, NamedSharding(mesh, P()))

    # One device: a Mosaic kernel is partitioned by shard_map or not at all.
    single = jax.jit(gather_rows)
    np.testing.assert_array_equal(np.asarray(single(host, idx[:128])),
                                  want[:128])
    sharded = jax.jit(jax.shard_map(
        gather_rows, mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS)))
    i_sh = jax.device_put(idx, NamedSharding(mesh, P(DATA_AXIS)))
    np.testing.assert_array_equal(np.asarray(sharded(table, i_sh)), want)

    def _scanned(table, idx_matrix):
        def body(_, idx_row):
            return None, gather_rows(table, idx_row).sum(axis=(1, 2, 3),
                                                         dtype=jnp.int32)
        return lax.scan(body, None, idx_matrix)[1]

    scanned = jax.jit(jax.shard_map(
        _scanned, mesh=mesh, in_specs=(P(), P(None, DATA_AXIS)),
        out_specs=P(None, DATA_AXIS)))
    i_mat = jax.device_put(idx.reshape(4, -1),
                           NamedSharding(mesh, P(None, DATA_AXIS)))
    np.testing.assert_array_equal(
        np.asarray(scanned(table, i_mat)),
        want.sum(axis=(1, 2, 3), dtype=np.int32).reshape(4, -1))
    pallas = _use_pallas()
    if pallas:
        for fn, args in ((single, (host, idx[:128])),
                         (sharded, (table, i_sh)),
                         (scanned, (table, i_mat))):
            text = fn.lower(*args).compile().as_text()
            if "tpu_custom_call" not in text:
                raise RuntimeError("gather_rows compiled without the "
                                   "Pallas kernel on TPU")
            copies = whole_table_writes(text, rows.shape[0],
                                        rows[0].size)
            if copies:
                raise RuntimeError("gather_rows' program writes the whole "
                                   "table: " + "; ".join(copies))
    print(f"gather: ok kernel={'pallas' if pallas else 'xla'} single=128 rows "
          f"shard_map={n_dev}x128 rows scan=4x{32 * n_dev} rows == table[idx], "
          f"no whole-table write", flush=True)


if __name__ == "__main__":
    _self_check()
