"""Rows added into an accumulator in place, one DMA a row each way, as a
Pallas kernel.

``acc.at[tok].add(v)`` inside a loop that carries ``acc`` is the expert
layer's hot op (``models/moe.py``: a tile of 512 rows a trip, added back
to their tokens).  XLA:TPU lowers that scatter-add to a generic per-row
update at about 300 ns a row, whatever the rows a call (PERF.md, section
6).  This kernel reads the destination rows into VMEM with one DMA each,
all in flight together, adds the tile in one vector pass and writes the
rows back the same way, in place (the accumulator is aliased to the
result).

A DMA moves whole tiles of the layout, so the accumulator holds a row as
``[d / 128, 128]`` (:func:`row_shape`): a row is then its own run of
tiles, where in a ``[n, d]`` array it would be one sublane of each of
``d / 128`` tiles.  A call's rows must name distinct rows of ``acc``: the
reads all land before any write, so a row named twice would keep one of
its additions.  A row of ``tok`` at or past ``acc``'s end adds nothing
(the layer's rows of padding).  Other backends (the CPU test mesh) hold
rows as they are and add with XLA's scatter: the same sums, so every
numerical test covers both paths' semantics; ``tests/test_scatter.py``
runs the kernel in the Pallas interpreter and compiles it for the chip.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather import _use_pallas

_LANE = 128


def row_shape(d: int) -> Tuple[int, ...]:
    """How an accumulator that :func:`add_rows` adds into holds a row of
    ``d`` elements: ``(d / 128, 128)`` where the kernel takes it (a TPU,
    ``d`` a multiple of 128), else ``(d,)``."""
    return (d // _LANE, _LANE) if _use_pallas() and d % _LANE == 0 else (d,)


def _kernel(tok_ref, v_ref, acc_ref, out_ref, buf, sem):
    del out_ref  # the buffer ``acc_ref`` names: written through it
    n = acc_ref.shape[0]

    def each(fn):
        def body(r, carry):
            t = tok_ref[r]

            @pl.when(t < n)
            def _():
                fn(r, t)
            return carry
        lax.fori_loop(0, v_ref.shape[0], body, 0)

    def read(r, t):
        return pltpu.make_async_copy(acc_ref.at[pl.ds(t, 1)],
                                     buf.at[pl.ds(r, 1)], sem.at[0])

    def write(r, t):
        return pltpu.make_async_copy(buf.at[pl.ds(r, 1)],
                                     acc_ref.at[pl.ds(t, 1)], sem.at[1])

    each(lambda r, t: read(r, t).start())
    each(lambda r, t: read(r, t).wait())
    buf[...] += v_ref[...].astype(buf.dtype)
    each(lambda r, t: write(r, t).start())
    each(lambda r, t: write(r, t).wait())


def _pallas_add_rows(acc, tok, v, interpret):
    vma = jax.typeof(acc).vma | jax.typeof(tok).vma | jax.typeof(v).vma
    zeros = (0,) * v.ndim
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(v.shape, lambda i, tok: zeros),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(v.shape, acc.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype, vma=vma),
        input_output_aliases={2: 0},
        # The rows' block twice (the pipeline's two buffers) and the
        # scratch, in float32, and room for the compiler's own.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * v.size * 4 + (8 << 20)),
        interpret=interpret,
        name="add_rows",
    )(tok, v, acc)


def add_rows(acc, tok, v, *, interpret=None):
    """``acc`` with ``v[r]`` added to row ``tok[r]``; a ``tok`` at or past
    the end adds nothing.  ``acc`` [n, *row], ``tok`` [R] int32 naming
    distinct rows, ``v`` [R, d] with ``d`` a row's elements.  By the
    kernel where ``acc`` holds rows as :func:`row_shape` gives them to it,
    or where ``interpret`` gives the Pallas interpreter's parameters (a
    test on the CPU); else by XLA's scatter-add."""
    v = v.astype(acc.dtype).reshape((v.shape[0],) + acc.shape[1:])
    if acc.ndim == 3 and (interpret is not None or _use_pallas()):
        return _pallas_add_rows(acc, tok.astype(jnp.int32), v,
                                interpret or False)
    return acc.at[tok].add(v, mode="drop")
