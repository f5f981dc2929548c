"""ops/scatter.py: the in-place row-add kernel in the Pallas interpreter
against XLA's scatter-add, alone and as the expert layer's routed half."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ddp_tpu.models import moe, moe_probe  # noqa: E402
from ddp_tpu.ops import scatter  # noqa: E402
from ddp_tpu.ops.scatter import add_rows, row_shape  # noqa: E402

F32 = jnp.float32
INTERPRET = pltpu.InterpretParams()


def _plan(n, rows, trips, seed):
    """``trips`` calls' rows: distinct tokens in rising order inside a call,
    the last few of each past the end (padding)."""
    keys = jax.random.split(jax.random.key(seed), trips)
    tok = jax.vmap(lambda k: jnp.sort(jax.random.choice(
        k, n, (rows,), replace=False)))(keys)
    return tok.at[:, rows - 3:].set(n + jnp.arange(3)).reshape(-1)


@pytest.mark.parametrize("d", [128, 384])
def test_the_kernel_adds_as_the_scatter_does(d):
    """A loop of calls that carries the accumulator: every live row's
    values added to its row, padding ignored, the same sums as XLA's
    scatter-add (each row adds one value a call, so no order differs)."""
    n, rows, trips = 48, 16, 5
    tok = _plan(n, rows, trips, d)
    v = jax.random.normal(jax.random.key(1), (trips * rows, d), F32)
    acc = jax.random.normal(jax.random.key(2), (n, d), F32)

    def loop(acc, **kw):
        def one(i, a):
            return add_rows(a, lax.dynamic_slice_in_dim(tok, i * rows, rows),
                            lax.dynamic_slice_in_dim(v, i * rows, rows), **kw)
        return lax.fori_loop(0, trips, one, acc)

    got = jax.jit(functools.partial(loop, interpret=INTERPRET))(
        acc.reshape(n, d // 128, 128))
    want = jax.jit(loop)(acc)
    assert got.shape == (n, d // 128, 128)
    np.testing.assert_array_equal(np.asarray(got).reshape(n, d), want)
    live = np.asarray(tok) < n
    assert not np.array_equal(np.asarray(want), np.asarray(acc))
    untouched = np.setdiff1d(np.arange(n), np.asarray(tok)[live])
    np.testing.assert_array_equal(np.asarray(want)[untouched],
                                  np.asarray(acc)[untouched])


def test_row_shape_is_the_kernels_where_it_runs(monkeypatch):
    assert row_shape(2688) == (2688,) and row_shape(24) == (24,)
    monkeypatch.setattr(scatter, "_use_pallas", lambda: True)
    assert row_shape(2688) == (21, 128) and row_shape(2048) == (16, 128)
    assert row_shape(24) == (24,)


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_the_routed_half_through_the_kernel(form, monkeypatch):
    """The layer's routed half with its two accumulators held and added
    as on the chip, against XLA's scatter-add: the same result and
    cotangents (a token's float32 sums in the same order)."""
    form = {"relu2": moe.RELU2, "swiglu": moe.SWIGLU}[form]
    n_tok, d, h, count, tile, tiles, live = 64, 128, 64, 4, 8, 12, 9
    *args, g = moe_probe._probe_operands(form, n_tok, d, h, count, tiles,
                                         tile, live, F32)
    run = moe_probe._with_backward(functools.partial(
        moe.routed_products, form, F32))
    want = jax.jit(run)(*args, g)
    monkeypatch.setattr(moe, "row_shape", lambda d: (d // 128, 128))
    monkeypatch.setattr(moe, "add_rows", functools.partial(
        add_rows, interpret=INTERPRET))
    got = jax.jit(run)(*args, g)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
