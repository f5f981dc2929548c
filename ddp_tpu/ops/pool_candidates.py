"""Alternative 2x2/2 max-pool aimed at the pool-backward residue.

The VGG step's phase split charges a visible share to "pool backward":
the autodiff VJP of ``lax.reduce_window`` max is ``select-and-scatter``,
a windowed scan op.  For the VGG case (window == stride == 2, no
padding, even spatial dims) the same pooling is expressible as a
reshape + axis max, whose backward is pure elementwise work (equality
mask + broadcast) that XLA can fuse — IF the tie-breaking is made to
match.  Plain ``jnp.max`` autodiff splits the cotangent EVENLY among
tied window elements; ``select_and_scatter`` (and torch's maxpool)
route it to the FIRST maximal element in row-major window order — and
ties are common here because post-ReLU activations carry exact zeros.
``max_pool_reshape`` therefore pins first-tie semantics with a custom
VJP (cumulative-count-of-ties == 1 mask), making it numerically
identical to :func:`~ddp_tpu.ops.layers.max_pool` forward AND backward.

Measure with ``python -m ddp_tpu.ops.pool_candidates`` (marginal-cost
chains, same differencing methodology as ``conv_probe``); one JSON line
per (impl, shape).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
from jax import lax

# Shared timing methodology — chain lengths, noise threshold, and the
# best-of core come from the conv probe so the two cannot drift.
from .conv_probe import N_LONG, N_SHORT, NOISE_S_PER_CALL, best_of

# (H=W, C) at batch 512 — every "M" site in VGG.ARCH (models/vgg.py:23).
VGG_POOL_SHAPES = [(32, 128), (16, 256), (8, 512), (4, 512)]


@jax.custom_vjp
def max_pool_reshape(x: jax.Array) -> jax.Array:
    """2x2 stride-2 max pool of NHWC ``x`` (even H and W) as reshape+max
    with a pure-elementwise first-tie backward — the CANDIDATE.  Its
    window-view transposes force activation relayouts that fight the
    conv layouts in the composed step, so the shipped ``max_pool`` stays
    on ``reduce_window`` (layers.py); neither side is measured on this
    stack."""
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _window_view(x):
    """[N,H,W,C] -> [N,H/2,W/2,4,C] with window index in ROW-MAJOR order
    ((dy,dx) = (0,0),(0,1),(1,0),(1,1)) — the order select_and_scatter
    (and torch) break ties in."""
    n, h, w, c = x.shape
    return (x.reshape(n, h // 2, 2, w // 2, 2, c)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(n, h // 2, w // 2, 4, c))


def _fwd(x):
    y = max_pool_reshape(x)
    return y, (x, y)


def _bwd(res, dy):
    x, y = res
    n, h, w, c = x.shape
    eq = (_window_view(x) == y[:, :, :, None, :])
    # First maximal element per window: the tie where the running count
    # of ties is exactly 1.  Pure elementwise + a length-4 cumsum — no
    # windowed scatter anywhere in the backward.
    first = eq & (jnp.cumsum(eq, axis=3) == 1)
    dxw = jnp.where(first, dy[:, :, :, None, :], 0).astype(x.dtype)
    dx = (dxw.reshape(n, h // 2, w // 2, 2, 2, c)
          .transpose(0, 1, 3, 2, 4, 5)
          .reshape(n, h, w, c))
    return (dx,)


max_pool_reshape.defvjp(_fwd, _bwd)


def _reduce_window_pool(x):
    """The shipped implementation (autodiff backward =
    select-and-scatter) — the probe baseline."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 2, 2, 1), window_strides=(1, 2, 2, 1),
        padding=((0, 0), (0, 0), (0, 0), (0, 0)))


IMPLS = {
    "baseline_reduce_window": _reduce_window_pool,
    "reshape_max_first_tie": max_pool_reshape,
}


def _train_chain(n, pool):
    def win(x):
        acc = jnp.zeros((), x.dtype)
        for _ in range(n):
            y, vjp = jax.vjp(pool, x + acc * 1e-30)
            (dx,) = vjp(y)
            acc = jnp.mean(dx) + jnp.mean(y)
        return acc

    return jax.jit(win)


def probe(batch=512, repeats=6, dtype=jnp.float32):
    records = []
    for name, pool in IMPLS.items():
        for h, c in VGG_POOL_SHAPES:
            # ReLU-like data: exact zeros make ties common, as in the
            # real activations this op pools.
            x = jax.nn.relu(jax.random.normal(
                jax.random.key(0), (batch, h, h, c), dtype) - 0.3)
            t_s = best_of(_train_chain(N_SHORT, pool), (x,), repeats)
            t_l = best_of(_train_chain(N_LONG, pool), (x,), repeats)
            per = max((t_l - t_s) / (N_LONG - N_SHORT), 1e-9)
            rec = {"impl": name, "shape": f"{h}x{h}x{c}",
                   "marginal_ms_per_call": round(per * 1e3, 3),
                   "noise_limited": (t_l - t_s) < NOISE_S_PER_CALL
                   * (N_LONG - N_SHORT)}
            records.append(rec)
            print(json.dumps(rec), flush=True)
    for name in IMPLS:
        total = sum(r["marginal_ms_per_call"] for r in records
                    if r["impl"] == name)
        print(json.dumps({"impl": name,
                          "sum_marginal_ms_per_step": round(total, 3)}),
              flush=True)
    return records


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--repeats", type=int, default=6)
    p.add_argument("--bf16", action="store_true")
    args = p.parse_args()
    probe(args.batch, args.repeats,
          jnp.bfloat16 if args.bf16 else jnp.float32)


if __name__ == "__main__":
    main()
