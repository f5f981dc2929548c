"""The expert layer the token models share: a sigmoid router over ALL of
a layer's experts, the ``top_k`` largest ``s + b`` chosen, and on this
chip the shared expert plus the weighted results of the chosen experts
it HOLDS (``dm["first"]``, ``dm["count"]``), a tile of rows at a time
over row tiles sorted by expert.  What an expert is made of is an
argument (:class:`ExpertForm`): ``nemotron_h``'s two matrices around
``relu2`` (:data:`RELU2`), ``glm4_moe_lite``'s three as ``silu(gate) *
up`` (:data:`SWIGLU`).  Routing, the buffer of row tiles and the
counters are one code for both, so a change to any of them is measured
on every cell that routes.

``dm`` holds what the layer reads: ``router`` (experts the router scores),
``first``/``count`` (the share held here), ``top_k``, ``norm_topk``,
``scale``.  The layer's state holds the router's ``e_bias``
(``e_score_correction_bias``: state, not trained) and two integer
counters, ``assignments`` (to each held expert) and ``dropped``, which a
training step adds to (train/step.py sums integer state over replicas;
the Trainer exports them where it flushes losses: obs/routing.py).

Scopes: ``moe_route`` (router, top-k, sort, gather in, scatter back),
``moe_experts`` (the held experts' products over the row tiles),
``moe_shared``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.layers import linear

F32 = jnp.float32
MOE_ROW_TILE = 512
# The expert layer's row buffer holds this many times the load that
# uniform routing sends to the experts held here.
MOE_LOAD_HEADROOM = 5


def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


class ExpertForm(NamedTuple):
    """An expert's matrices and what it computes with them.  ``routed``
    names the stacked leaves ``[count, ...]`` of the experts held,
    ``shared`` the shared expert's in the same order; ``fn(mm, x, w)`` is
    one expert on rows ``x`` with the product ``mm``, ``w(i)`` its i-th
    matrix (fetched where the form first uses it, so that the traced
    program reads a matrix beside its product)."""
    routed: Tuple[str, ...]
    shared: Tuple[str, ...]
    fn: Callable


RELU2 = ExpertForm(
    ("up", "down"), ("shared_up", "shared_down"),
    lambda mm, x, w: mm(relu2(mm(x, w(0))), w(1)))
SWIGLU = ExpertForm(
    ("gate", "up", "down"), ("shared_gate", "shared_up", "shared_down"),
    lambda mm, x, w: mm(jax.nn.silu(mm(x, w(0))) * mm(x, w(1)), w(2)))


def route_weights(s, e_bias, dm: dict):
    """The chosen experts and their weights: the ``top_k`` largest ``s +
    b``; weights are ``s`` itself (without ``b``), over their sum, times
    the scaling factor.  ``s`` [N,router] float32 -> (idx, w) [N,top_k]."""
    _, idx = lax.top_k(s + e_bias, dm["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dm["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * dm["scale"]


def shared_expert(p, x, cd, form: ExpertForm):
    return form.fn(linear, x, lambda i: p[form.shared[i]].astype(cd))


def row_plan(key, count: int, tile: int, tiles: int):
    """Which assignment each row holds, in a buffer of ``tiles`` tiles of
    ``tile`` rows in which every held expert's rows start on a tile's
    edge.  ``key`` [A]: the held expert of an assignment, ``count`` where
    it is held elsewhere.  Returns ``sizes`` [count] (assignments to each
    held expert), ``src`` [tiles * tile] (the assignment of a row; ``A``
    for a row of padding), ``tile_expert`` [tiles] and ``dropped`` (held
    here and no room)."""
    n, cap = key.shape[0], tiles * tile
    sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    padded = (sizes + tile - 1) // tile * tile
    ends = jnp.cumsum(padded)
    # Sorted by expert (stably: by token inside an expert), an assignment's
    # rank among its expert's is its rank less the ranks before the expert.
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    e = jnp.minimum(key_s, count - 1)
    row = (ends - padded)[e] + jnp.arange(n) - (jnp.cumsum(sizes) - sizes)[e]
    placed = (key_s < count) & (row < cap)
    src = jnp.full((cap + 1,), n, jnp.int32).at[
        jnp.where(placed, row, cap)].set(order)[:cap]
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(tiles) * tile, side="right"), count - 1)
    dropped = jnp.sum((key_s < count) & (row >= cap), dtype=jnp.int32)
    return sizes, src, tile_expert, dropped


def buffer_tiles(n_tok: int, dm: dict, tile: int) -> int:
    """Row tiles of the buffer for ``n_tok`` tokens: ``MOE_LOAD_HEADROOM``
    times the rows that uniform routing sends here (``top_k * count /
    router`` a token; never more than ``min(top_k, count)`` a token, which
    is every assignment that can fall on a held expert) plus a tile an
    expert for the edges."""
    k, count = dm["top_k"], dm["count"]
    rows = n_tok * min(MOE_LOAD_HEADROOM * k * count / dm["router"],
                       min(k, count))
    return -(-int(rows) // tile) + count


def expert_layer(p, st, x, dm: dict, cd, *, train: bool, form: ExpertForm):
    """Returns ``(y, new layer state)``.  The held experts' products run
    over a buffer of row tiles, each tile one expert's (:func:`row_plan`),
    a tile at a time with its expert's weights: every tile is computed
    whether rows fell on it or not, so a step's time does not follow the
    routers' load, and padding rows are zeros that pass through either
    form's activation and products as zeros (no mask:
    ``jax.lax.ragged_dot``, which this replaced, leaves the rows past its
    groups as it finds them, and made the step's time follow the seed:
    PERF.md, findings of PR 28).

    The buffer (:func:`buffer_tiles`) holds ``MOE_LOAD_HEADROOM`` times
    the uniform load: no assignment is dropped while the held experts'
    load is within that, whatever its split among them, and ``dropped``
    counts those that found no room beyond it.  The buffer's size is
    memory AND time: its rows are gathered, multiplied and scattered back
    whether they hold a token or not (``nemotron_h``'s worst case, 6 rows
    a token where uniform routing sends 0.375, would triple the layer's
    time)."""
    bsz, t, d = x.shape
    n_tok, k = bsz * t, dm["top_k"]
    first, count, tile = dm["first"], dm["count"], MOE_ROW_TILE
    tiles = buffer_tiles(n_tok, dm, tile)
    xf = x.reshape(n_tok, d)
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.matmul(xf.astype(F32), p["router"],
                                      precision=lax.Precision.HIGHEST))
        idx, w = route_weights(s, st["e_bias"], dm)
        held = (idx >= first) & (idx < first + count)
        sizes, src, tile_expert, dropped = row_plan(
            jnp.where(held, idx - first, count).reshape(-1), count, tile,
            tiles)
        # Assignment a is slot a % k of token a // k; a row of padding
        # reads the zero row and weighs nothing.
        token = src // k
        rows = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])[token]
        row_w = jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)])[
            src]
    with jax.named_scope("moe_experts"):
        stacks = tuple(p[name].astype(cd) for name in form.routed)
        out = lax.map(
            lambda a: form.fn(jnp.dot, a[0], lambda i: stacks[i][a[1]]),
            (rows.reshape(tiles, tile, d), tile_expert))
    with jax.named_scope("moe_route"):
        # Back to token order: each row adds its weighted result to its
        # token (padding to the row past the tokens' end).
        routed = jnp.zeros((n_tok + 1, d), F32).at[token].add(
            out.reshape(tiles * tile, d).astype(F32) * row_w[:, None])[
                :n_tok]
    with jax.named_scope("moe_shared"):
        y = shared_expert(p, xf, cd, form).astype(F32) + routed
    new_st = st
    if train:
        new_st = {"e_bias": st["e_bias"],
                  "assignments": st["assignments"] + sizes,
                  "dropped": st["dropped"] + dropped}
    return y.astype(cd).reshape(bsz, t, d), new_st
