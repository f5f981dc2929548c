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
(``e_score_correction_bias``: state, not trained) and four integer
counters, ``assignments`` (to each held expert), ``dropped``,
``live_tiles`` (the row tiles that held a row, which are the tiles the
layer's loop visited: gathered, multiplied and scattered back) and
``buffer_tiles`` (the tiles the buffer offered: the live share's other
side, counted where the live tiles are so that the quotient holds over
replicas and micro-batches), which a training step adds to
(train/step.py sums integer state over replicas; the Trainer exports them
where it flushes losses: obs/routing.py).

Scopes: ``moe_route`` (router, top-k, the row plan, and inside the loop
over the live row tiles each tile's gather from the tokens and its
scatter-add back), ``moe_experts`` (the held experts' products over those
tiles, in the backward pass too: :func:`routed_products`),
``moe_shared``.

``python -m ddp_tpu.models.moe`` runs ``models/moe_probe.py``: the routed
half (gather, products, scatter-add) at the three routing cells' shapes
against the form it replaced, which gathered and scattered the whole
buffer.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.layers import linear
from ..ops.scatter import add_rows, row_shape

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
    here and no room) and ``live``, the tiles that hold a row: an expert's
    tiles follow the expert's before it, so they are tiles ``0 .. live-1``
    and every tile from ``live`` on is padding whole."""
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
    live = jnp.minimum(ends[-1] // tile, tiles)
    return sizes, src, tile_expert, dropped, live


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


def _varying(x, vma: frozenset):
    """``x`` varying over the mesh axes ``vma``.  Inside shard_map a
    parameter and a fresh constant do not vary over the mesh and the data
    does; a loop's carry must vary as its body's result does, and a
    ``custom_vjp`` is given parameters that already vary, so that their
    gradient is summed over the mesh by this cast's own transpose."""
    missing = tuple(vma - jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _tile(a, i):
    return lax.dynamic_index_in_dim(a, i, keepdims=False)


def _put_tile(a, v, i):
    return lax.dynamic_update_index_in_dim(a, v.astype(a.dtype), i, 0)


def _rows(a, i, tile):
    """Tile ``i``'s entries of a per-row array ``[tiles * tile, ...]``."""
    return lax.dynamic_slice_in_dim(a, i * tile, tile)


def _gather(a, tok):
    """Rows ``tok`` of ``a``; a row of padding (``tok`` past the end)
    reads zeros."""
    return a.at[tok].get(mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_products(form: ExpertForm, cd, xf, token, row_w, stacks,
                    tile_expert, live):
    """The held experts' weighted results in token order, over the live
    prefix of the row buffer.  ``xf`` [n_tok,d]; ``token``, ``row_w``
    [tiles * tile]: a row's token (``n_tok`` for padding) and weight (0
    for padding); ``stacks``: the parameters ``form.routed`` names,
    ``[count, ...]`` each, multiplied in ``cd``; tile ``i`` is expert
    ``tile_expert[i]``'s.  Returns ``routed`` [n_tok,d] float32: each row's
    ``row_w * expert(xf[token])`` added to its token.

    Both rules are a loop of ``live`` trips, a tile each, so no pass
    touches a tile of padding and no array of the buffer's rows exists:
    a trip gathers its tile's rows from ``xf`` (``moe_route``), runs its
    expert (``moe_experts``) and adds the weighted result into a float32
    accumulator at the tile's tokens (``moe_route``).  The backward takes
    ``jax.vjp`` of one tile (any form passes through it), writes the
    tile's weights' cotangent, adds the rows' into a float32 accumulator
    of ``xf``'s (cast once at the end) and the weight gradients into their
    expert's slice of another.  Both accumulators of the tokens' rows are
    added to in place by ``ops/scatter.py:add_rows`` (a DMA a row each
    way on a TPU, which holds them as :func:`row_shape` says), which
    needs a call's rows to name distinct tokens: a tile is one expert's,
    and a token chooses an expert once.  Each rule opens its scopes
    itself: a backward rule is traced outside the layer's."""
    return _routed_fwd(form, cd, xf, token, row_w, stacks, tile_expert,
                       live)[0]


def _routed_fwd(form, cd, xf, token, row_w, stacks, tile_expert, live):
    (n_tok, d), tile = xf.shape, token.shape[0] // tile_expert.shape[0]
    vma = jax.typeof(xf).vma
    with jax.named_scope("moe_experts"):
        low = tuple(s.astype(cd) for s in stacks)

    def one(i, acc):
        with jax.named_scope("moe_route"):
            tok, e = _rows(token, i, tile), tile_expert[i]
            rows = _gather(xf, tok)
            with jax.named_scope("moe_experts"):
                out = form.fn(jnp.dot, rows, lambda j: _tile(low[j], e))
            return add_rows(
                acc, tok, out.astype(F32) * _rows(row_w, i, tile)[:, None])

    routed = lax.fori_loop(0, live, one, _varying(
        jnp.zeros((n_tok,) + row_shape(d), F32), vma))
    with jax.named_scope("moe_route"):
        routed = routed.reshape(n_tok, d)
    return routed, (xf, token, row_w, stacks, tile_expert, live)


def _routed_bwd(form, cd, res, g):
    xf, token, row_w, stacks, tile_expert, live = res
    (n_tok, d), tile = xf.shape, token.shape[0] // tile_expert.shape[0]
    vma = jax.typeof(xf).vma
    with jax.named_scope("moe_experts"):
        low = tuple(s.astype(cd) for s in stacks)

    def one(i, carry):
        d_xf, d_row_w, d_stacks = carry
        with jax.named_scope("moe_route"):
            tok, w_i, e = _rows(token, i, tile), _rows(row_w, i, tile), \
                tile_expert[i]
            rows, g_rows = _gather(xf, tok), _gather(g, tok)
            with jax.named_scope("moe_experts"):
                out, pull = jax.vjp(
                    lambda x, *w: form.fn(jnp.dot, x, lambda j: w[j]),
                    rows, *(_tile(s, e) for s in low))
                dx, *dw = pull((g_rows * w_i[:, None]).astype(out.dtype))
                d_stacks = tuple(
                    _put_tile(acc, _tile(acc, e) + v.astype(F32), e)
                    for acc, v in zip(d_stacks, dw))
            d_row_w = lax.dynamic_update_slice_in_dim(
                d_row_w, jnp.sum(out.astype(F32) * g_rows, axis=-1),
                i * tile, 0)
            return add_rows(d_xf, tok, dx.astype(F32)), d_row_w, d_stacks

    d_xf, d_row_w, d_stacks = lax.fori_loop(0, live, one, (
        _varying(jnp.zeros((n_tok,) + row_shape(d), F32), vma),
        _varying(jnp.zeros(row_w.shape, F32), vma),
        tuple(_varying(jnp.zeros(s.shape, F32), vma) for s in stacks)))
    with jax.named_scope("moe_experts"):
        d_stacks = tuple(a.astype(s.dtype) for a, s in zip(d_stacks, stacks))
    with jax.named_scope("moe_route"):
        d_xf = d_xf.reshape(n_tok, d).astype(xf.dtype)
    return (d_xf, None, d_row_w.astype(row_w.dtype),
            d_stacks, None, None)


routed_products.defvjp(_routed_fwd, _routed_bwd)


def expert_layer(p, st, x, dm: dict, cd, *, train: bool, form: ExpertForm):
    """Returns ``(y, new layer state)``.  The held experts' rows are laid
    out in a buffer of row tiles, each tile one expert's (:func:`row_plan`),
    and a loop visits the tiles that hold a row and no others
    (:func:`routed_products`): the rows are packed expert after expert
    from the buffer's start, so those tiles are a prefix.  A trip gathers
    its tile's rows from the tokens, multiplies them with its expert's
    weights and adds their weighted results back to their tokens; the
    buffer itself is a plan of indices and weights, never an array of
    rows.  A step's time therefore follows the held experts' load; with
    every tile live it is below what the form that gathered and scattered
    the whole buffer cost (``python -m ddp_tpu.models.moe`` holds it
    there).  Padding rows inside a live tile
    read zeros, which pass through either form's activation and products
    as zeros, and weigh nothing (no mask: ``jax.lax.ragged_dot``, which
    the buffer replaced, leaves the rows past its groups as it finds them,
    NaN on the chip: PERF.md, section 6).

    The buffer (:func:`buffer_tiles`) holds ``MOE_LOAD_HEADROOM`` times
    the uniform load: no assignment is dropped while the held experts'
    load is within that, whatever its split among them, and ``dropped``
    counts those that found no room beyond it."""
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
        sizes, src, tile_expert, dropped, live = row_plan(
            jnp.where(held, idx - first, count).reshape(-1), count, tile,
            tiles)
        # Assignment a is slot a % k of token a // k; a row of padding is
        # token n_tok, past the end, and weighs nothing.
        token = src // k
        row_w = jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)])[
            src]
    routed = routed_products(
        form, cd, xf, token, row_w,
        tuple(_varying(p[name], jax.typeof(xf).vma) for name in form.routed),
        tile_expert, live)
    with jax.named_scope("moe_shared"):
        y = shared_expert(p, xf, cd, form).astype(F32) + routed
    new_st = st
    if train:
        new_st = {"e_bias": st["e_bias"],
                  "assignments": st["assignments"] + sizes,
                  "dropped": st["dropped"] + dropped,
                  "live_tiles": st["live_tiles"] + live,
                  "buffer_tiles": st["buffer_tiles"] + tiles}
    return y.astype(cd).reshape(bsz, t, d), new_st


if __name__ == "__main__":
    from ddp_tpu.models import moe_probe
    moe_probe.main()
