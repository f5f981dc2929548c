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
products visited) and ``buffer_tiles`` (the tiles the buffer offered: the
live share's other side, counted where the live tiles are so that the
quotient holds over replicas and micro-batches), which a training step
adds to (train/step.py sums
integer state over replicas; the Trainer exports them where it flushes
losses: obs/routing.py).

Scopes: ``moe_route`` (router, top-k, sort, gather in, scatter back),
``moe_experts`` (the held experts' products over the live row tiles, in
the backward pass too: :func:`tile_products`), ``moe_shared``.

``python -m ddp_tpu.models.moe`` times the products alone at both routing
cells' shapes against the form that computes every tile.
"""
from __future__ import annotations

import functools
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def tile_products(form: ExpertForm, cd, rows, stacks, tile_expert, live):
    """The held experts' products over the live prefix of the row buffer.
    ``rows`` [tiles,tile,d]; ``stacks``: the parameters ``form.routed``
    names, ``[count, ...]`` each, multiplied in ``cd``; tile ``i`` is
    expert ``tile_expert[i]``'s.  Returns ``out`` [tiles,tile,d]: tiles
    ``0 .. live-1`` computed, the rest zeros, which is what either form
    makes of their zero rows.

    Both rules are a loop of ``live`` trips, so no pass multiplies a tile
    of padding: the backward takes ``jax.vjp`` of one tile (any form
    passes through it), writes the rows' cotangent a tile at a time and
    adds the tile's weight gradients into its expert's slice of a float32
    accumulator.  Each opens the ``moe_experts`` scope itself: a backward
    rule is traced outside the layer's."""
    return _products_fwd(form, cd, rows, stacks, tile_expert, live)[0]


def _products_fwd(form, cd, rows, stacks, tile_expert, live):
    vma = jax.typeof(rows).vma
    with jax.named_scope("moe_experts"):
        low = tuple(s.astype(cd) for s in stacks)

        def one(i, out):
            e = tile_expert[i]
            return _put_tile(out, form.fn(
                jnp.dot, _tile(rows, i), lambda j: _tile(low[j], e)), i)

        out = lax.fori_loop(0, live, one, _varying(jnp.zeros(
            rows.shape, jnp.promote_types(rows.dtype, cd)), vma))
    return out, (rows, stacks, tile_expert, live)


def _products_bwd(form, cd, res, g):
    rows, stacks, tile_expert, live = res
    vma = jax.typeof(rows).vma
    with jax.named_scope("moe_experts"):
        low = tuple(s.astype(cd) for s in stacks)

        def one(i, carry):
            d_rows, d_stacks = carry
            e = tile_expert[i]
            _, pull = jax.vjp(
                lambda x, *w: form.fn(jnp.dot, x, lambda j: w[j]),
                _tile(rows, i), *(_tile(s, e) for s in low))
            dx, *dw = pull(_tile(g, i))
            return _put_tile(d_rows, dx, i), tuple(
                _put_tile(acc, _tile(acc, e) + v.astype(F32), e)
                for acc, v in zip(d_stacks, dw))

        d_rows, d_stacks = lax.fori_loop(0, live, one, (
            _varying(jnp.zeros_like(rows), vma),
            tuple(_varying(jnp.zeros(s.shape, F32), vma) for s in stacks)))
        d_stacks = tuple(a.astype(s.dtype) for a, s in zip(d_stacks, stacks))
    return d_rows, d_stacks, None, None


tile_products.defvjp(_products_fwd, _products_bwd)


def expert_layer(p, st, x, dm: dict, cd, *, train: bool, form: ExpertForm):
    """Returns ``(y, new layer state)``.  The held experts' products run
    over a buffer of row tiles, each tile one expert's (:func:`row_plan`),
    a tile at a time with its expert's weights, over the tiles that hold
    a row and no others (:func:`tile_products`): the rows are packed
    expert after expert from the buffer's start, so those tiles are a
    prefix, and what lies past it stays zero.  A step's time therefore
    follows the held experts' load, up to the whole buffer's (every tile
    live: what each step cost before PR 38).  Padding rows inside a live
    tile are zeros that pass through either form's activation and
    products as zeros (no mask: ``jax.lax.ragged_dot``, which the buffer
    replaced, leaves the rows past its groups as it finds them, NaN on
    the chip: PERF.md, findings of PR 28).

    The buffer (:func:`buffer_tiles`) holds ``MOE_LOAD_HEADROOM`` times
    the uniform load: no assignment is dropped while the held experts'
    load is within that, whatever its split among them, and ``dropped``
    counts those that found no room beyond it.  The buffer's size is
    memory, and time in ``moe_route``: all its rows are gathered and
    scattered back whether they hold a token or not."""
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
        # Assignment a is slot a % k of token a // k; a row of padding
        # reads the zero row and weighs nothing.
        token = src // k
        rows = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])[token]
        row_w = jnp.concatenate([w.reshape(-1), jnp.zeros((1,), w.dtype)])[
            src]
    with jax.named_scope("moe_experts"):
        out = tile_products(
            form, cd, rows.reshape(tiles, tile, d),
            tuple(_varying(p[name], jax.typeof(rows).vma)
                  for name in form.routed), tile_expert, live)
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
                  "dropped": st["dropped"] + dropped,
                  "live_tiles": st["live_tiles"] + live,
                  "buffer_tiles": st["buffer_tiles"] + tiles}
    return y.astype(cd).reshape(bsz, t, d), new_st


# -- python -m ddp_tpu.models.moe ------------------------------------------------

# (name, form, hidden, expert width, router experts, top_k) of the two
# routing cells' expert layers, 8 experts held each, at the cells' 16,384
# tokens a step; and what a process without a TPU runs instead.
SELF_CHECK_LAYERS = (("nemotron_h", RELU2, 2688, 1856, 128, 6),
                     ("glm4_moe_lite", SWIGLU, 2048, 1536, 64, 4))
SELF_CHECK_TINY = (("relu2", RELU2, 128, 256, 32, 4),
                   ("swiglu", SWIGLU, 128, 256, 16, 2))
SELF_CHECK_SHARES = (0.25, 0.5, 1.0)


def _every_tile(form, cd, rows, stacks, tile_expert, live):
    """What :func:`tile_products` replaced, kept as the probe's yardstick:
    every tile of the buffer multiplied, live or not."""
    del live
    low = tuple(s.astype(cd) for s in stacks)
    return lax.map(
        lambda a: form.fn(jnp.dot, a[0], lambda j: low[j][a[1]]),
        (rows, tile_expert))


def _probe_operands(form, d, h, count, tiles, tile, live, cd):
    """A buffer whose first ``live`` tiles hold rows, shared evenly among
    the experts in order, and the cotangent the layer would send back
    (zero wherever the rows are padding)."""
    keys = jax.random.split(jax.random.key(live), 2 + len(form.routed))
    holds = (jnp.arange(tiles) < live)[:, None, None]
    rows, g = (jnp.where(holds, jax.random.normal(k, (tiles, tile, d), F32),
                         0).astype(cd) for k in keys[:2])
    stacks = tuple(
        0.02 * jax.random.normal(
            k, (count, h, d) if name == "down" else (count, d, h), F32)
        for k, name in zip(keys[2:], form.routed))
    tile_expert = jnp.minimum(jnp.arange(tiles) * count // max(live, 1),
                              count - 1).astype(jnp.int32)
    return rows, stacks, tile_expert, jnp.int32(live), g


def _self_check() -> None:
    """The held experts' products alone, forward and with their backward,
    at both routing cells' shapes (on a TPU; elsewhere tiny ones) with a
    quarter, a half and all of the buffer live, against the form that
    computes every tile: that the results are the same, and on a TPU the
    milliseconds of both.  Raises where a result differs or, on a TPU, the
    live loop over a FULL buffer costs a step (forward, then forward and
    backward) over 3% more than the every-tile form."""
    from ..ops.attention import _ms, _rel
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = jax.default_backend() == "tpu"
    cd, count = jnp.bfloat16, 8
    tile, n_tok = (MOE_ROW_TILE, 16384) if on_chip else (16, 256)
    for name, form, d, h, router, k in (SELF_CHECK_LAYERS if on_chip
                                        else SELF_CHECK_TINY):
        tiles = buffer_tiles(n_tok, {"top_k": k, "count": count,
                                     "router": router}, tile)
        print(f"moe: {name} d={d} expert={h} held={count} tiles={tiles} x "
              f"{tile} rows, {len(form.routed)} matrices an expert, "
              f"{jnp.dtype(cd).name}", flush=True)
        if on_chip:
            print("moe:   live tiles     ms forward: live, every tile   "
                  "ms forward+backward: live, every tile")
        for share in SELF_CHECK_SHARES:
            live = round(share * tiles)
            *args, g = _probe_operands(form, d, h, count, tiles, tile, live,
                                       cd)
            fwd = {n: jax.jit(functools.partial(f, form, cd))
                   for n, f in (("live", tile_products),
                                ("every", _every_tile))}
            both = {n: jax.jit(lambda rows, stacks, te, lv, g, f=f: jax.vjp(
                lambda r, s: f(r, s, te, lv), rows, stacks)[1](g))
                for n, f in fwd.items()}
            if not jnp.array_equal(fwd["live"](*args), fwd["every"](*args)):
                raise RuntimeError(
                    f"moe: {name} at {live} live tiles of {tiles}: the live "
                    "loop's result is not the every-tile form's")
            far = max(_rel(a, b) for a, b in zip(
                *(jax.tree_util.tree_leaves(both[n](*args, g))
                  for n in ("live", "every"))))
            # The every-tile form adds a tile's weight gradient to its
            # expert's in ``cd``, the live loop in float32.
            if far > 0.01:
                raise RuntimeError(
                    f"moe: {name} at {live} live tiles of {tiles}: a "
                    f"gradient is {far:.5f} from the every-tile form's")
            if not on_chip:
                print(f"moe:   {live:>3} of {tiles}: result equal, "
                      f"gradients within {far:.5f}", flush=True)
                continue
            ms = [_ms(fn[n], *a) for fn, a in ((fwd, args), (both, (*args, g)))
                  for n in ("live", "every")]
            print(f"moe:   {live:>3} of {tiles}" + " " * 14
                  + f"{ms[0]:9.2f}{ms[1]:9.2f}" + " " * 15
                  + f"{ms[2]:9.2f}{ms[3]:9.2f}   gradients within "
                  f"{far:.5f}", flush=True)
            # What a step pays a layer: the forward, and under the block's
            # checkpoint the forward again with the backward.
            paid = ms[0] + ms[2], ms[1] + ms[3]
            if live == tiles and paid[0] > 1.03 * paid[1]:
                raise RuntimeError(
                    f"moe: {name} with every tile live costs {paid[0]:.2f} "
                    f"ms a step and layer against the every-tile form's "
                    f"{paid[1]:.2f}: over 3% more")
    print(f"moe: ok platform={jax.default_backend()} the live loop's "
          "results are the every-tile form's at "
          + ", ".join(f"{s:.0%}" for s in SELF_CHECK_SHARES) + " live",
          flush=True)


if __name__ == "__main__":
    from ddp_tpu.models import moe
    moe._self_check()
