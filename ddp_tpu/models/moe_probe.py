"""``python -m ddp_tpu.models.moe``: the expert layer's routed half
(gather, products, scatter-add) at the three routing cells' shapes against
the form it replaced, which gathered and scattered the whole buffer
(:func:`former`, also the reference of ``tests/test_moe.py``), and the
check that a compiled loop updates its accumulator in place
(:func:`accumulator_copies`)."""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.scatter import row_shape
from . import moe
from .moe import (F32, MOE_ROW_TILE, RELU2, SWIGLU, buffer_tiles,
                  routed_products)

# (name, form, hidden, expert width, router experts, top_k, experts held) of
# the three routing cells' expert layers, at the cells' 16,384 tokens a
# step; and what a process without a TPU runs instead.
SELF_CHECK_LAYERS = (("nemotron_h", RELU2, 2688, 1856, 128, 6, 8),
                     ("glm4_moe_lite", SWIGLU, 2048, 1536, 64, 4, 8),
                     ("laguna", SWIGLU, 2048, 512, 256, 8, 32))
SELF_CHECK_TINY = (("relu2", RELU2, 128, 256, 32, 4, 8),
                   ("swiglu", SWIGLU, 128, 256, 16, 2, 8))
SELF_CHECK_SHARES = (0.25, 0.5, 1.0)


def accumulator_copies(hlo_text: str, shape: str) -> list:
    """The instructions of a compiled module's loop bodies that copy an
    array of ``shape`` (as the text writes it: ``f32[16384,2688]``).  A
    loop that updates its accumulator in place has none; one that copies
    it pays the accumulator's bytes on every trip."""
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo_text))
    found, comp = [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = re.match(r"%?([\w.\-]+) ", line)
            comp = head.group(1) if head else None
        elif comp in bodies:
            copy = re.search(r" copy(?:-start)?\(", line)
            if copy and shape in line[:copy.start()]:
                found.append(line.strip()[:200])
    return found


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _former_products(form, cd, rows, stacks, tile_expert, live):
    """The former form's products over the live prefix of a gathered
    buffer ``rows`` [tiles,tile,d]: ``out`` [tiles,tile,d], each rule a
    loop of ``live`` trips that writes a tile of the buffer's products (or
    of its rows' cotangent) a trip."""
    return _former_fwd(form, cd, rows, stacks, tile_expert, live)[0]


def _former_fwd(form, cd, rows, stacks, tile_expert, live):
    low = tuple(s.astype(cd) for s in stacks)

    def one(i, out):
        return moe._put_tile(out, form.fn(
            jnp.dot, moe._tile(rows, i),
            lambda j: moe._tile(low[j], tile_expert[i])), i)

    out = lax.fori_loop(0, live, one, jnp.zeros(
        rows.shape, jnp.promote_types(rows.dtype, cd)))
    return out, (rows, stacks, tile_expert, live)


def _former_bwd(form, cd, res, g):
    rows, stacks, tile_expert, live = res
    low = tuple(s.astype(cd) for s in stacks)

    def one(i, carry):
        d_rows, d_stacks = carry
        e = tile_expert[i]
        _, pull = jax.vjp(
            lambda x, *w: form.fn(jnp.dot, x, lambda j: w[j]),
            moe._tile(rows, i), *(moe._tile(s, e) for s in low))
        dx, *dw = pull(moe._tile(g, i))
        return moe._put_tile(d_rows, dx, i), tuple(
            moe._put_tile(acc, moe._tile(acc, e) + v.astype(F32), e)
            for acc, v in zip(d_stacks, dw))

    d_rows, d_stacks = lax.fori_loop(0, live, one, (
        jnp.zeros_like(rows), tuple(jnp.zeros(s.shape, F32) for s in stacks)))
    return d_rows, tuple(a.astype(s.dtype) for a, s in zip(d_stacks, stacks)
                         ), None, None


_former_products.defvjp(_former_fwd, _former_bwd)


def former(form, cd, xf, token, row_w, stacks, tile_expert, live):
    """What :func:`routed_products` replaced, kept as the probe's
    yardstick and the tests' reference: every row of the buffer gathered
    (padding reads the zero row past the tokens), the live tiles
    multiplied, every row's weighted result scatter-added back (padding to
    the spare row)."""
    (n_tok, d), tiles = xf.shape, tile_expert.shape[0]
    rows = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])[token]
    out = _former_products(form, cd, rows.reshape(tiles, -1, d), stacks,
                           tile_expert, live)
    return jnp.zeros((n_tok + 1, d), F32).at[token].add(
        out.reshape(-1, d).astype(F32) * row_w[:, None])[:n_tok]


def _probe_operands(form, n_tok, d, h, count, tiles, tile, live, cd):
    """Tokens ``xf``, a plan whose first ``live`` tiles hold rows (a tile's
    tokens drawn at random, distinct and in rising order as
    :func:`row_plan` leaves them; the experts' tiles in order, shared
    evenly) and the rest padding, the held experts' matrices, and the
    cotangent of the routed result."""
    keys = jax.random.split(jax.random.key(live), 4 + len(form.routed))
    xf = jax.random.normal(keys[0], (n_tok, d), F32).astype(cd)
    g = jax.random.normal(keys[1], (n_tok, d), F32)
    drawn = jax.vmap(lambda k: jnp.sort(jax.random.choice(
        k, n_tok, (tile,), replace=False)))(jax.random.split(keys[2], tiles))
    token = jnp.where((jnp.arange(tiles) < live)[:, None], drawn,
                      n_tok).reshape(-1)
    row_w = jnp.where(token < n_tok,
                      jax.random.uniform(keys[3], token.shape, F32), 0)
    stacks = tuple(
        0.02 * jax.random.normal(
            k, (count, h, d) if name == "down" else (count, d, h), F32)
        for k, name in zip(keys[4:], form.routed))
    tile_expert = jnp.minimum(jnp.arange(tiles) * count // max(live, 1),
                              count - 1).astype(jnp.int32)
    return xf, token, row_w, stacks, tile_expert, jnp.int32(live), g


def _with_backward(f):
    """``f``'s result and its cotangents for ``xf``, ``row_w`` and the
    stacks: the result is returned too, so that neither form's forward
    loop is dead code in the pair."""
    def run(xf, token, row_w, stacks, tile_expert, live, g):
        y, pull = jax.vjp(lambda x, w, s: f(x, token, w, s, tile_expert,
                                            live), xf, row_w, stacks)
        return y, pull(g)
    return run


def main() -> None:
    """The routed half alone (gather, products, scatter-add), forward and
    with its backward, at the three routing cells' shapes (on a TPU;
    elsewhere tiny ones) with a quarter, a half and all of the buffer
    live, against the form it replaced, which gathered and scattered the
    whole buffer: that the results agree, and on a TPU the milliseconds
    of both.  Raises where a result differs and, once every layer's table
    is printed, where a compiled loop copies its float32 accumulator of
    the tokens' rows or, on a TPU, where a FULL buffer costs a step
    (forward, then forward and backward) over 3% more than the former
    form."""
    from ..ops.attention import _ms, _rel
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = jax.default_backend() == "tpu"
    cd = jnp.bfloat16
    tile, n_tok = (MOE_ROW_TILE, 16384) if on_chip else (16, 256)
    faults = []
    for name, form, d, h, router, k, count in (SELF_CHECK_LAYERS if on_chip
                                               else SELF_CHECK_TINY):
        tiles = buffer_tiles(n_tok, {"top_k": k, "count": count,
                                     "router": router}, tile)
        print(f"moe: {name} d={d} expert={h} held={count} tokens={n_tok} "
              f"tiles={tiles} x {tile} rows, {len(form.routed)} matrices "
              f"an expert, {jnp.dtype(cd).name}", flush=True)
        if on_chip:
            print("moe:   live tiles     ms forward: live, former   "
                  "ms forward+backward: live, former")
        for share in SELF_CHECK_SHARES:
            live = round(share * tiles)
            *args, g = _probe_operands(form, n_tok, d, h, count, tiles, tile,
                                       live, cd)
            fwd = {n: jax.jit(functools.partial(f, form, cd))
                   for n, f in (("live", routed_products),
                                ("former", former))}
            both = {n: jax.jit(_with_backward(f)) for n, f in fwd.items()}
            if live == tiles:
                acc = "f32[{}]".format(",".join(
                    str(n) for n in (n_tok,) + row_shape(d)))
                copies = [c for fn, a in ((fwd, args), (both, (*args, g)))
                          for c in accumulator_copies(
                              fn["live"].lower(*a).compile().as_text(), acc)]
                print(f"moe:   {name}: {len(copies)} copies of the {acc} "
                      "accumulator in the compiled loops", flush=True)
                if copies:
                    faults.append(f"{name}: a compiled loop copies its {acc} "
                                  f"accumulator: {copies[0]}")
            near = _rel(fwd["live"](*args), fwd["former"](*args))
            far = max(_rel(a, b) for a, b in zip(
                *(jax.tree_util.tree_leaves(both[n](*args, g))
                  for n in ("live", "former"))))
            # A product may now reach its float32 weighting unrounded (the
            # former form wrote it to a buffer in ``cd``), the order of a
            # token's sum changed, and the rows' cotangent adds up in
            # float32 where the former form added it in ``cd``.
            if near > 0.01 or far > 0.01:
                raise RuntimeError(
                    f"moe: {name} at {live} live tiles of {tiles}: the "
                    f"result is {near:.2e} and a gradient {far:.5f} from "
                    "the former form's")
            if not on_chip:
                print(f"moe:   {live:>3} of {tiles}: result within "
                      f"{near:.2e}, gradients within {far:.5f}", flush=True)
                continue
            ms = [_ms(fn[n], *a) for fn, a in ((fwd, args), (both, (*args, g)))
                  for n in ("live", "former")]
            print(f"moe:   {live:>3} of {tiles}" + " " * 14
                  + f"{ms[0]:9.2f}{ms[1]:9.2f}" + " " * 15
                  + f"{ms[2]:9.2f}{ms[3]:9.2f}   result within {near:.2e}, "
                  f"gradients within {far:.5f}", flush=True)
            # What a step pays a layer: the forward, and under the block's
            # checkpoint the forward again with the backward.
            paid = ms[0] + ms[2], ms[1] + ms[3]
            if live == tiles and paid[0] > 1.03 * paid[1]:
                faults.append(
                    f"{name} with every tile live costs {paid[0]:.2f} ms a "
                    f"step and layer against the former form's "
                    f"{paid[1]:.2f}: over 3% more")
    if faults:
        raise RuntimeError("moe: " + "; ".join(faults))
    print(f"moe: ok platform={jax.default_backend()} the live loop's "
          "results are the former form's at "
          + ", ".join(f"{s:.0%}" for s in SELF_CHECK_SHARES) + " live, and "
          "no loop copies its accumulator", flush=True)
