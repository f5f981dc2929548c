"""The Mamba-1 selective scan, with its softplus, skip and gate, as a blocked
Pallas kernel pair: the ``[N, channels]`` float32 state and a block's
states live and die in VMEM.

``selscan(x [B,T,C], z [B,T,C], dt_raw [B,T,C], dt_bias [C], a [N,C],
b [B,T,N], c [B,T,N], d_skip [C])`` is the ``sel_scan`` scope of
``models/sambay.py:mamba_mixer``: ``dt = softplus(dt_raw + dt_bias)``,
``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t``, ``y_t = C_t . s_t + D x_t``,
``gated = y silu(z)``; it returns ``(gated, y)`` in ``x``'s type (``y`` is
the memory the GMU layers read, so both carry a cotangent).  The decay
differs a channel AND a state element, so no product over chunks exists:
the recurrence is a token a step.  In plain XLA the backward pass writes a
chunk's 128 states to HBM and reads them back a token at a time, and the
operands are transposed time-first around three forward passes: the scope
ran at 2.9% of its bytes roofline on the chip (PERF.md).  Here:

- **Forward** (``selscan_fwd``), grid ``(sequence, channel tile, block of
  tokens)``, the last axis sequential.  The state of a channel tile is one
  ``[N, tile]`` float32 value (the state elements on the sublanes, the
  channels on the lanes), carried through a block's token loop in
  registers and across blocks in scratch, zero at the first.  ``x``,
  ``z``, ``dt_raw`` are read in place from ``[B,T,C]`` and ``B``, ``C``
  from ``[B,T,N]`` through block specs.  What is dense over a block
  (softplus, ``dt x``, the skip, the gate, the casts) runs before and
  after the token loop on ``[tokens, tile]`` tiles; the loop itself reads
  a token's ``dt`` and ``dt x`` as rows spread over the sublanes and its
  ``B_t``, ``C_t`` as columns spread over the lanes (made once a block, a
  token a tile), and sums ``C_t s_t`` over the state eight tokens at a
  time (:func:`_state_sums`).  Under ``jax.vjp`` the state ENTERING each
  block is written out too: all the backward needs of the forward.
- **Backward** (``selscan_bwd``), ONE reverse sweep over the same grid
  with the state's cotangent ``[N, tile]`` in scratch.  A block's states
  are made again from its entering state into VMEM scratch, then walked
  in reverse: out come ``dx``, ``dz``, ``d(dt_raw)``, a channel tile's
  partial sums of ``dB`` and ``dC`` (time on the lanes; summed over the
  tiles in XLA) and a sequence's partial sums of ``dA``, ``dD`` and
  ``d(dt_bias)`` (summed over the sequences in XLA).

Precision is the scope's, point for point: ``dt``, ``A``, the state, its
recurrence, ``y``, the skip and the gate in float32; ``x``, ``z``, ``B``,
``C`` read in the compute type and ``gated``, ``y`` written in it, where
the mixer casts.

The ``pallas_call`` sites are ``ops/attention.py``'s: inside ``shard_map``
with ``check_vma=True`` every ``out_shape`` declares the mesh axes it
varies over.  Who takes the kernel is read off the input
(:func:`kernel_applies`).  ``python -m ddp_tpu.ops.selscan`` checks it
against the XLA path and a float32 answer on whatever device the process
sees.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather import _use_pallas

F32 = jnp.float32
_LANE = 128
_SUBLANE = 8
# Tokens a block and channels a tile, forward and backward: swept on the chip
# at the cell's shape, 2 x 8,192 tokens, 5,120 channels, state 16, bf16
# (PERF.md section 6).
TOKENS = 128
FWD_TILE = 1024
BWD_TILE = 512
VMEM_LIMIT_BYTES = 64 * 2**20

# How many times ``models.sambay.mamba_mixer`` was traced through the
# kernel and through ``selective_scan`` (as ``attention.TRACED``).
TRACED = {"kernel": 0, "xla": 0}


def _varies(*arrays) -> frozenset:
    """The mesh axes a result of ``arrays`` varies over (as
    ``attention._varies``; not imported, so that this model's process
    holds none of ``nemotron_h``'s kernels)."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _tile(channels: int, want: int) -> int:
    """Channels a tile: the most whole lane tiles within ``want`` that
    divide ``channels`` (0: none does)."""
    return max((w for w in range(_LANE, min(want, channels) + 1, _LANE)
                if channels % w == 0), default=0)


def _vmem_bytes(tokens: int, tile: int, n: int, itemsize: int) -> int:
    """What the backward kernel holds at once: its blocks (``x``, ``z``,
    the two cotangents, ``dx``, ``dz`` in the compute type, ``dt_raw`` and
    its cotangent in float32), each double-buffered by the pipeline, a
    block's states, seven float32 ``[tokens, tile]`` tiles and the
    columns of ``B`` and ``C``."""
    blocks = tokens * tile * (6 * itemsize + 2 * 4)
    return 2 * blocks + 4 * ((tokens + 1) * n * tile + 7 * tokens * tile
                             + 2 * n * tokens * _LANE)


def _whole(t: int, channels: int, n: int) -> bool:
    """``t`` whole blocks of tokens, the channels whole lane tiles and the
    state whole sublane tiles."""
    return not (t % TOKENS or channels % _LANE or n % _SUBLANE
                or TOKENS % _LANE)


def kernel_applies(t: int, channels: int, n: int, itemsize: int = 4) -> bool:
    """Whether :func:`selscan` can run this problem here: a TPU backend,
    whole blocks, lanes and sublanes (:func:`_whole`) and the backward
    kernel's blocks within the VMEM budget."""
    return _use_pallas() and _whole(t, channels, n) and _vmem_bytes(
        TOKENS, max(_tile(channels, FWD_TILE), _tile(channels, BWD_TILE)),
        n, itemsize) <= VMEM_LIMIT_BYTES


# -- inside a block -----------------------------------------------------------

def _columns(b_ref, c_ref, dst_ref):
    """``B`` and ``C`` ``[tokens, N]`` as ``dst [tokens, 2 N, 128]``: a
    token's ``N`` values of each down the sublanes, spread over the lanes,
    a token a tile (so that the loop reads a token's with plain loads:
    gathering them from an ``[N, tokens, 128]`` layout with a stride cost
    a quarter of the forward kernel's time on the chip)."""
    v = jnp.concatenate([b_ref[...], c_ref[...]], axis=1).astype(F32)
    tokens, n2 = v.shape
    by_state = jnp.concatenate(
        [v, jnp.zeros((tokens, _LANE - n2), F32)], axis=1).T[:n2]
    # Unrolled: a loop that rotates the next tokens to the first lanes
    # cost 2.3 ms a forward pass on the chip.
    for t in range(tokens):
        dst_ref[t] = jnp.broadcast_to(by_state[:, t:t + 1], (n2, _LANE))


def _column(ref, t, which: int, width: int):
    """Token ``t``'s column of ``B`` (``which`` 0) or ``C`` (1) from
    :func:`_columns` over ``width`` lanes: ``[N, width]``."""
    n = ref.shape[1] // 2
    return jnp.tile(ref[t, which * n:(which + 1) * n, :],
                    (1, width // _LANE))


def _row(ref, t, n: int):
    """Row ``t`` of ``ref [tokens, width]`` spread over ``n`` sublanes."""
    return jnp.broadcast_to(ref[pl.ds(t, 1), :], (n, ref.shape[1]))


def _folded(v, axis: int, size: int):
    """``v``'s slices of ``size`` along ``axis``, added."""
    return functools.reduce(jnp.add, [
        lax.slice_in_dim(v, k, k + size, axis=axis)
        for k in range(0, v.shape[axis], size)])


def _lane_sums(v):
    """``v [N, width]`` summed over the lanes: ``[N, 1]``, the lane tiles
    added first."""
    return jnp.sum(_folded(v, 1, _LANE), axis=1, keepdims=True)


def _state_sums(prods):
    """Eight tokens' ``[N, width]`` products summed over the state:
    ``[8, width]``, token ``u`` on sublane ``u``.  A tree over the eight
    that halves the registers a stage (rows ``k`` and ``k + 4``, then ``k``
    and ``k + 2``, then ``k`` and ``k + 1`` of a token meet by a sublane
    rotation): ten rotations for the eight tokens where a sum a token
    takes twenty-four."""
    q = [_folded(p, 0, _SUBLANE) for p in prods]
    row = lax.broadcasted_iota(jnp.int32, q[0].shape, 0)
    low = row < 4
    q = [jnp.where(low, q[u], q[u + 4])
         + pltpu.roll(jnp.where(low, q[u + 4], q[u]), 4, 0) for u in range(4)]
    for step, count in ((2, 2), (1, 1)):
        first = row % (2 * step) < step
        q = [jnp.where(first, q[u] + pltpu.roll(q[u], _SUBLANE - step, 0),
                       q[u + count] + pltpu.roll(q[u + count], step, 0))
             for u in range(count)]
    return q[0]


def _tokens(body, carry, sums=(), reverse: bool = False):
    """``body(t, carry) -> (carry, products)`` over a block's tokens, eight
    an iteration; a token's ``i``-th product ``[N, width]`` is summed over
    the state into row ``t`` of ``sums[i]``."""
    def group(i, carry):
        base = pl.multiple_of(i * _SUBLANE, _SUBLANE)
        if reverse:
            base = TOKENS - _SUBLANE - base
        prods = []
        for u in range(_SUBLANE):
            carry, p = body(base + (_SUBLANE - 1 - u if reverse else u),
                            carry)
            prods.append(p)
        if reverse:
            prods.reverse()
        for ref, of_tokens in zip(sums, zip(*prods)):
            ref[pl.ds(base, _SUBLANE), :] = _state_sums(of_tokens)
        return carry
    return lax.fori_loop(0, TOKENS // _SUBLANE, group, carry)


def _dense_in(x_ref, dtr_ref, bias_ref, dt_ref, dtx_ref):
    """``dt = softplus(dt_raw + bias)`` and ``dt x`` of a block."""
    dt = jax.nn.softplus(dtr_ref[...] + bias_ref[...])
    dt_ref[...] = dt
    dtx_ref[...] = dt * x_ref[...].astype(F32)


def _fwd_kernel(x_ref, z_ref, dtr_ref, b_ref, c_ref, a_ref, bias_ref, d_ref,
                g_ref, y_ref, *rest):
    """``rest``: where it is saved, the entering state's block; then the
    scratch: the running state, the columns of ``B`` and ``C``, ``dt``,
    ``dt x`` and float32 ``y``."""
    enter_ref = rest[0] if len(rest) == 6 else None
    s_ref, bc_ref, dt_ref, dtx_ref, yf_ref = rest[-5:]
    n, w = a_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if enter_ref is not None:
        enter_ref[...] = s_ref[...]
    _dense_in(x_ref, dtr_ref, bias_ref, dt_ref, dtx_ref)
    _columns(b_ref, c_ref, bc_ref)
    a = a_ref[...]

    def token(t, s):
        s = jnp.exp(_row(dt_ref, t, n) * a) * s \
            + _row(dtx_ref, t, n) * _column(bc_ref, t, 0, w)
        return s, (s * _column(bc_ref, t, 1, w),)

    s_ref[...] = _tokens(token, s_ref[...], (yf_ref,))
    z = z_ref[...].astype(F32)
    y = yf_ref[...] + d_ref[...] * x_ref[...].astype(F32)
    y_ref[...] = y.astype(y_ref.dtype)
    g_ref[...] = (y * (z * jax.nn.sigmoid(z))).astype(g_ref.dtype)


def _bwd_kernel(x_ref, z_ref, dtr_ref, b_ref, c_ref, a_ref, bias_ref, d_ref,
                enter_ref, dg_ref, dyo_ref, dx_ref, dz_ref, ddtr_ref, db_ref,
                dc_ref, da_ref, dd_ref, dbias_ref, ds_ref, st_ref, bc_ref,
                dt_ref, dtx_ref, yf_ref, dy_ref, ddtx_ref, ddta_ref):
    """Scratch: the state's cotangent scaled by the next token's decay,
    the block's states (the entering one first), the columns of ``B`` and
    ``C``, and float32 ``[tokens, tile]`` tiles: ``dt``, ``dt x``, ``y``
    without the skip, ``dy``, and the sums over the state of ``g B`` and
    ``g s a A``."""
    n, w = a_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    _dense_in(x_ref, dtr_ref, bias_ref, dt_ref, dtx_ref)
    _columns(b_ref, c_ref, bc_ref)
    z = z_ref[...].astype(F32)
    sig = jax.nn.sigmoid(z)
    dg = dg_ref[...].astype(F32)
    dy_ref[...] = dyo_ref[...].astype(F32) + dg * (z * sig)
    a = a_ref[...]

    # The block's states again, kept: st[t + 1] is the state after token t.
    def token(t, s):
        s = jnp.exp(_row(dt_ref, t, n) * a) * s \
            + _row(dtx_ref, t, n) * _column(bc_ref, t, 0, w)
        st_ref[t + 1] = s
        return s, (s * _column(bc_ref, t, 1, w),)

    # (Carries start from scratch, not from a block: under shard_map a
    # block's value is typed varying, a value computed here is not.)
    st_ref[0] = enter_ref[...]
    _tokens(token, st_ref[0], (yf_ref,))

    lane = lax.broadcasted_iota(jnp.int32, (n, TOKENS), 1)

    # In reverse, with g_t = C_t dy_t + a_{t+1} g_{t+1} the cotangent of
    # s_t: the carried ``ag`` is a_{t+1} g_{t+1}.
    def back(t, carry):
        ag, da, db, dc = carry
        dy = _row(dy_ref, t, n)
        g = _column(bc_ref, t, 1, w) * dy + ag
        dc = jnp.where(lane == t, _lane_sums(st_ref[t + 1] * dy), dc)
        dt = _row(dt_ref, t, n)
        ag = g * jnp.exp(dt * a)
        through_a = ag * st_ref[t]
        da = da + through_a * dt
        db = jnp.where(lane == t, _lane_sums(g * _row(dtx_ref, t, n)), db)
        return (ag, da, db, dc), (through_a * a, g * _column(bc_ref, t, 0, w))

    zeros = jnp.zeros((n, TOKENS), F32)
    ag, da, db, dc = _tokens(
        back, (ds_ref[...], jnp.zeros((n, w), F32), zeros, zeros),
        (ddta_ref, ddtx_ref), reverse=True)
    ds_ref[...] = ag
    da_ref[...] += da
    db_ref[...] = db
    dc_ref[...] = dc

    x = x_ref[...].astype(F32)
    dy, ddtx = dy_ref[...], ddtx_ref[...]
    ddtr = (ddta_ref[...] + ddtx * x) * jax.nn.sigmoid(
        dtr_ref[...] + bias_ref[...])
    ddtr_ref[...] = ddtr
    dbias_ref[...] += jnp.sum(ddtr, axis=0, keepdims=True)
    dd_ref[...] += jnp.sum(dy * x, axis=0, keepdims=True)
    dx_ref[...] = (ddtx * dt_ref[...] + d_ref[...] * dy).astype(dx_ref.dtype)
    y = yf_ref[...] + d_ref[...] * x
    dz_ref[...] = (dg * y * (sig * (1.0 + z * (1.0 - sig)))).astype(
        dz_ref.dtype)


# -- the calls ----------------------------------------------------------------

def _check(x, z, dtr, b, c, a, bias_row, d_row):
    """The operands' sizes: ``x``, ``z``, ``dtr`` [B,T,C], ``b``/``c``
    [B,T,N], ``a`` [N,C], ``bias_row``/``d_row`` [1,C]."""
    bsz, t, ch = x.shape
    n = a.shape[0]
    if z.shape != x.shape or dtr.shape != x.shape \
            or b.shape != (bsz, t, n) or c.shape != b.shape \
            or a.shape != (n, ch) or bias_row.shape != (1, ch) \
            or d_row.shape != (1, ch):
        raise ValueError(
            f"selscan: x {x.shape} wants z and dt_raw the same, b and c "
            f"[{bsz},{t},N], a [N,{ch}], dt_bias and D [1,{ch}], got "
            f"{z.shape}, {dtr.shape}, {b.shape}, {c.shape}, {a.shape}, "
            f"{bias_row.shape}, {d_row.shape}")
    if not _whole(t, ch, n):
        raise ValueError(
            f"selscan: t={t} in blocks of {TOKENS}, {ch} channels, state "
            f"{n} are not whole blocks, lanes of {_LANE} and sublanes of "
            f"{_SUBLANE}")
    return bsz, t, ch, n


def _specs(tile: int, n: int, block):
    """Block specs over the grid ``(sequence, channel tile, block of
    tokens)``; ``block(i)`` is the block of tokens grid step ``i`` works
    on."""
    wide = pl.BlockSpec((None, TOKENS, tile),
                        lambda s, j, i: (s, block(i), j))
    state = pl.BlockSpec((None, TOKENS, n), lambda s, j, i: (s, block(i), 0))
    decay = pl.BlockSpec((n, tile), lambda s, j, i: (0, j))
    lane = pl.BlockSpec((1, tile), lambda s, j, i: (0, j))
    enter = pl.BlockSpec((None, None, n, tile),
                         lambda s, j, i: (s, block(i), 0, j))
    return wide, state, decay, lane, enter


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _tiles_f32(n: int, tile: int, count: int) -> list:
    """The scratch both kernels share after the state's: the columns of
    ``B`` and ``C`` and ``count`` float32 ``[tokens, tile]`` tiles."""
    return [pltpu.VMEM((TOKENS, 2 * n, _LANE), F32)] \
        + [pltpu.VMEM((TOKENS, tile), F32)] * count


# Jitted, both calls: a kernel's body is traced anew at every pallas_call
# site, under jit once a signature.  Unjitted, the cell's step traced these
# bodies eight times (two layers, the checkpoint, the logits program) and
# its set-up grew by 14 s on the chip's host (PERF.md section 6).
@functools.partial(jax.jit, static_argnames=("tile", "save", "interpret"))
def _forward(x, z, dtr, b, c, a, bias_row, d_row, tile: int, save: bool,
             interpret: bool):
    """``(gated, y)`` [B,T,C] in ``x``'s type and, where ``save``, the state
    entering each block ``f32[B,blocks,N,C]`` (else None)."""
    bsz, t, ch, n = _check(x, z, dtr, b, c, a, bias_row, d_row)
    tile = _tile(ch, tile)
    wide, state, decay, lane, enter = _specs(tile, n, lambda i: i)
    vma = _varies(x, z, dtr, b, c, a, bias_row, d_row)
    out_specs = [wide, wide]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)] * 2
    if save:
        out_specs.append(enter)
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, t // TOKENS, n, ch), F32, vma=vma))
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, ch // tile, t // TOKENS),
        in_specs=[wide, wide, wide, state, state, decay, lane, lane],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, tile), F32)] + _tiles_f32(n, tile, 3),
        compiler_params=_params(),
        name="selscan_fwd",
        interpret=interpret,
    )(x, z, dtr, b, c, a, bias_row, d_row)
    return tuple(out) if save else (out[0], out[1], None)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _backward(x, z, dtr, b, c, a, bias_row, d_row, entering, dg, dyo,
              tile: int, interpret: bool):
    """``(dx, dz, ddtr, db, dc, da, dbias_row, dd_row)``."""
    bsz, t, ch, n = _check(x, z, dtr, b, c, a, bias_row, d_row)
    tile = _tile(ch, tile)
    tiles, blocks = ch // tile, t // TOKENS
    wide, state, decay, lane, enter = _specs(
        tile, n, lambda i: blocks - 1 - i)
    cols = pl.BlockSpec((None, None, n, TOKENS),
                        lambda s, j, i: (s, j, 0, blocks - 1 - i))
    sums = pl.BlockSpec((None, n, tile), lambda s, j, i: (s, 0, j))
    row_sums = pl.BlockSpec((None, 1, tile), lambda s, j, i: (s, 0, j))
    vma = _varies(x, z, dtr, b, c, a, bias_row, d_row, entering, dg, dyo)

    def shape(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, vma=vma)

    dx, dz, ddtr, db, dc, da, dd, dbias = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, tiles, blocks),
        in_specs=[wide, wide, wide, state, state, decay, lane, lane, enter,
                  wide, wide],
        out_specs=[wide, wide, wide, cols, cols, sums, row_sums, row_sums],
        out_shape=[shape(x.shape, x.dtype), shape(z.shape, z.dtype),
                   shape(dtr.shape), shape((bsz, tiles, n, t)),
                   shape((bsz, tiles, n, t)), shape((bsz, n, ch)),
                   shape((bsz, 1, ch)), shape((bsz, 1, ch))],
        scratch_shapes=[pltpu.VMEM((n, tile), F32),
                        pltpu.VMEM((TOKENS + 1, n, tile), F32)]
        + _tiles_f32(n, tile, 6),
        compiler_params=_params(),
        name="selscan_bwd",
        interpret=interpret,
    )(x, z, dtr, b, c, a, bias_row, d_row, entering, dg.astype(x.dtype),
      dyo.astype(x.dtype))

    def over_tiles(v):  # [B,tiles,N,T] -> [B,T,N]
        return v.sum(axis=1).transpose(0, 2, 1)

    return (dx, dz, ddtr, over_tiles(db).astype(b.dtype),
            over_tiles(dc).astype(c.dtype), da.sum(axis=0),
            dbias.sum(axis=0), dd.sum(axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _selscan(x, z, dtr, b, c, a, bias_row, d_row, interpret: bool):
    return _forward(x, z, dtr, b, c, a, bias_row, d_row, FWD_TILE, False,
                    interpret)[:2]


def _selscan_fwd(x, z, dtr, b, c, a, bias_row, d_row, interpret):
    g, y, entering = _forward(x, z, dtr, b, c, a, bias_row, d_row, FWD_TILE,
                              True, interpret)
    return (g, y), (x, z, dtr, b, c, a, bias_row, d_row, entering)


def _selscan_bwd(interpret, res, cotangents):
    return _backward(*res, *cotangents, BWD_TILE, interpret)


_selscan.defvjp(_selscan_fwd, _selscan_bwd)


def selscan(x, z, dt_raw, dt_bias, a, b, c, d_skip, interpret: bool = False):
    """The ``sel_scan`` scope of models/sambay.py through the kernels:
    ``x``, ``z`` [B,T,C] and ``b``, ``c`` [B,T,N] in the compute type,
    ``dt_raw`` [B,T,C], ``dt_bias`` and ``d_skip`` [C] and ``a`` [N,C]
    float32; ``(gated, y)`` [B,T,C] in ``x``'s type.  ``T`` is whole blocks
    of ``TOKENS``.  ``interpret`` runs the kernels in the Pallas
    interpreter (the CPU tests)."""
    # Inside shard_map a parameter does not vary over the mesh; what a
    # kernel reads beside the data must, and its cotangent is then summed
    # over the mesh by the cast's own transpose.
    vma = _varies(x, z, dt_raw, b, c)

    def varying(v):
        v = v.astype(F32)
        missing = tuple(vma - jax.typeof(v).vma)
        return lax.pcast(v, missing, to="varying") if missing else v

    return _selscan(x, z.astype(x.dtype), dt_raw.astype(F32),
                    b.astype(x.dtype), c.astype(x.dtype), varying(a),
                    varying(dt_bias[None, :]), varying(d_skip[None, :]),
                    interpret)


# -- python -m ddp_tpu.ops.selscan ----------------------------------------------

SWEEP = (256, 512, 1024)
_NAMES = ("gated", "y", "dx", "dz", "ddt_raw", "ddt_bias", "da", "db", "dc",
          "dd")


def _operands(bsz: int, t: int, ch: int, n: int, seed=0):
    """``(x, z, dt_raw, dt_bias, a, b, c, d_skip)`` and a cotangent for
    each result, float32: ``dt`` and ``a`` in the initialiser's ranges."""
    ks = jax.random.split(jax.random.key(seed), 10)
    x, z, wg, wy = (jax.random.normal(k, (bsz, t, ch), F32) for k in ks[:4])
    b, c = (jax.random.normal(k, (bsz, t, n), F32) for k in ks[4:6])
    dt_raw = jax.random.normal(ks[6], (bsz, t, ch), F32) - 3.0
    dt_bias = 0.5 * jax.random.normal(ks[7], (ch,), F32)
    a = -jax.random.uniform(ks[8], (n, ch), F32, 1.0, 16.0)
    return (x, z, dt_raw, dt_bias, a, b, c,
            jax.random.normal(ks[9], (ch,), F32)), (wg, wy)


def _low(args, cd):
    """The operands as the mixer hands them over: ``x``, ``z``, ``b``, ``c``
    in the compute type, the rest float32."""
    x, z, dt_raw, dt_bias, a, b, c, d = args
    return x.astype(cd), z.astype(cd), dt_raw, dt_bias, a, b.astype(cd), \
        c.astype(cd), d


def _xla_path(x, z, dt_raw, dt_bias, a, b, c, d):
    """What ``mamba_mixer`` runs where the kernel does not apply."""
    from ..models.sambay import SCAN_CHUNK, selective_scan
    dt = jax.nn.softplus(dt_raw + dt_bias)
    y = selective_scan(x, dt, a, b, c, SCAN_CHUNK) + d * x.astype(F32)
    return (y * jax.nn.silu(z.astype(F32))).astype(x.dtype), y.astype(x.dtype)


def _mixer_operands(bsz: int, t: int, ch: int, n: int, cd, d_model: int = 64,
                    rank: int = 8):
    """``(dm, weights, u)`` for ``models.sambay.mamba_mixer`` at these
    sizes, as shapes."""
    weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
        ("in_proj", (d_model, 2 * ch)), ("conv_w", (4, ch)),
        ("conv_b", (ch,)), ("x_proj", (ch, rank + 2 * n)),
        ("dt_proj", (rank, ch)), ("dt_bias", (ch,)), ("A_log", (ch, n)),
        ("D", (ch,)), ("out_proj", (ch, d_model)))}
    return {"n": n, "dt_rank": rank}, weights, \
        jax.ShapeDtypeStruct((bsz, t, d_model), cd)


def _vjp_of(path):
    def run(args, w):
        out, pull = jax.vjp(path, *args)
        return out + pull(tuple(wi.astype(o.dtype) for wi, o in zip(w, out)))
    return run


def _self_check() -> None:
    """On a TPU, at the second token cell's shape (2 sequences of 8,192
    tokens, 5,120 channels, state 16, bf16): each path's distance from the
    float32 answer (the two results and the eight gradients),
    milliseconds forward and forward plus backward for the kernel and the
    XLA path, and the sweep of channels a tile.  Elsewhere: a small shape
    (two blocks, two tiles) through the interpreter, distances only.
    Raises where the kernel is further from float32 than the XLA path by
    more than a quarter."""
    from ..models.sambay import mamba_mixer
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache
    from .attention import _ms, _rel

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = _use_pallas()
    bsz, t, ch, n = (2, 8192, 5120, 16) if on_chip else (2, 256, 1024, 16)
    cd = jnp.bfloat16
    print(f"selscan: B={bsz} T={t} C={ch} N={n} {jnp.dtype(cd).name} blocks "
          f"of {TOKENS} tokens, tiles of {_tile(ch, FWD_TILE)} channels "
          f"forward and {_tile(ch, BWD_TILE)} backward", flush=True)
    args, w = _operands(bsz, t, ch, n)
    forward = {"kernel": lambda *a: selscan(*a, interpret=not on_chip),
               "xla": _xla_path}
    exact = jax.jit(_vjp_of(_xla_path))(args, w)
    # The cotangents arrive in the compute type, as the mixer's do (cast
    # inside the jitted function, XLA would keep the XLA path's in float32).
    low, w_low = _low(args, cd), tuple(v.astype(cd) for v in w)
    dist = {name: [_rel(a, b) for a, b in zip(
        jax.jit(_vjp_of(fn))(low, w_low), exact)]
        for name, fn in forward.items()}
    print("selscan: distance from float32" + "".join(
        f"{name:>9}" for name in _NAMES))
    for name, d in dist.items():
        print(f"selscan:   {name:<27}" + "".join(f"{x:9.5f}" for x in d),
              flush=True)
    for what, got, ref in zip(_NAMES, dist["kernel"], dist["xla"]):
        if got > 1.25 * ref:
            raise RuntimeError(
                f"selscan's {what} is {got:.5f} from the float32 answer, "
                f"the XLA path {ref:.5f}: further by more than a quarter")

    if on_chip:
        print("selscan: ms            forward  forward+backward")
        for name, fn in forward.items():
            print(f"selscan:   {name:<8}{_ms(jax.jit(fn), *low):11.2f}"
                  f"{_ms(jax.jit(_vjp_of(fn)), low, w_low):11.2f}",
                  flush=True)
        x, z, dt_raw, dt_bias, a, b, c, d = low
        core = (x, z, dt_raw, b, c, a, dt_bias[None, :], d[None, :])
        kw = dict(interpret=False)
        entering = jax.jit(functools.partial(
            _forward, tile=FWD_TILE, save=True, **kw))(*core)[2]
        print("selscan: the kernels alone, sweep (channels a tile): ms "
              "forward, forward that saves the entering states, backward")
        for tile in SWEEP:
            f, fs = (jax.jit(functools.partial(
                _forward, tile=tile, save=save, **kw))
                for save in (False, True))
            bw = jax.jit(functools.partial(_backward, tile=tile, **kw))
            print(f"selscan:   {tile:<6}{_ms(f, *core):9.2f}"
                  f"{_ms(fs, *core):9.2f}"
                  f"{_ms(bw, *core, entering, *w_low):9.2f}", flush=True)
    # The mixer itself at this shape: which path it is traced through.
    dm, weights, u = _mixer_operands(bsz, t, ch, n, cd)
    jax.eval_shape(lambda p, u: mamba_mixer(p, u, dm, cd), weights, u)
    print(f"selscan: mixer traced through {TRACED}", flush=True)
    if on_chip and TRACED != {"kernel": 1, "xla": 0}:
        raise RuntimeError("mamba_mixer did not take the kernel at the "
                           "second token cell's shape on a TPU")
    print(f"selscan: ok kernel={'pallas' if on_chip else 'interpret'} "
          f"B={bsz} T={t} C={ch} N={n} within a quarter of the XLA path's "
          f"distance from float32", flush=True)


if __name__ == "__main__":
    # Through the module as the model imports it, not this second copy of
    # it: ``TRACED`` is the one the mixer adds to.
    from ddp_tpu.ops import selscan
    selscan._self_check()
