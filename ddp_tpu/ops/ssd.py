"""The Mamba-2 chunked scan, its gate and its norm as a blocked Pallas
kernel pair: a chunk's decay matrices, the running state and float32
``y`` on its way to the norm live and die in VMEM.

``ssd_scan(x [B,T,H,P], dt [B,T,H], a [H], b [B,T,G,N], c [B,T,G,N],
d_skip [H], z [B,T,H*P], weight [H*P], chunk, eps)`` is
``gated_norm(ssd_chunked(...), z, weight)`` of models/nemotron_h.py:
``y_t = H_t C_t + D x_t`` with ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x)
B_t``, then ``GroupRMSNorm(y silu(z))`` over the scan's ``G`` groups, in
``x``'s type.  In plain XLA every chunk's ``[Q,Q]`` float32 segment sums,
decays and weighted ``C B^T`` (one each a head) are written to HBM and
read again, the chunk states are transposed, scanned by a 64-step
``lax.scan`` and transposed back, and float32 ``y`` and the norm's
statistics, broadcast to its size, pass through HBM several times more,
forward, under the checkpoint and backward: the scope ran at 4% of its
bytes roofline on the chip (PERF.md).  Here:

- **Forward** (``ssd_fwd``), grid ``(sequence, group, block of chunks)``,
  the last axis sequential.  A group's ``R`` heads are ``R*P`` whole lanes
  of ``x`` and share one ``B``, ``C``: a chunk computes ``C B^T [Q,Q]``
  once, and a head at a time builds ``exp(cs_i - cs_j) dt_j`` under the
  causal mask and multiplies.  The group's state is one ``[N, R*P]``
  float32 tile in scratch (zero at the first chunk): read out through
  ``C H`` scaled by ``exp(cs)``, then ``H <- exp(cs_last) H + B^T xw``.
  A group's lanes are also one group of the norm, so the chunk's ``y`` is
  gated, normed and written in the compute type there.  Under ``jax.vjp``
  float32 ``y`` and the state ENTERING each chunk are written out too:
  all the backward needs of the forward.
- **Backward** (``ssd_bwd``), ONE reverse sweep over the same grid with
  ``dH [N, R*P]`` float32 in scratch.  The norm's and the gate's
  backward give ``dy`` and ``dz`` from the saved ``y``; a chunk's ``C
  B^T``, decays and ``M`` are made again from the inputs; out come
  ``dx``, ``dB``/``dC`` (summed over the group's heads inside the step),
  the cotangents of ``dt`` and of the cumulative ``dt*A``, and the
  partial sums of ``dD`` and of the norm's weight's gradient.

**A group wider than a grid step** (``HEAD_TILE``: more heads than the
shape the kernels were swept at, e.g. one group of 64 heads, 4,096 lanes,
whose blocks would hold three times the VMEM budget) is scanned a tile of
``HEAD_TILE`` heads a grid step: the grid's second axis runs over the
tiles, every tile of a group reads the group's one ``B``, ``C`` block
through its index map, and ``dB``/``dC`` leave the kernel as a tile's
float32 partial sums that XLA adds up over the group's tiles.  The norm's
group is then wider than a tile, so the gate and the norm are not the
kernel's: the forward kernel writes float32 ``y`` and ``gated_norm`` of
models/nemotron_h.py follows in XLA, whose backward hands the backward
kernel ``dy``.  A group within a grid step (the first token cell's 8 heads)
takes the kernels above, unchanged.

A ``[Q,Q]`` tile wants a head's ``dt`` and cumulative ``dt*A`` along lanes
and along sublanes; they come in as rows ``[R,Q]`` and are transposed in
the kernel (a column block ``[Q,R]`` in HBM is padded to 128 lanes: 16
times the bytes).  A lane tile of 128 holds ``128/P`` heads where ``P <
128``; a head's product then runs over the whole tile and the head's
lanes are picked by a mask, so nothing is sliced inside a tile.

What stays in XLA, outside the kernels: the cumulative sum of ``dt*A``
inside a chunk (and with it ``dA``), the rows' layout ``[B,G,nc,R,Q]`` of
the ``[B,T,H]`` float32 pieces, and the sums of ``dD`` and the weight's
gradient over sequences.

Precision is ``ssd_chunked``'s and ``gated_norm``'s, point for point:
``dt``, the cumulative sums, the decays, the state and its recurrence,
``y``, the gate and the norm's statistics in float32; the products'
operands in the compute type exactly where ``ssd_chunked`` casts (``M``,
``xw``, the entering state; backward, the cotangents' counterparts),
float32 accumulation.

The ``pallas_call`` sites are ``ops/attention.py``'s: inside ``shard_map``
with ``check_vma=True`` every ``out_shape`` declares the mesh axes it
varies over.  Who takes the kernel is read off the input
(:func:`kernel_applies`).  ``python -m ddp_tpu.ops.ssd`` checks it against
the XLA path and a float32 answer on whatever device the process sees.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _NT, _TN, _varies
from .gather import _use_pallas

F32 = jnp.float32
_LANE = 128
# Chunks a grid step, forward and backward: swept on the chip at the cell's
# shape, 2 x 8,192 tokens, 64 heads of 64 in 8 groups, state 128, chunks of
# 128, bf16 (PERF.md section 6).
FWD_CHUNKS = 4
BWD_CHUNKS = 4
# Heads a grid step where a group holds more than this many: that shape's.
HEAD_TILE = 8
VMEM_LIMIT_BYTES = 64 * 2**20
# The segment sum above the diagonal: far below any, and finite.
_MASKED = -0.7 * float(jnp.finfo(F32).max)

# How many times ``models.nemotron_h.mamba_mixer`` was traced through the
# kernel and through ``ssd_chunked`` (as ``attention.TRACED``).
TRACED = {"kernel": 0, "xla": 0}


def _vmem_bytes(q: int, rp: int, n: int, steps: int, itemsize: int) -> int:
    """What the backward kernel holds at once: its blocks (``x``, ``z``,
    ``do``, ``dx``, ``dz``, ``B``, ``C``, ``dB``, ``dC`` in the compute
    type, ``y`` and the entering states in float32), each double-buffered
    by the pipeline, the state in scratch and room for a chunk's tiles."""
    rows = steps * q
    blocks = (5 * rows * rp + 4 * rows * n) * itemsize \
        + 4 * (rows * rp + steps * n * rp)
    return 2 * blocks + 4 * n * rp + 16 * 4 * q * max(q, rp)


def _whole(t: int, h: int, p: int, g: int, n: int, q: int) -> bool:
    """``t`` whole chunks of whole lanes, a group's ``R*P`` and ``n`` whole
    lanes, a head that fills or evenly shares a lane tile, and a group's
    ``R`` heads within one lane tile (their rows are transposed there)."""
    return not (h % g or t % q or q % _LANE or n % _LANE
                or (h // g * p) % _LANE or (p % _LANE and _LANE % p)
                or h // g > _LANE)


def _head_tile(r: int, p: int) -> int:
    """Heads a grid step of a group of ``r``: all of them, or
    ``HEAD_TILE`` where they are more and fall into whole tiles of whole
    lanes."""
    if r <= HEAD_TILE or r % HEAD_TILE or HEAD_TILE * p % _LANE:
        return r
    return HEAD_TILE


def kernel_applies(t: int, h: int, p: int, g: int, n: int, chunk: int,
                   itemsize: int = 4) -> bool:
    """Whether :func:`ssd_scan` can run this problem here: a TPU backend,
    whole chunks and lanes (:func:`_whole`), a grid step's heads whole
    sublane tiles, and the blocks within the VMEM budget."""
    rt = _head_tile(h // g, p)
    if not (_use_pallas() and _whole(t, h, p, g, n, chunk)) or rt % 8:
        return False
    steps = max(_steps(t // chunk, FWD_CHUNKS), _steps(t // chunk,
                                                       BWD_CHUNKS))
    return _vmem_bytes(chunk, rt * p, n, steps, itemsize) \
        <= VMEM_LIMIT_BYTES


def _steps(nc: int, want: int) -> int:
    """Chunks a grid step: the largest divisor of ``nc`` within ``want``."""
    return max(d for d in range(1, min(want, nc) + 1) if nc % d == 0)


# -- inside a chunk -----------------------------------------------------------

def _tiles(r: int, p: int) -> List[Tuple[int, int, Tuple[int, ...]]]:
    """The group's ``r*p`` lanes as ``(start, width, heads)``: a head a
    tile where a head is whole lane tiles, else the heads sharing one."""
    if p >= _LANE:
        return [(h * p, p, (h,)) for h in range(r)]
    per = _LANE // p
    return [(i * _LANE, _LANE, tuple(range(i * per, (i + 1) * per)))
            for i in range(r * p // _LANE)]


def _lane_tiles(r: int, p: int, q: int) -> list:
    """:func:`_tiles` as ``(lanes, heads, masks)``: the tile's slice and,
    a head, its lanes of the tile as ``[q,width]`` (None: the whole)."""
    out = []
    for start, width, heads in _tiles(r, p):
        lane = lax.broadcasted_iota(jnp.int32, (q, width), 1)
        masks = [None] if len(heads) == 1 else [
            (lane >= k * p) & (lane < (k + 1) * p)
            for k in range(len(heads))]
        out.append((slice(start, start + width), heads, masks))
    return out


def _spread(cols, heads, masks, shape):
    """``cols [Q,R]`` -> ``[Q,width]``: a head's column over its lanes."""
    out = jnp.broadcast_to(cols[:, heads[0]:heads[0] + 1], shape)
    for h, m in zip(heads[1:], masks[1:]):
        out = jnp.where(m, jnp.broadcast_to(cols[:, h:h + 1], shape), out)
    return out


def _head_sums(v, masks) -> list:
    """``v [rows,width]`` summed over each head's lanes: ``[rows,1]``."""
    return [jnp.sum(v if m is None else jnp.where(m[:v.shape[0]], v, 0.0),
                    axis=1, keepdims=True) for m in masks]


def _place(pieces: Dict[int, jax.Array], shape, axis: int):
    """Head ``h``'s piece (one wide along ``axis``) at index ``h``."""
    at = lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.zeros(shape, F32)
    for h, piece in pieces.items():
        out = jnp.where(at == h, jnp.broadcast_to(piece, shape), out)
    return out


def _decay(cs_col, cs_row, h: int, causal):
    """``exp(cs_i - cs_j)`` for ``j <= i``, else 0: head ``h``'s ``[Q,Q]``."""
    seg = cs_col[:, h:h + 1] - cs_row[h:h + 1, :]
    return jnp.exp(jnp.where(causal, seg, _MASKED))


def _cols(rows):
    """``rows [R,Q]`` float32 as columns ``[Q,R]``: padded to a whole
    ``[128,Q]`` tile and transposed there."""
    r, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((-r % _LANE, q), rows.dtype)], axis=0).T[:, :r]


def _causal(q: int):
    return lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _mm(a, b, dims=None):
    """A product with float32 accumulation; ``dims`` as ``_NT``/``_TN``."""
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=F32)
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _wide(cols, tiles):
    """``cols [Q,R]`` over the group's lanes ``[Q,R*P]``."""
    q = cols.shape[0]
    return jnp.concatenate(
        [_spread(cols, heads, masks, (q, lanes.stop - lanes.start))
         for lanes, heads, masks in tiles], axis=1)


def _by_head(v, masks, cd):
    """A tile's ``[Q,width]`` float32 as one ``[Q,width]`` a head, zero
    outside the head's lanes, stacked along rows, in the compute type."""
    return jnp.concatenate(
        [(v if m is None else jnp.where(m, v, 0.0)).astype(cd)
         for m in masks], axis=0)


def _fwd_kernel(*refs, q: int, p: int, eps: float, norm: bool):
    """With the norm (``norm``): ``x, b, c, dt, cs, d, z, w``, the result
    ``o``, then where they are saved float32 ``y`` and the entering states'
    blocks and the running state's scratch, where they are not the scratch
    and one for a chunk's ``y``.  Without it: ``x, b, c, dt, cs, d``, the
    result float32 ``y``, where they are saved the entering states' blocks,
    then the scratch."""
    if norm:
        x_ref, b_ref, c_ref, dt_ref, cs_ref, d_ref, z_ref, w_ref, o_ref, \
            *rest = refs
        save = len(rest) == 3
        y_ref, h_ref, ht_ref = rest if save else (rest[1], None, rest[0])
    else:
        x_ref, b_ref, c_ref, dt_ref, cs_ref, d_ref, y_ref, *rest = refs
        save = len(rest) == 2
        h_ref, ht_ref = rest if save else (None, rest[0])
    steps, r = dt_ref.shape[0], dt_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ht_ref[...] = jnp.zeros_like(ht_ref)

    causal = _causal(q)
    tiles = _lane_tiles(r, p, q)

    def chunk(k, _):
        rows = pl.ds(pl.multiple_of(k * q, q), q)
        y_rows = rows if save or not norm else slice(None)
        bm, cm = b_ref[rows, :], c_ref[rows, :]              # [Q,N]
        cd = bm.dtype
        dt_row, cs_row = dt_ref[k], cs_ref[k]                # [R,Q]
        dt_col, cs_col = _cols(dt_row), _cols(cs_row)        # [Q,R]
        cb = _mm(cm, bm, _NT)
        e_col = jnp.exp(cs_col)
        f_col = jnp.exp(cs_col[q - 1:q, :] - cs_col) * dt_col
        for lanes, heads, masks in tiles:
            x_t = x_ref[rows, lanes]
            # The tile's heads' M stacked along rows against its x, each
            # head's lanes then picked.
            mm = jnp.concatenate(
                [((_decay(cs_col, cs_row, h, causal) * dt_row[h:h + 1, :])
                  * cb).astype(cd) for h in heads], axis=0)
            y_h = _mm(mm, x_t)                               # [heads*Q,width]
            y_t = y_h[:q]
            for i, m in enumerate(masks[1:], 1):
                y_t = jnp.where(m, y_h[i * q:(i + 1) * q], y_t)
            enter = ht_ref[:, lanes]                         # [N,width]
            if save:
                h_ref[k, :, lanes] = enter
            e_t = _spread(e_col, heads, masks, y_t.shape)
            x_f = x_t.astype(F32)
            y_ref[y_rows, lanes] = y_t + _mm(cm, enter.astype(cd)) * e_t \
                + d_ref[:, lanes] * x_f
            xw = (x_f * _spread(f_col, heads, masks, x_f.shape)).astype(cd)
            ht_ref[:, lanes] = e_t[q - 1:q, :] * enter + _mm(bm, xw, _TN)
        if not norm:
            return
        # The gate and the group's norm: the group's lanes are the norm's.
        z = z_ref[rows, :].astype(F32)
        u = y_ref[y_rows, :] * (z * jax.nn.sigmoid(z))
        u = u * lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        o_ref[rows, :] = (u * w_ref[...]).astype(o_ref.dtype)

    lax.fori_loop(0, steps, chunk, None)


def _bwd_kernel(*refs, q: int, p: int, eps: float, norm: bool):
    """With the norm (``norm``): ``x, b, c, dt, cs, d, z, w``, the entering
    states, float32 ``y`` and ``do``; then ``dx, db, dc, ddt, dcs, dd, dz,
    dw``.  Without it: ``x, b, c, dt, cs, d``, the entering states and
    float32 ``dy``; then ``dx, db, dc, ddt, dcs, dd``.  Last the scratch:
    ``dH`` and two ``[Q,128]`` in which the heads' columns are gathered, a
    lane a head, to be transposed into rows."""
    if norm:
        (x_ref, b_ref, c_ref, dt_ref, cs_ref, d_ref, z_ref, w_ref, h_ref,
         y_ref, do_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref, dd_ref,
         dz_ref, dw_ref, dht_ref, dcs_col_ref, df_col_ref) = refs
    else:
        (x_ref, b_ref, c_ref, dt_ref, cs_ref, d_ref, h_ref, dy_ref, dx_ref,
         db_ref, dc_ref, ddt_ref, dcs_ref, dd_ref, dht_ref, dcs_col_ref,
         df_col_ref) = refs
    steps, r = dt_ref.shape[0], dt_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dht_ref[...] = jnp.zeros_like(dht_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)
        if norm:
            dw_ref[...] = jnp.zeros_like(dw_ref)

    causal = _causal(q)
    tiles = _lane_tiles(r, p, q)

    def chunk(i, _):
        k = steps - 1 - i
        rows = pl.ds(pl.multiple_of(k * q, q), q)
        if norm:
            # Through the norm and the gate: o = u rs w, u = y silu(z), rs
            # = rsqrt(mean(u^2) + eps) over the group's lanes.
            y = y_ref[rows, :]
            z = z_ref[rows, :].astype(F32)
            sig = jax.nn.sigmoid(z)
            gate, dgate = z * sig, sig * (1.0 + z * (1.0 - sig))
            u = y * gate
            rs = lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
            do = do_ref[rows, :].astype(F32)
            dw_ref[...] += jnp.sum(do * (u * rs), axis=0, keepdims=True)
            dn = do * w_ref[...]
            du = rs * dn - u * (rs * rs * rs
                                * jnp.mean(dn * u, axis=1, keepdims=True))
            dz_ref[rows, :] = (du * y * dgate).astype(dz_ref.dtype)
            dy = du * gate                                   # [Q,R*P]
        else:
            dy = dy_ref[rows, :]

        bm, cm = b_ref[rows, :], c_ref[rows, :]
        cd = bm.dtype
        dt_row, cs_row = dt_ref[k], cs_ref[k]
        dt_col, cs_col = _cols(dt_row), _cols(cs_row)
        cb = _mm(cm, bm, _NT)
        e_col = jnp.exp(cs_col)
        g_col = jnp.exp(cs_col[q - 1:q, :] - cs_col)
        f_col = g_col * dt_col
        e, f = _wide(e_col, tiles), _wide(f_col, tiles)
        x_f = x_ref[rows, :].astype(F32)
        enter = h_ref[k]                                     # [N,R*P]
        enter_c = enter.astype(cd)
        # The entering state's read-out: y += exp(cs) (C H).
        dye = dy * e
        dy_in = dye.astype(cd)
        dc = _mm(dy_in, enter_c, _NT)                        # [Q,N]
        read_out = dye * _mm(cm, enter_c)
        # The chunk's own state: S = B^T xw, H <- exp(cs_last) H + S.
        ds = dht_ref[...]
        ds_c = ds.astype(cd)
        dxw = _mm(bm, ds_c)                                  # [Q,R*P]
        db = _mm((x_f * f).astype(cd), ds_c, _NT)            # [Q,N]
        through_x = dxw * x_f
        decayed = jnp.sum(ds * enter, axis=0, keepdims=True)
        dht_ref[...] = e[q - 1:q, :] * ds + _mm(cm, dy_in, _TN)
        dd_ref[...] += jnp.sum(dy * x_f, axis=0, keepdims=True)
        dx = dxw * f + d_ref[...] * dy
        # Inside the chunk: y += M x, M = (decay dt_j) C B^T.
        dcb = jnp.zeros((q, q), F32)
        da = {}
        for lanes, heads, masks in tiles:
            decay = [_decay(cs_col, cs_row, h, causal) for h in heads]
            ldt = [dec * dt_row[h:h + 1, :] for dec, h in zip(decay, heads)]
            # Outside a head's lanes its dy is zero, so M^T dy adds nothing
            # there and dy x^T sums over the head alone.
            dy_h = _by_head(dy[:, lanes], masks, cd)         # [heads*Q,width]
            dx_ref[rows, lanes] = (dx[:, lanes] + _mm(
                jnp.concatenate([(l * cb).astype(cd) for l in ldt], axis=0),
                dy_h, _TN)).astype(dx_ref.dtype)
            dm_h = _mm(dy_h, x_ref[rows, lanes], _NT)        # [heads*Q,Q]
            inter = _head_sums(read_out[:, lanes], masks)
            df = _head_sums(through_x[:, lanes], masks)
            da_t = _head_sums(decayed[:, lanes], masks)
            for i, h in enumerate(heads):
                dm = dm_h[i * q:(i + 1) * q]
                dcb += dm * ldt[i]
                w = dm * cb * decay[i]
                ddt_ref[k, h:h + 1, :] = jnp.sum(w, axis=0, keepdims=True)
                dcs_col_ref[:, h:h + 1] = inter[i] + jnp.sum(
                    w * dt_row[h:h + 1, :], axis=1, keepdims=True)
                df_col_ref[:, h:h + 1] = df[i]
                da[h] = da_t[i]
        dcb_c = dcb.astype(cd)
        dc_ref[rows, :] = (dc + _mm(dcb_c, bm)).astype(dc_ref.dtype)
        db_ref[rows, :] = (db + _mm(dcb_c, cm, _TN)).astype(db_ref.dtype)
        # Rows: dt_j and cs_j of the decay.  Columns: cs_i of the decay and
        # of the read-out; dt_j and cs_j of xw's weight exp(cs_last - cs_j)
        # dt_j; cs_last, in it and in the state's decay exp(cs_last).
        df_col = df_col_ref[:, :r]
        through_f = df_col * f_col
        at_last = jnp.sum(through_f, axis=0, keepdims=True) \
            + e_col[q - 1:q, :] * _place(da, (1, r), 1)
        last = lax.broadcasted_iota(jnp.int32, (q, r), 0) == q - 1
        dcs_col_ref[:, :r] = dcs_col_ref[:, :r] - through_f \
            + jnp.where(last, jnp.broadcast_to(at_last, (q, r)), 0.0)
        df_col_ref[:, :r] = df_col * g_col
        ddt_row = ddt_ref[k]
        ddt_ref[k] = ddt_row + df_col_ref[...].T[:r]
        dcs_ref[k] = dcs_col_ref[...].T[:r] - ddt_row * dt_row

    lax.fori_loop(0, steps, chunk, None)


# -- the calls ----------------------------------------------------------------

def _check(x, dt, cs, b, c, d_row, z, w_row, g: int, q: int):
    """The flat operands' sizes: ``x``, ``z`` [B,T,H*P], ``b``/``c``
    [B,T,G*N], ``d_row``/``w_row`` [1,H*P]; ``z`` and ``w_row`` None where
    the norm is not the kernels'."""
    bsz, t, hp = x.shape
    h, n = dt.shape[-1], b.shape[-1] // g
    p = hp // h
    if z is None:
        z, w_row = x, d_row
    if dt.shape != (bsz, t, h) or cs.shape != dt.shape \
            or b.shape != (bsz, t, g * n) or c.shape != b.shape \
            or z.shape != x.shape or d_row.shape != (1, hp) \
            or w_row.shape != (1, hp) or hp != h * p:
        raise ValueError(
            f"ssd_scan: x {x.shape} wants z the same, dt and cs "
            f"[{bsz},{t},H], b and c [{bsz},{t},{g}*N], D and the norm's "
            f"weight [1,{hp}], got {z.shape}, {dt.shape}, {cs.shape}, "
            f"{b.shape}, {c.shape}, {d_row.shape}, {w_row.shape}")
    if not _whole(t, h, p, g, n, q):
        raise ValueError(
            f"ssd_scan: t={t} in chunks of {q}, {h} heads of {p} in {g} "
            f"groups, state {n} are not whole chunks and lanes of {_LANE}")
    return bsz, t, h, p, n


def _rows(v, g: int, q: int):
    """``[B,T,H]`` float32 as ``[B,G,nc,R,Q]``: a head's chunk a row."""
    bsz, t, h = v.shape
    return v.reshape(bsz, t // q, q, g, h // g).transpose(0, 3, 1, 4, 2)


def _from_rows(v):
    bsz, g, nc, r, q = v.shape
    return v.transpose(0, 2, 4, 1, 3).reshape(bsz, nc * q, g * r)


def _specs(q: int, rp: int, n: int, r: int, steps: int, block,
           split: int = 1):
    """Block specs over the grid ``(sequence, group, block of chunks)``
    (``split`` > 1: ``(sequence, tile of a group's heads, block of
    chunks)``, a group's ``B``, ``C`` read by each of its ``split``
    tiles); ``block(i)`` is the block of chunks grid step ``i`` works on,
    ``rp`` and ``r`` a grid step's lanes and heads."""
    wide = pl.BlockSpec((None, steps * q, rp),
                        lambda s, g, i: (s, block(i), g))
    state = pl.BlockSpec((None, steps * q, n),
                         (lambda s, g, i: (s, block(i), g)) if split == 1
                         else lambda s, j, i: (s, block(i), j // split))
    row = pl.BlockSpec((None, None, steps, r, q),
                       lambda s, g, i: (s, g, block(i), 0, 0))
    lane = pl.BlockSpec((1, rp), lambda s, g, i: (0, g))
    enter = pl.BlockSpec((None, None, steps, n, rp),
                         lambda s, g, i: (s, g, block(i), 0, 0))
    return wide, state, row, lane, enter


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _forward(x, dt, cs, b, c, d_row, z, w_row, g: int, q: int, eps: float,
             steps: int, save: bool, interpret: bool, split: int = 1):
    """The gated, normed ``o [B,T,H*P]`` in ``x``'s type and, where
    ``save``, float32 ``y [B,T,H*P]`` and the state entering each chunk
    ``f32[B,G,nc,N,R*P]`` (else two None).  ``split`` > 1: a group's heads
    in that many tiles (``z``, ``w_row`` None), no gate nor norm: float32
    ``y`` and, where ``save``, the entering states ``f32[B,G*split,nc,N,
    R*P/split]`` (else None)."""
    bsz, t, h, p, n = _check(x, dt, cs, b, c, d_row, z, w_row, g, q)
    r, nc = h // g // split, t // q
    gs = g * split
    steps = _steps(nc, steps)
    wide, state, row, lane, enter = _specs(
        q, r * p, n, r, steps, lambda i: i, split)
    vma = _varies(*(v for v in (x, dt, cs, b, c, d_row, z, w_row)
                    if v is not None))
    out_specs = [wide]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype if split == 1
                                      else F32, vma=vma)]
    scratch = [pltpu.VMEM((n, r * p), F32)]
    entering = jax.ShapeDtypeStruct((bsz, gs, nc, n, r * p), F32, vma=vma)
    if split > 1:
        operands = ()
        if save:
            out_specs.append(enter)
            out_shape.append(entering)
    elif save:
        operands = (z, w_row)
        out_specs += [wide, enter]
        out_shape += [jax.ShapeDtypeStruct(x.shape, F32, vma=vma), entering]
    else:
        operands = (z, w_row)
        scratch.append(pltpu.VMEM((q, r * p), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, q=q, p=p, eps=eps, norm=split == 1),
        grid=(bsz, gs, nc // steps),
        in_specs=[wide, state, state, row, row, lane] + [wide, lane][
            :len(operands)],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=_params(),
        name="ssd_fwd",
        interpret=interpret,
    )(x, b, c, _rows(dt, gs, q), _rows(cs, gs, q), d_row, *operands)
    if split > 1:
        return out[0], out[1] if save else None
    return tuple(out) if save else (out[0], None, None)


def _backward(x, dt, cs, b, c, d_row, z, w_row, y, entering, do, g: int,
              q: int, eps: float, steps: int, interpret: bool,
              split: int = 1):
    """``(dx, ddt, dcs, db, dc, dd_row, dz, dw_row)``; ``db`` and ``dc``
    are summed over a group's heads inside the kernel.  ``split`` > 1:
    ``z``, ``w_row``, ``y`` None and ``do`` float32 ``dy``, no gate nor
    norm: ``(dx, ddt, dcs, db, dc, dd_row)``, ``db`` and ``dc`` a tile's
    float32 sums out of the kernel and added up over the group's tiles
    here."""
    bsz, t, h, p, n = _check(x, dt, cs, b, c, d_row, z, w_row, g, q)
    r, nc = h // g // split, t // q
    gs = g * split
    steps = _steps(nc, steps)
    blocks = nc // steps
    wide, state, row, lane, enter = _specs(
        q, r * p, n, r, steps, lambda i: blocks - 1 - i, split)
    sums = pl.BlockSpec((None, None, 1, r * p), lambda s, g, i: (s, g, 0, 0))
    vma = _varies(*(v for v in (x, dt, cs, b, c, d_row, z, w_row, y,
                                entering, do) if v is not None))

    def shape(dims, dtype=F32):
        return jax.ShapeDtypeStruct(dims, dtype, vma=vma)

    rows = (shape((bsz, gs, nc, r, q)), shape((bsz, gs, nc, r, q)),
            shape((bsz, gs, 1, r * p)))
    dt_rows, cs_rows = _rows(dt, gs, q), _rows(cs, gs, q)
    if split == 1:
        in_specs = [wide, state, state, row, row, lane, wide, lane, enter,
                    wide, wide]
        operands = (z, w_row, entering, y, do.astype(x.dtype))
        out_specs = [wide, state, state, row, row, sums, wide, sums]
        out_shape = [shape(x.shape, x.dtype), shape(b.shape, b.dtype),
                     shape(c.shape, c.dtype), *rows,
                     shape(z.shape, z.dtype), shape((bsz, g, 1, r * p))]
    else:
        in_specs = [wide, state, state, row, row, lane, enter, wide]
        operands = (entering, do.astype(F32))
        part = pl.BlockSpec((None, steps * q, n),
                            lambda s, j, i: (s, blocks - 1 - i, j))
        out_specs = [wide, part, part, row, row, sums]
        out_shape = [shape(x.shape, x.dtype), shape((bsz, t, gs * n)),
                     shape((bsz, t, gs * n)), *rows]
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, q=q, p=p, eps=eps, norm=split == 1),
        grid=(bsz, gs, blocks),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, r * p), F32),
                        pltpu.VMEM((q, _LANE), F32),
                        pltpu.VMEM((q, _LANE), F32)],
        compiler_params=_params(),
        name="ssd_bwd",
        interpret=interpret,
    )(x, b, c, dt_rows, cs_rows, d_row, *operands)
    dx, db, dc, ddt, dcs, dd = out[:6]
    common = (dx, _from_rows(ddt), _from_rows(dcs))
    if split > 1:
        db, dc = (v.reshape(bsz, t, g, split, n).sum(axis=3).reshape(
            b.shape).astype(b.dtype) for v in (db, dc))
        return common + (db, dc, dd.sum(axis=0).reshape(1, h * p))
    return common + (db, dc, dd.sum(axis=0).reshape(1, h * p), out[6],
                     out[7].sum(axis=0).reshape(1, h * p))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _ssd(x, dt, cs, b, c, d_row, z, w_row, g: int, q: int, eps: float,
         interpret: bool):
    return _forward(x, dt, cs, b, c, d_row, z, w_row, g, q, eps, FWD_CHUNKS,
                    False, interpret)[0]


def _ssd_fwd(x, dt, cs, b, c, d_row, z, w_row, g, q, eps, interpret):
    o, y, entering = _forward(x, dt, cs, b, c, d_row, z, w_row, g, q, eps,
                              FWD_CHUNKS, True, interpret)
    return o, (x, dt, cs, b, c, d_row, z, w_row, y, entering)


def _ssd_bwd(g, q, eps, interpret, res, do):
    return _backward(*res, do, g, q, eps, BWD_CHUNKS, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _scan_tiled(x, dt, cs, b, c, d_row, g: int, split: int, q: int,
                interpret: bool):
    """Float32 ``y`` of a group wider than a grid step, ``split`` tiles of
    its heads: the scan alone (:func:`_forward`'s ``split``)."""
    return _forward(x, dt, cs, b, c, d_row, None, None, g, q, 0.0,
                    FWD_CHUNKS, False, interpret, split)[0]


def _scan_tiled_fwd(x, dt, cs, b, c, d_row, g, split, q, interpret):
    y, entering = _forward(x, dt, cs, b, c, d_row, None, None, g, q, 0.0,
                           FWD_CHUNKS, True, interpret, split)
    return y, (x, dt, cs, b, c, d_row, entering)


def _scan_tiled_bwd(g, split, q, interpret, res, dy):
    x, dt, cs, b, c, d_row, entering = res
    return _backward(x, dt, cs, b, c, d_row, None, None, None, entering, dy,
                     g, q, 0.0, BWD_CHUNKS, interpret, split)


_scan_tiled.defvjp(_scan_tiled_fwd, _scan_tiled_bwd)


def ssd_scan(x, dt, a, b, c, d_skip, z, weight, chunk: int, eps: float,
             interpret: bool = False):
    """``gated_norm(ssd_chunked(x, dt, a, b, c, d_skip), z, weight)`` of
    models/nemotron_h.py through the kernels: ``x`` [B,T,H,P] and
    ``b``/``c`` [B,T,G,N] in the compute type, ``dt`` [B,T,H] float32,
    ``a`` and ``d_skip`` [H], the gate ``z`` [B,T,H*P] and the norm's
    ``weight`` [H*P], whose groups are the scan's ``G``; the result
    [B,T,H*P] in ``x``'s type.  ``T`` is whole chunks.  A group of more
    heads than a grid step takes (:func:`_head_tile`) is scanned a tile of
    them at a time and gated and normed after the kernels, in XLA.
    ``interpret`` runs the kernels in the Pallas interpreter (the CPU
    tests)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if dt.shape != (bsz, t, h) or b.shape != (bsz, t, g, n) \
            or c.shape != b.shape or z.shape != (bsz, t, h * p) \
            or weight.shape != (h * p,) or t % chunk:
        raise ValueError(
            f"ssd_scan: x {x.shape} wants dt {(bsz, t, h)}, b and c "
            f"[{bsz},{t},G,N], z {(bsz, t, h * p)}, the norm's weight "
            f"{(h * p,)} and whole chunks of {chunk}, got {dt.shape}, "
            f"{b.shape}, {c.shape}, {z.shape}, {weight.shape}")
    dt = dt.astype(F32)
    cs = jnp.cumsum((dt * a).reshape(bsz, t // chunk, chunk, h),
                    axis=2).reshape(bsz, t, h)
    # Inside shard_map a parameter does not vary over the mesh; what a
    # kernel reads beside the data must, and its cotangent is then summed
    # over the mesh by the cast's own transpose.
    vma = _varies(x, dt, b, c, z)

    def row(v):
        v = v.astype(F32)[None, :]
        missing = tuple(vma - jax.typeof(v).vma)
        return lax.pcast(v, missing, to="varying") if missing else v

    d_row = row(jnp.repeat(d_skip, p))
    split = h // g // _head_tile(h // g, p)
    if split > 1:
        from ..models.nemotron_h import gated_norm
        y = _scan_tiled(x.reshape(bsz, t, h * p), dt, cs,
                        b.reshape(bsz, t, g * n), c.reshape(bsz, t, g * n),
                        d_row, g, split, chunk, interpret)
        return gated_norm(y, z, weight, g, eps, x.dtype)
    w_row = row(weight)
    # The kernels take a token's heads and groups flat, as the mixer's
    # projection leaves them: no [.., 64]-wide minor dimension exists.
    return _ssd(x.reshape(bsz, t, h * p), dt, cs, b.reshape(bsz, t, g * n),
                c.reshape(bsz, t, g * n), d_row, z.astype(x.dtype), w_row,
                g, chunk, float(eps), interpret)


# -- python -m ddp_tpu.ops.ssd --------------------------------------------------

SWEEP = (1, 2, 4, 8, 16)
_NAMES = ("o", "dx", "ddt", "da", "db", "dc", "dd", "dz", "dw")
_EPS = 1e-5


def _operands(bsz: int, t: int, h: int, p: int, g: int, n: int, seed=0):
    """``(x, dt, a, b, c, d_skip, z, weight)`` and a cotangent for the
    result, float32: ``dt`` and ``a`` in the initialiser's ranges."""
    ks = jax.random.split(jax.random.key(seed), 10)
    x = jax.random.normal(ks[0], (bsz, t, h, p), F32)
    z, w = (jax.random.normal(k, (bsz, t, h * p), F32) for k in ks[1:3])
    b, c = (jax.random.normal(k, (bsz, t, g, n), F32) for k in ks[3:5])
    dt = jax.nn.softplus(jax.random.normal(ks[5], (bsz, t, h), F32) - 3.0)
    a = -jax.random.uniform(ks[6], (h,), F32, 1.0, 16.0)
    weight = 1.0 + 0.1 * jax.random.normal(ks[8], (h * p,), F32)
    return (x, dt, a, b, c, jax.random.normal(ks[7], (h,), F32), z,
            weight), w


def _low(args, cd):
    """The operands as the mixer hands them over: ``x``, ``b``, ``c``, ``z``
    in the compute type, the rest float32."""
    x, dt, a, b, c, d, z, weight = args
    return x.astype(cd), dt, a, b.astype(cd), c.astype(cd), d, \
        z.astype(cd), weight


def _xla_path(x, dt, a, b, c, d, z, weight, *, chunk: int, cd):
    """What ``mamba_mixer`` runs where the kernel does not apply."""
    from ..models.nemotron_h import gated_norm, ssd_chunked
    y = ssd_chunked(x, dt, a, b, c, d, chunk, cd)
    return gated_norm(y.reshape(z.shape), z, weight, b.shape[2], _EPS, cd)


def _mixer_operands(bsz: int, t: int, h: int, p: int, g: int, n: int,
                    chunk: int, cd, d_model: int = 64):
    """``(dm, weights, x)`` for ``models.nemotron_h.mamba_mixer`` at these
    sizes, as shapes."""
    d_inner = h * p
    dm = {"d_inner": d_inner, "g": g, "n": n, "k": 4, "h": h, "p": p,
          "conv_dim": d_inner + 2 * g * n, "chunk": chunk, "eps": _EPS}
    weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
        ("in_proj", (d_model, 2 * d_inner + 2 * g * n + h)),
        ("conv_w", (4, dm["conv_dim"])), ("conv_b", (dm["conv_dim"],)),
        ("dt_bias", (h,)), ("A_log", (h,)), ("D", (h,)),
        ("gate_norm", (d_inner,)), ("out_proj", (d_inner, d_model)))}
    return dm, weights, jax.ShapeDtypeStruct((bsz, t, d_model), cd)


def _vjp_of(path):
    def run(args, w):
        o, pull = jax.vjp(path, *args)
        return (o,) + pull(w.astype(o.dtype))
    return run


# (B, T, H, P, G, N, chunk) on a TPU and through the interpreter: the first
# token cell's 8 groups of 8 heads, and the fifth's ONE group of 64 (two
# grid steps of ``HEAD_TILE`` heads off the chip).
SELF_CHECK_SHAPES = (((2, 8192, 64, 64, 8, 128, 128),
                      (2, 384, 16, 64, 2, 128, 128)),
                     ((2, 8192, 64, 64, 1, 128, 256),
                      (2, 256, 16, 64, 1, 128, 128)))


def _check_shape(bsz, t, h, p, g, n, q, *, on_chip: bool, sweep: bool):
    """One shape of :func:`_self_check`; returns the mixer's paths: the
    scan's and (``conv_``) its convolution's."""
    from ..models.nemotron_h import gated_norm, mamba_mixer
    from . import causal_conv
    from .attention import _ms, _rel

    cd = jnp.bfloat16
    split = h // g // _head_tile(h // g, p)
    print(f"ssd: B={bsz} T={t} H={h} P={p} G={g} N={n} chunk={q} "
          f"{jnp.dtype(cd).name} chunks a step fwd={FWD_CHUNKS} "
          f"bwd={BWD_CHUNKS}, a group in {split} grid step(s)", flush=True)
    args, w = _operands(bsz, t, h, p, g, n)
    forward = {
        "kernel": lambda *a: ssd_scan(*a, q, _EPS, not on_chip),
        "xla": functools.partial(_xla_path, chunk=q, cd=cd)}
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(_vjp_of(functools.partial(
            _xla_path, chunk=q, cd=F32)))(args, w)
    low = _low(args, cd)
    dist = {name: [_rel(a, b) for a, b in zip(jax.jit(_vjp_of(fn))(low, w),
                                              exact)]
            for name, fn in forward.items()}
    del exact
    print("ssd: distance from float32" + "".join(
        f"{name:>9}" for name in _NAMES))
    for name, d in dist.items():
        print(f"ssd:   {name:<23}" + "".join(f"{x:9.5f}" for x in d),
              flush=True)
    for what, got, ref in zip(_NAMES, dist["kernel"], dist["xla"]):
        if got > 1.25 * ref:
            raise RuntimeError(
                f"ssd_scan's {what} is {got:.5f} from the float32 answer, "
                f"the XLA path {ref:.5f}: further by more than a quarter")

    if on_chip:
        print("ssd: ms            forward  forward+backward")
        for name, fn in forward.items():
            print(f"ssd:   {name:<8}{_ms(jax.jit(fn), *low):11.2f}"
                  f"{_ms(jax.jit(_vjp_of(fn)), low, w):11.2f}", flush=True)
    if on_chip and split > 1:
        # What the gate and the norm over a group wider than a grid step
        # cost in XLA, beside the kernels' part.
        y = jnp.zeros((bsz, t, h * p), F32)
        x, dt, a, b, c, d, z, weight = low
        norm = functools.partial(gated_norm, groups=g, eps=_EPS,
                                 out_dtype=cd)
        print(f"ssd:   {'norm':<8}{_ms(jax.jit(norm), y, z, weight):11.2f}"
              f"{_ms(jax.jit(_vjp_of(norm)), (y, z, weight), w):11.2f}  "
              "(the gate and the norm in XLA, of the kernel's line)",
              flush=True)
    if on_chip and sweep:
        x, dt, a, b, c, d, z, weight = low
        cs = jnp.cumsum((dt * a).reshape(bsz, t // q, q, h),
                        axis=2).reshape(bsz, t, h)
        core = (x.reshape(bsz, t, h * p), dt, cs, b.reshape(bsz, t, g * n),
                c.reshape(bsz, t, g * n), jnp.repeat(d, p)[None, :], z,
                weight[None, :])
        kw = dict(g=g, q=q, eps=_EPS, interpret=False)
        _, y, entering = jax.jit(functools.partial(
            _forward, steps=FWD_CHUNKS, save=True, **kw))(*core)
        print("ssd: the kernels alone, sweep (chunks a grid step): ms "
              "forward, forward that saves y and the entering states, "
              "backward")
        for steps in SWEEP:
            f, fs = (jax.jit(functools.partial(
                _forward, steps=steps, save=save, **kw))
                for save in (False, True))
            bw = jax.jit(functools.partial(_backward, steps=steps, **kw))
            print(f"ssd:   {steps:<6}{_ms(f, *core):9.2f}"
                  f"{_ms(fs, *core):9.2f}"
                  f"{_ms(bw, *core, y, entering, w.astype(cd)):9.2f}",
                  flush=True)
    # The mixer itself at this shape: which path its scan and its
    # convolution are traced through.
    before, conv_before = dict(TRACED), dict(causal_conv.TRACED)
    dm, weights, x_in = _mixer_operands(bsz, t, h, p, g, n, q, cd)
    jax.eval_shape(lambda w, x: mamba_mixer(w, x, dm, cd), weights, x_in)
    path = {k: TRACED[k] - before[k] for k in TRACED}
    path.update({f"conv_{k}": causal_conv.TRACED[k] - conv_before[k]
                 for k in causal_conv.TRACED})
    print(f"ssd: mixer traced through {path}", flush=True)
    return path


def _self_check() -> None:
    """On a TPU, at the token cells' shapes (2 sequences of 8,192 tokens,
    64 heads of 64 in 8 groups with chunks of 128 and in ONE group with
    chunks of 256, state 128, bf16): each path's distance from the float32
    answer (the result and the eight gradients), milliseconds forward and
    forward plus backward for the kernel and the XLA path (at one group
    also the gate and the norm alone, which follow the kernels in XLA
    there), and at the first shape the sweep of chunks a grid step.
    Elsewhere: small shapes through the interpreter, distances only.
    Raises where the kernel is further from float32 than the XLA path by
    more than a quarter, or, on a TPU, where the mixer did not take it or
    its convolution did not take ops/causal_conv.py's."""
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = _use_pallas()
    for i, shapes in enumerate(SELF_CHECK_SHAPES):
        shape = shapes[0 if on_chip else 1]
        path = _check_shape(*shape, on_chip=on_chip, sweep=i == 0)
        if on_chip and path != {"kernel": 1, "xla": 0, "conv_kernel": 1,
                                "conv_xla": 0}:
            raise RuntimeError(f"mamba_mixer did not take the kernels at "
                               f"{shape} on a TPU")
        print(f"ssd: ok kernel={'pallas' if on_chip else 'interpret'} "
              f"B, T, H, P, G, N, chunk = {shape} within a quarter of the "
              f"XLA path's distance from float32", flush=True)


if __name__ == "__main__":
    # Through the module as the model imports it, not this second copy of
    # it: ``TRACED`` is the one the mixer adds to.
    from ddp_tpu.ops import ssd
    ssd._self_check()
