"""Causal attention as blocked Pallas kernels: a tile's scores live and
die in VMEM.  Two forms of one algorithm (online softmax over the visited
key tiles forward, keys-first backward from the saved log-sum-exp), chosen
by what the input is (:func:`kernel_applies`), never by a switch:

``causal_gqa(q [P,R,T,hd], k [P,T,hd], v [P,T,hd], scale, window=None)``:
``P`` is (sequence, key-value head) pairs and ``R`` the query heads that
share a key-value head; keys and values are whole lanes of equal width
(``nemotron_h``'s ``attn_core`` at 128 with ``R`` = 16,
``glm4_moe_lite``'s ``mla_core`` at 256 with ``R`` = 1, ``laguna``'s
``full_core`` at 128 with ``R`` = 6 and its ``swa_core`` with ``R`` = 8
under a window of 512).  Nothing is repeated ``R`` times.  In plain XLA
(models/nemotron_h.py ``_attend``) every block of float32 scores is
written to HBM, read by the softmax, written again as probabilities and
read by the values' product, forward, under the checkpoint and backward:
the layer is bound by those bytes (8% of its roofline on the chip:
PERF.md).  Here:

- **Forward** (``causal_gqa_fwd``), grid ``(pair, query head, query
  block)``.  A pair's whole ``k`` and ``v`` are one block each, whose
  index moves with the pair alone, so they are fetched once a pair and
  stay in VMEM while its ``R x T/bq`` query blocks pass.  A query block
  walks the key tiles up to its own diagonal (:func:`tile_kinds`): tiles
  wholly before it without a mask, tiles crossing it with one, tiles past
  it never.  Online softmax over the tiles: float32 running maximum, sum
  and accumulator in VMEM scratch.  Out come ``o`` and one float32
  log-sum-exp a query row, stored as rows ``[P,R,1,T]``.
- **Backward** (``causal_gqa_bwd``), ONE kernel over the same grid,
  keys-first: a tile is ``[bkv, bq]``, so the saved log-sum-exp and ``di
  = sum(do * o)`` are rows that broadcast down it.  A tile's
  probabilities are made again from the log-sum-exp and give dQ (summed
  over the block's key tiles in scratch), dK and dV (a pair's whole
  length in float32 scratch, summed over its ``R`` heads and their query
  blocks, written once a pair).

``diff_attention(q [B,T,pairs*128], k, v [B,T,kv_pairs*128], lam, weight,
scale, eps, window)``: ``models/sambay.py``'s differential attention,
``RMSNorm(A1 V - lam A2 V) weight``, two softmax maps a pair with 64-wide
queries and keys against ONE 128-wide value, causal or over a query's last
``window`` keys.  What that form adds, each read off the input:

- **A key pair narrower than the value.**  A pair's two maps lie side by
  side as 128 lanes in what the projection writes, so ``q`` and ``k`` enter
  lane-whole and unmoved (the block specs index the pair's lanes of the
  ``[B,T,pairs*128]`` arrays: no head-major transpose, no 64-lane array in
  HBM).  Inside the kernel a map's queries are ``q`` with the other map's
  64 lanes zeroed, so that a full-depth product with the pair's keys is
  that map's scores (what a padded 64-deep product would cost).
- **Two maps that share one value** (``diff_attention_fwd``,
  ``diff_attention_bwd``; kernels of their own over the same grid, specs
  and walk, because the backward is another: ``causal_gqa``'s bodies stay
  as they were, which tests/test_attention_kernel.py holds to a golden).
  Forward the maps cannot be subtracted before their sums are known: each
  runs its own online softmax against the one value tile, and when a
  query block's tiles are done its float32 ``A1 V`` and ``A2 V`` are
  subtracted and normed where they lie (in XLA the 128-lane reduction
  moves both 168 MB results to another layout and back: 5.6 ms a layer on
  the chip, PERF.md); out come the normed rows, ``A1 V``, ``A2 V`` and two
  log-sum-exp rows.  Backward a block's prologue takes the norm's
  cotangent back to ``dO`` (and sums ``weight``'s gradient over every
  row); the maps are made again from the log-sum-exp and subtracted in
  float32, as the XLA loop subtracts them, before the ONE ``dV`` product;
  ``dP = dO V^T`` is one product for both; a map's ``dS`` goes against the
  pair's keys and lands in its own 64 lanes of dQ, and against its zeroed
  queries into its own 64 lanes of dK.  ``lam``'s gradient is the sum of
  the second map's ``sum(dO * o)`` rows, which the kernel writes.
- **A lower key bound**, which ``causal_gqa`` takes too.  With a static
  ``window`` :func:`tile_kinds` also gives the first tile that holds a
  visible key and the tiles that cross the window's lower edge (masked
  ``key > query - window``): a window layer visits two tiles of 512 a
  query block, not up to sixteen.  ``window=None`` walks the causal
  tiles, and ``causal_gqa``'s bodies are then what they were before the
  window (tests/test_attention_kernel.py holds them to a golden).

Precision is the XLA loops': operands as they come, float32 accumulation
and softmax statistics, ``scale`` applied to the float32 scores,
probabilities cast to the operands' type for the values' product;
backward, ``dS`` cast for its two products.

The structure is that of ``jax.experimental.pallas.ops.tpu.
splash_attention`` (Apache-2.0), cut down to causal, grouped, ``hd`` a
multiple of 128 and no segment ids.  The ``pallas_call`` sites are this
repo's own: the step runs inside ``shard_map`` with ``check_vma=True``,
where every ``out_shape`` must declare the mesh axes it varies over (as
``ops/gather.py`` does), and the shipped kernels fail at trace time
there.

Who takes a kernel is read off the input (:func:`kernel_applies`): a TPU
backend, whole lanes of equal width or two 64-wide maps beside a 128-wide
value, whole blocks, a pair's keys and values within the VMEM budget.
``python -m ddp_tpu.ops.attention`` checks both forms against the XLA
paths and a float32 answer on whatever device the process sees.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather import _use_pallas

F32 = jnp.float32
_LANE = 128
# (query block, key tile), forward and backward: swept on the chip at the
# cell's shape, 4 pairs x 16 heads x 8,192 x 128 in bf16 (PERF.md section 6).
FWD_BLOCKS = (512, 512)
BWD_BLOCKS = (512, 512)
# The same for :func:`diff_attention`, swept at the second token cell's
# shape, 20 pairs x 2 query pairs x 2 maps x 8,192 x (64 | 128) in bf16,
# with no window and with its 512: the fastest of the nine in all four
# (PERF.md section 6).
DIFF_FWD_BLOCKS = (512, 512)
DIFF_BWD_BLOCKS = (512, 512)
# v5e has 128 MiB of VMEM; the kernels may take this much of it.
VMEM_LIMIT_BYTES = 96 * 2**20
# Masked scores: far below any score, and finite, so that a row of a
# crossing tile that sees none of its keys gives exp(.) = 0 and no NaN.
_MASKED = -0.7 * float(jnp.finfo(F32).max)
# lhs [m,k] x rhs [n,k] -> [m,n]; lhs [k,m] x rhs [k,n] -> [m,n].
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# How many times ``models.nemotron_h.attention_mixer`` was traced through
# the kernel and through the XLA loop.  The choice is made at trace time
# from shapes and holds on every step after, so a trace-time tally says
# what the compiled program runs; tests and the chip phase read it.
TRACED = {"kernel": 0, "xla": 0}


def tile_kinds(i, bq: int, bkv: int, window: Optional[int] = None):
    """For query block ``i`` of ``bq`` rows against key tiles of ``bkv``:
    ``(clear, visited)``.  Tiles ``j < clear`` lie wholly at or before the
    block's first query (no mask), ``clear <= j < visited`` cross the
    diagonal (masked), ``j >= visited`` lie wholly past its last query
    (never computed).  ``i`` is an int or a traced int32.

    With a static ``window`` (a query sees its last ``window`` keys, itself
    counted) ``(clear, visited, first, inside)``: tiles ``j < first`` lie
    wholly before the window of the block's first query (never computed),
    tiles ``first <= j < inside`` hold a key that the block's last query no
    longer sees (masked, whatever the diagonal says of them), and what is
    left of ``j < clear`` needs no mask."""
    clear, visited = (i * bq + 1) // bkv, ((i + 1) * bq + bkv - 1) // bkv
    if window is None:
        return clear, visited
    floor0 = (lambda x: max(x, 0)) if isinstance(i, int) \
        else (lambda x: jnp.maximum(x, 0))
    return (clear, visited, floor0(i * bq + 1 - window) // bkv,
            floor0((i + 1) * bq - window + bkv - 1) // bkv)


def _two_maps(hd: int, vd: int) -> bool:
    """A pair's two 64-wide maps side by side as one row of lanes, beside
    a value as wide as the row: :func:`diff_attention`'s form."""
    return 2 * hd == vd == _LANE


def _vmem_bytes(t: int, hd: int, itemsize: int, maps: int = 1) -> int:
    """What the backward kernel holds at once: ``k``, ``v``, ``dk``, ``dv``
    blocks of a pair's whole length, each double-buffered by the pipeline,
    the float32 ``dk``/``dv`` scratch, and room for the tiles (a tile of
    scores, probabilities and their gradients for each of ``maps``)."""
    whole = t * hd
    blocks = (DIFF_FWD_BLOCKS, DIFF_BWD_BLOCKS) if maps == 2 \
        else (FWD_BLOCKS, BWD_BLOCKS)
    tile = max(bq * bkv for bq, bkv in blocks)
    return 8 * whole * itemsize + 2 * whole * 4 + 8 * maps * tile * 4


def kernel_applies(t: int, hd: int, itemsize: int = 4,
                   vd: Optional[int] = None) -> bool:
    """Whether a kernel of this module can run a problem of ``t`` tokens,
    keys ``hd`` wide and values ``vd`` wide (``hd`` where not given) here:
    a TPU backend; whole lanes of equal width (:func:`causal_gqa`) or two
    64-wide maps beside a 128-wide value (:func:`diff_attention`); ``t``
    whole forward and backward blocks of that form; and a pair's keys and
    values within the VMEM budget."""
    vd = hd if vd is None else vd
    if _two_maps(hd, vd):
        maps, blocks = 2, DIFF_FWD_BLOCKS + DIFF_BWD_BLOCKS
    elif hd == vd and hd % _LANE == 0:
        maps, blocks = 1, FWD_BLOCKS + BWD_BLOCKS
    else:
        return False
    return (_use_pallas() and all(t % b == 0 for b in blocks)
            and _vmem_bytes(t, vd, itemsize, maps) <= VMEM_LIMIT_BYTES)


def _visible(i, j, bq: int, bkv: int, keys_first: bool,
             window: Optional[int] = None):
    """The mask of tile (query block ``i``, key tile ``j``): a key at or
    before its query and, under ``window``, among the query's last
    ``window``."""
    shape = (bkv, bq) if keys_first else (bq, bkv)
    key = j * bkv + lax.broadcasted_iota(jnp.int32, shape,
                                         0 if keys_first else 1)
    query = i * bq + lax.broadcasted_iota(jnp.int32, shape,
                                          1 if keys_first else 0)
    if window is None:
        return key <= query
    return (key <= query) & (key > query - window)


def _walk(i, bq: int, bkv: int, step, window: Optional[int] = None):
    """``step(j, masked)`` for the key tiles query block ``i`` sees."""
    if window is None:
        clear, visited = tile_kinds(i, bq, bkv)
        lax.fori_loop(0, clear, lambda j, _: step(j, False), None)
        lax.fori_loop(clear, visited, lambda j, _: step(j, True), None)
        return
    clear, visited, first, inside = tile_kinds(i, bq, bkv, window)
    edge = jnp.minimum(inside, visited)
    lax.fori_loop(first, edge, lambda j, _: step(j, True), None)
    lax.fori_loop(edge, clear, lambda j, _: step(j, False), None)
    lax.fori_loop(jnp.maximum(edge, clear), visited,
                  lambda j, _: step(j, True), None)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, bq: int, bkv: int, window):
    i = pl.program_id(2)
    q = q_ref[...]
    hd = q.shape[-1]
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, masked):
        ks = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        s = lax.dot_general(q, k_ref[ks, :], _NT,
                            preferred_element_type=F32) * scale
        if masked:
            s = jnp.where(_visible(i, j, bq, bkv, False, window), s, _MASKED)
        m_prev, l_prev = m_ref[...], l_ref[...]          # [bq, 128]
        m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
        p = jnp.exp(s - jnp.tile(m_next, (1, bkv // _LANE)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=1)[:, None]
        m_ref[...] = m_next
        v = v_ref[ks, :]
        acc_ref[...] = jnp.tile(alpha, (1, hd // _LANE)) * acc_ref[...] \
            + jnp.dot(p.astype(v.dtype), v, preferred_element_type=F32)

    _walk(i, bq, bkv, step, window)
    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.tile(l, (1, hd // _LANE))).astype(
        o_ref.dtype)
    # Every lane of a row holds the row's statistic; transposed, every
    # sublane holds all of them as one row.
    lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, scale: float, bq: int, bkv: int, window):
    r, i = pl.program_id(1), pl.program_id(2)
    last = (r == pl.num_programs(1) - 1) & (i == pl.num_programs(2) - 1)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, do = q_ref[...], do_ref[...]                      # [bq, hd]
    lse, di = lse_ref[...], di_ref[...]                  # [1, bq]
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(j, masked):
        ks = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        k, v = k_ref[ks, :], v_ref[ks, :]                # [bkv, hd]
        s = lax.dot_general(k, q, _NT, preferred_element_type=F32) * scale
        if masked:
            s = jnp.where(_visible(i, j, bq, bkv, True, window), s, _MASKED)
        p = jnp.exp(s - lse)                             # [bkv, bq]
        dv_acc[ks, :] += jnp.dot(p.astype(do.dtype), do,
                                 preferred_element_type=F32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=F32)
        ds = (p * (dp - di) * scale).astype(q.dtype)
        dk_acc[ks, :] += jnp.dot(ds, q, preferred_element_type=F32)
        dq_acc[...] += lax.dot_general(ds, k, _TN,
                                       preferred_element_type=F32)

    _walk(i, bq, bkv, step, window)
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _varies(*arrays) -> frozenset:
    """The mesh axes a result of ``arrays`` varies over: inside shard_map
    (``check_vma=True``) a ``pallas_call``'s ``out_shape`` must say."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _check(q, k, v, bq: int, bkv: int) -> Tuple[int, int, int, int]:
    p, r, t, hd = q.shape
    if k.shape != (p, t, hd) or v.shape != (p, t, hd):
        raise ValueError(f"causal_gqa: q {q.shape} wants k and v "
                         f"{(p, t, hd)}, got {k.shape} and {v.shape}")
    if hd % _LANE or t % bq or t % bkv or bq % _LANE or bkv % _LANE:
        raise ValueError(f"causal_gqa: t={t}, hd={hd} are not whole blocks "
                         f"of {bq} x {bkv} and lanes of {_LANE}")
    return p, r, t, hd


def _specs(t: int, hd: int, bq: int):
    """Block specs over the grid ``(pair, head, query block)``: a query
    block's rows, its statistics as a row, and a pair's whole length."""
    rows = pl.BlockSpec((None, None, bq, hd), lambda p, r, i: (p, r, i, 0))
    stat = pl.BlockSpec((None, None, 1, bq), lambda p, r, i: (p, r, 0, i))
    whole = pl.BlockSpec((None, t, hd), lambda p, r, i: (p, 0, 0))
    return rows, stat, whole


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _forward(q, k, v, scale: float, blocks: Tuple[int, int],
             interpret: bool, window: Optional[int] = None):
    """``(o [P,R,T,hd], lse f32[P,R,1,T])``."""
    bq, bkv = blocks
    p, r, t, hd = _check(q, k, v, bq, bkv)
    rows, stat, whole = _specs(t, hd, bq)
    vma = _varies(q, k, v)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          window=window),
        grid=(p, r, t // bq),
        in_specs=[rows, whole, whole],
        out_specs=[rows, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((p, r, 1, t), F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bq, _LANE), F32),
                        pltpu.VMEM((bq, _LANE), F32),
                        pltpu.VMEM((bq, hd), F32)],
        compiler_params=_params(),
        name="causal_gqa_fwd",
        interpret=interpret,
    )(q, k, v)


def _backward(q, k, v, o, lse, do, scale: float, blocks: Tuple[int, int],
              interpret: bool, window: Optional[int] = None):
    """``(dq, dk, dv)``; ``dk`` and ``dv`` are summed over the ``R``
    heads inside the kernel."""
    bq, bkv = blocks
    p, r, t, hd = _check(q, k, v, bq, bkv)
    rows, stat, whole = _specs(t, hd, bq)
    di = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)[:, :, None, :]
    vma = _varies(q, k, v, do, lse)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          window=window),
        grid=(p, r, t // bq),
        in_specs=[rows, whole, whole, rows, stat, stat],
        out_specs=[rows, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                   jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                   jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bq, hd), F32),
                        pltpu.VMEM((t, hd), F32),
                        pltpu.VMEM((t, hd), F32)],
        compiler_params=_params(),
        name="causal_gqa_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_gqa(q, k, v, scale: float, interpret: bool = False,
               window: Optional[int] = None):
    """Causal attention of ``R`` query heads against the one key-value
    head they share, for ``P`` (sequence, key-value head) pairs: ``q``
    [P,R,T,hd], ``k``/``v`` [P,T,hd] -> [P,R,T,hd].  Under a static
    ``window`` a query sees its last ``window`` keys, itself counted, and
    a query block walks only the tiles that hold one (:func:`tile_kinds`).
    ``interpret`` runs the kernels in the Pallas interpreter (the CPU
    tests)."""
    return _forward(q, k, v, scale, FWD_BLOCKS, interpret, window)[0]


def _causal_gqa_fwd(q, k, v, scale, interpret, window):
    o, lse = _forward(q, k, v, scale, FWD_BLOCKS, interpret, window)
    return o, (q, k, v, o, lse)


def _causal_gqa_bwd(scale, interpret, window, res, do):
    return _backward(*res, do, scale, BWD_BLOCKS, interpret, window)


causal_gqa.defvjp(_causal_gqa_fwd, _causal_gqa_bwd)


# -- two maps a pair, one value: differential attention -------------------------

def _map_rows(q):
    """``q`` [bq,128] holds a pair's two maps as 64 lanes each: each map's
    queries with the other's lanes zeroed, so that a full-depth product
    with the pair's keys (laid out the same way) is that map's scores, and
    which lanes are the first map's."""
    first = lax.broadcasted_iota(jnp.int32, q.shape, 1) < q.shape[1] // 2
    zero = jnp.zeros_like(q)
    return (jnp.where(first, q, zero), jnp.where(first, zero, q)), first


# The two row-wise helpers are closed calls inside the kernels' bodies
# (Mosaic inlines them): what a body does outside its loops the Pallas
# interpreter evaluates an operation at a time, and inside shard_map with
# ``check_vma=True`` an operation between a block of the data and a literal
# is refused there; a call is taken whole.
@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(o1, o2, lam, w, *, eps: float):
    """A block's rows of the two float32 results to ``RMSNorm(o1 - lam
    o2) w`` [bq,128], ``lam`` and ``w`` as rows of lanes."""
    o = o1 - lam * o2
    return o * lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed_bwd(o1, o2, lam, w, dy, *, eps: float):
    """:func:`_normed`'s backward a block of rows: ``(do [bq,128], di
    [2,bq], dw [1,128])``: the cotangent of ``o1 - lam o2``, each map's
    ``sum(do * o)`` a query as a row like the log-sum-exp, and the rows'
    sum of ``w``'s gradient."""
    o = o1 - lam * o2
    rinv = lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
    g = dy * w
    do = rinv * (g - o * (rinv * rinv * jnp.mean(g * o, axis=1,
                                                  keepdims=True)))
    di = jnp.concatenate([jnp.sum((do * oa).T, axis=0, keepdims=True)
                          for oa in (o1, o2)])
    return do, di, jnp.sum(dy * o * rinv, axis=0, keepdims=True)


def _diff_fwd_kernel(lam_ref, w_ref, q_ref, k_ref, v_ref,
                     y_ref, o1_ref, o2_ref, lse_ref, m_ref, l_ref, acc_ref,
                     *, scale: float, eps: float, bq: int, bkv: int, window):
    i = pl.program_id(2)
    maps, _ = _map_rows(q_ref[...])
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(j, masked):
        ks = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        k, v = k_ref[ks, :], v_ref[ks, :]
        seen = _visible(i, j, bq, bkv, False, window) if masked else None
        for a, q in enumerate(maps):
            s = lax.dot_general(q, k, _NT, preferred_element_type=F32) * scale
            if masked:
                s = jnp.where(seen, s, _MASKED)
            m_prev, l_prev = m_ref[a], l_ref[a]          # [bq, 128]
            m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, bkv // _LANE)))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[a] = alpha * l_prev + p.sum(axis=1)[:, None]
            m_ref[a] = m_next
            acc_ref[a] = alpha * acc_ref[a] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=F32)

    _walk(i, bq, bkv, step, window)
    o = []
    for a, o_ref in enumerate((o1_ref, o2_ref)):
        l = l_ref[a]
        o.append(acc_ref[a] / l)
        o_ref[...] = o[a]
        lse_ref[a:a + 1, :] = (m_ref[a] + jnp.log(l)).T[:1]
    # The pair's rows are here in float32: the difference and its norm
    # cost two passes over [bq,128], where XLA moves both 168 MB results
    # to another layout and back to reduce over their lanes (PERF.md).
    y_ref[...] = _normed(*o, lam_ref[...], w_ref[...], eps=eps).astype(
        y_ref.dtype)


def _diff_bwd_kernel(lam_ref, w_ref, q_ref, k_ref, v_ref, dy_ref, o1_ref,
                     o2_ref, lse_ref, dq_ref, dk_ref, dv_ref, di_ref, dw_ref,
                     dq_acc, dk_acc, dv_acc, dw_acc,
                     *, scale: float, eps: float, bq: int, bkv: int, window):
    pair, r, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = (r == pl.num_programs(1) - 1) & (i == pl.num_programs(2) - 1)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((pair == 0) & (r == 0) & (i == 0))
    def _():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    maps, first = _map_rows(q_ref[...])
    lse = lse_ref[...]                                   # [2, bq]
    # The norm's backward, a row at a time, takes ``dy`` back to ``do``.
    do, di, dw = _normed_bwd(o1_ref[...], o2_ref[...], lam_ref[...],
                             w_ref[...], dy_ref[...].astype(F32), eps=eps)
    dw_acc[...] += dw
    do = do.astype(dy_ref.dtype)
    lam = jnp.tile(lam_ref[...], (1, bq // _LANE))       # [1, bq]
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(j, masked):
        ks = pl.ds(pl.multiple_of(j * bkv, bkv), bkv)
        k, v = k_ref[ks, :], v_ref[ks, :]                # [bkv, 128]
        seen = _visible(i, j, bq, bkv, True, window) if masked else None
        p = []
        for a, q in enumerate(maps):
            s = lax.dot_general(k, q, _NT, preferred_element_type=F32) * scale
            if masked:
                s = jnp.where(seen, s, _MASKED)
            p.append(jnp.exp(s - lse[a:a + 1]))          # [bkv, bq]
        # The one value product's map, subtracted in float32 as the XLA
        # loop subtracts it, and its gradient, which both maps share.
        dv_acc[ks, :] += jnp.dot((p[0] - lam * p[1]).astype(do.dtype), do,
                                 preferred_element_type=F32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=F32)
        for a, weight in enumerate((scale, -lam * scale)):
            ds = (p[a] * (dp - di[a:a + 1]) * weight).astype(k.dtype)
            dk_acc[ks, :] += jnp.dot(ds, maps[a], preferred_element_type=F32)
            dq_acc[a] += lax.dot_general(ds, k, _TN,
                                         preferred_element_type=F32)

    _walk(i, bq, bkv, step, window)
    # A map's dQ is right in its own 64 lanes (the other 64 hold its dS
    # against the other map's keys).
    dq_ref[...] = jnp.where(first, dq_acc[0], dq_acc[1]).astype(dq_ref.dtype)
    di_ref[...] = di

    @pl.when(last)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(last & (pair == pl.num_programs(0) - 1))
    def _():
        dw_ref[...] = dw_acc[...]


def _diff_check(q, k, v, bq: int, bkv: int) -> Tuple[int, int, int, int]:
    bsz, t, width = q.shape
    if (k.shape != v.shape or k.shape[:2] != (bsz, t) or width % _LANE
            or k.shape[2] % _LANE or width % k.shape[2]):
        raise ValueError(f"diff_attention: q {q.shape} wants k and v "
                         f"[{bsz},{t},lanes that divide {width}], got "
                         f"{k.shape} and {v.shape}")
    if t % bq or t % bkv or bq % _LANE or bkv % _LANE:
        raise ValueError(f"diff_attention: t={t} is not whole blocks of "
                         f"{bq} x {bkv}")
    kvp = k.shape[2] // _LANE
    return bsz, t, kvp, width // k.shape[2]


def _diff_specs(t: int, kvp: int, rep: int, bq: int):
    """Block specs over the grid ``(sequence x key-value pair, query pair
    of it, query block)`` into arrays that stay as the projections wrote
    them, ``[B,T,pairs*128]``: a query block's rows of one pair's lanes,
    its statistics as two rows, a key-value pair's whole length, and one
    row of lanes that every grid step shares (``lam``, the norm's weight)."""
    rows = pl.BlockSpec((None, bq, _LANE), lambda p, r, i: (
        p // kvp, i, (p % kvp) * rep + r))
    stat = pl.BlockSpec((None, None, 2, bq), lambda p, r, i: (p, r, 0, i))
    whole = pl.BlockSpec((None, t, _LANE), lambda p, r, i: (
        p // kvp, 0, p % kvp))
    lane = pl.BlockSpec((1, _LANE), lambda p, r, i: (0, 0))
    return rows, stat, whole, lane


def _lane_row(x):
    """A float32 scalar or ``[128]`` as the ``[1,128]`` block of ``lane``."""
    return jnp.broadcast_to(x, (1, _LANE))


# Jitted, both calls: a kernel's body is traced anew at every pallas_call
# site, under jit once a signature (PERF.md section 6, PR 34).
@functools.partial(jax.jit, static_argnames=("scale", "eps", "window",
                                             "blocks", "interpret"))
def _diff_forward(q, k, v, lam, weight, *, scale: float, eps: float, window,
                  blocks: Tuple[int, int], interpret: bool):
    """``(y [B,T,pairs*128], o1, o2 f32[B,T,pairs*128], lse
    f32[B*kv_pairs,R,2,T])``: the normed result in ``q``'s type, each
    map's ``softmax(q k^T) v`` and its log-sum-exp a query row."""
    bq, bkv = blocks
    bsz, t, kvp, rep = _diff_check(q, k, v, bq, bkv)
    rows, stat, whole, lane = _diff_specs(t, kvp, rep, bq)
    vma = _varies(q, k, v, lam, weight)
    o = jax.ShapeDtypeStruct(q.shape, F32, vma=vma)
    return pl.pallas_call(
        functools.partial(_diff_fwd_kernel, scale=scale, eps=eps, bq=bq,
                          bkv=bkv, window=window),
        grid=(bsz * kvp, rep, t // bq),
        in_specs=[lane, lane, rows, whole, whole],
        out_specs=[rows, rows, rows, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), o, o,
                   jax.ShapeDtypeStruct((bsz * kvp, rep, 2, t), F32,
                                        vma=vma)],
        scratch_shapes=[pltpu.VMEM((2, bq, _LANE), F32)] * 3,
        compiler_params=_params(),
        name="diff_attention_fwd",
        interpret=interpret,
    )(_lane_row(lam), _lane_row(weight), q, k, v)


@functools.partial(jax.jit, static_argnames=("scale", "eps", "window",
                                             "blocks", "interpret"))
def _diff_backward(q, k, v, lam, weight, o1, o2, lse, dy, *, scale: float,
                   eps: float, window, blocks: Tuple[int, int],
                   interpret: bool):
    """``(dq, dk, dv, di, dweight)`` given the normed result's cotangent
    ``dy`` (in the operands' type); ``dk`` and ``dv`` are summed over the
    ``R`` query pairs inside the kernel, ``dweight`` f32[1,128] over every
    row, and ``di`` f32[B*kv_pairs,R,2,T] is each map's ``sum(do * o)`` a
    query row, ``do`` the cotangent of ``o1 - lam o2`` (the second map's
    sum over everything is ``-dlam``)."""
    bq, bkv = blocks
    bsz, t, kvp, rep = _diff_check(q, k, v, bq, bkv)
    rows, stat, whole, lane = _diff_specs(t, kvp, rep, bq)
    vma = _varies(q, k, v, lam, weight, o1, o2, lse, dy)
    return pl.pallas_call(
        functools.partial(_diff_bwd_kernel, scale=scale, eps=eps, bq=bq,
                          bkv=bkv, window=window),
        grid=(bsz * kvp, rep, t // bq),
        in_specs=[lane, lane, rows, whole, whole, rows, rows, rows, stat],
        out_specs=[rows, whole, whole, stat, lane],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
                   jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
                   jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
                   jax.ShapeDtypeStruct(lse.shape, F32, vma=vma),
                   jax.ShapeDtypeStruct((1, _LANE), F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((2, bq, _LANE), F32),
                        pltpu.VMEM((t, _LANE), F32),
                        pltpu.VMEM((t, _LANE), F32),
                        pltpu.VMEM((1, _LANE), F32)],
        compiler_params=_params(),
        name="diff_attention_bwd",
        interpret=interpret,
    )(_lane_row(lam), _lane_row(weight), q, k, v, dy, o1, o2, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _diff_attention(q, k, v, lam, weight, scale, eps, window, interpret):
    return _diff_attention_fwd(q, k, v, lam, weight, scale, eps, window,
                               interpret)[0]


def _diff_attention_fwd(q, k, v, lam, weight, scale, eps, window, interpret):
    y, o1, o2, lse = _diff_forward(
        q, k, v, lam, weight, scale=scale, eps=eps, window=window,
        blocks=DIFF_FWD_BLOCKS, interpret=interpret)
    return y, (q, k, v, lam, weight, o1, o2, lse)


def _diff_attention_bwd(scale, eps, window, interpret, res, dy):
    q, k, v, lam, weight, o1, o2, lse = res
    # Inside shard_map a parameter does not vary over the mesh and the
    # data does: what the backward kernel reads beside the data must vary
    # as it does, and a parameter's gradient is the sum over the mesh of
    # what each shard finds (the cast's own transpose).
    missing = tuple(_varies(q, k, v) - jax.typeof(lam).vma)

    def varying(x):
        return lax.pcast(x, missing, to="varying") if missing else x

    dq, dk, dv, di, dw = _diff_backward(
        q, k, v, varying(lam), varying(weight), o1, o2, lse, dy, scale=scale,
        eps=eps, window=window, blocks=DIFF_BWD_BLOCKS, interpret=interpret)
    dlam, dw = -di[:, :, 1].sum(), dw[0]
    if missing:
        dlam, dw = lax.psum((dlam, dw), missing)
    return dq, dk, dv, dlam, dw


_diff_attention.defvjp(_diff_attention_fwd, _diff_attention_bwd)


def diff_attention(q, k, v, lam, weight, scale: float, eps: float,
                   window: Optional[int] = None, interpret: bool = False):
    """Differential attention with its norm, ``RMSNorm(A1 V - lam A2 V)
    weight``, with the arrays as the projections write them: ``q``
    [B,T,pairs*128] (a pair's two 64-wide maps side by side), ``k`` the
    same over the key-value pairs, ``v`` [B,T,kv_pairs*128], ``lam`` a
    scalar, ``weight`` [128] (parameters: inside shard_map they do not
    vary over the mesh) -> [B,T,pairs*128] in ``q``'s type.  ``A`` is
    the causal softmax of a map's scores, under ``window`` over a query's
    last ``window`` keys, itself counted.  Forward each map runs its own
    online softmax against the one value tile (the maps cannot be
    subtracted before their sums are known) and the float32 results are
    subtracted and normed a block of rows at a time; backward the maps
    are made again from the saved log-sum-exp and subtracted in float32
    before the one ``dV`` product, and share ``dP = dO V^T``.
    ``interpret`` runs the kernels in the Pallas interpreter (the CPU
    tests)."""
    return _diff_attention(q, k, v, lam.astype(F32), weight.astype(F32),
                           scale, eps, window, interpret)


# -- python -m ddp_tpu.ops.attention ------------------------------------------

SWEEP = tuple((bq, bkv) for bq in (256, 512, 1024)
              for bkv in (256, 512, 1024))


def _xla_path(q, k, v, scale: float, cd, window: Optional[int] = None):
    """The loop the cores run where the kernel does not apply:
    ``nemotron_h.attention_mixer``'s causal, ``laguna``'s under a
    window."""
    if window is None:
        from ..models.nemotron_h import _attend_head
        return lax.map(lambda a: _attend_head(*a, scale=scale, cd=cd),
                       (q, k, v))
    from ..models.laguna import _attend_head
    return lax.map(lambda a: _attend_head(*a, scale=scale, cd=cd,
                                          window=window), (q, k, v))


def _shipped(r: int, t: int, scale: float, fwd, bwd):
    """JAX's own splash kernel at the same blocks, a pair at a time (one
    key-value head: its MQA form): the yardstick, outside ``shard_map``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    sizes = sk.BlockSizes(
        block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[1],
        block_q_dkv=bwd[0], block_kv_dkv=bwd[1], block_kv_dkv_compute=bwd[1],
        use_fused_bwd_kernel=True)
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([sm.CausalMask((t, t))] * r), block_sizes=sizes)
    return lambda q, k, v: jax.vmap(kernel)(
        (q.astype(F32) * scale).astype(q.dtype), k, v)


def _rel(a, b) -> float:
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _ms(fn, *args, reps: int = 5) -> float:
    import time
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


# (pairs, query heads a pair, tokens, head width) the self-check runs: the
# first token cell's grouped shape, and the latent-attention cell's (every
# query head its own 256-wide keys and values: 2 sequences x 20 heads).
# The second of each pair is what a process without a TPU runs instead,
# through the interpreter.
SELF_CHECK_SHAPES = (((4, 16, 8192, 128), (2, 4, 1024, 128)),
                     ((40, 1, 8192, 256), (2, 1, 1024, 256)))


# (sequences, tokens, query pairs, key-value pairs) of the two-map form:
# the second token cell's (20 (sequence, key-value pair) units x 2 query
# pairs x 2 maps, 64 | 128 wide), each run with no window and with the
# cell's 512; and what a process without a TPU runs instead.
DIFF_SELF_CHECK_SHAPES = ((2, 8192, 20, 10), (1, 1024, 4, 2))
DIFF_WINDOW = 512

# (pairs, query heads a pair, tokens, head width, window) of the fourth
# token cell's two cores (2 sequences x 8 key-value heads): the window
# layer's R = 8 under 512 and the full layer's R = 6; and what a process
# without a TPU runs instead (a window that is no whole block).
GQA_WINDOW_SELF_CHECK_SHAPES = (((16, 8, 8192, 128, 512),
                                 (2, 4, 1024, 128, 384)),
                                ((16, 6, 8192, 128, None),
                                 (2, 3, 1024, 128, None)))


def _self_check() -> None:
    """On a TPU, at each cell's shape (:data:`SELF_CHECK_SHAPES`, bf16):
    each path's distance from the float32 answer (output and the three
    gradients) and milliseconds forward and forward plus backward for the
    kernel and the XLA loop; at the first shape also the shipped kernel
    and the block sweep; then the two-map form at the second token cell's
    shape (:data:`DIFF_SELF_CHECK_SHAPES`), with no window and with its
    512, the same way (:func:`_check_diff_shape`).  Elsewhere: small
    shapes (two query blocks of two key tiles) through the interpreter,
    distances only.  Raises where a kernel is further from float32 than
    the XLA loop by more than a quarter, and where a model's mixer does
    not take the kernel at its cell's shape on a TPU."""
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = _use_pallas()
    for i, shapes in enumerate(SELF_CHECK_SHAPES):
        _check_shape(*shapes[0 if on_chip else 1], on_chip=on_chip,
                     yardsticks=on_chip and i == 0)
    for shapes in GQA_WINDOW_SELF_CHECK_SHAPES:
        *shape, window = shapes[0 if on_chip else 1]
        _check_shape(*shape, on_chip=on_chip, yardsticks=False,
                     window=window)
    for window in (None, DIFF_WINDOW):
        _check_diff_shape(*DIFF_SELF_CHECK_SHAPES[0 if on_chip else 1],
                          window=window, on_chip=on_chip)
    # The mixers themselves at these shapes: which path they are traced
    # through.
    from ..models import glm4_moe_lite
    from ..models.nemotron_h import attention_mixer
    cd = jnp.bfloat16
    (p, r, t, hd), (p2, _, t2, hd2) = (s[0 if on_chip else 1]
                                       for s in SELF_CHECK_SHAPES)
    heads, d = 2 * r, 256
    weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
        ("q", (d, heads * hd)), ("k", (d, 2 * hd)), ("v", (d, 2 * hd)),
        ("o", (heads * hd, d)))}
    jax.eval_shape(
        lambda w, x: attention_mixer(
            w, x, {"heads": heads, "kv_heads": 2, "head_dim": hd}, cd),
        weights, jax.ShapeDtypeStruct((p // 2, t, d), cd))
    print(f"attention: mixer traced through {TRACED}", flush=True)
    if on_chip and TRACED != {"kernel": 1, "xla": 0}:
        raise RuntimeError("attention_mixer did not take the kernel at the "
                           "token cell's shape on a TPU")
    dm = {"heads": p2 // 2, "nope": hd2 - 64, "rope": 64, "qk": hd2,
          "v": hd2, "q_rank": 96, "kv_rank": 64, "eps": 1e-5}
    weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
        ("q_a", (d, 96)), ("q_norm", (96,)), ("q_b", (96, p2 // 2 * hd2)),
        ("kv_a", (d, 64 + 64)), ("kv_norm", (64,)),
        ("kv_b", (64, p2 // 2 * (2 * hd2 - 64))), ("o", (p2 // 2 * hd2, d)))}
    jax.eval_shape(
        lambda w, x: glm4_moe_lite.mla(
            w, x, dm, cd, *glm4_moe_lite.rope_angles(t2, 64, 1e6)),
        weights, jax.ShapeDtypeStruct((2, t2, d), cd))
    print(f"attention: mla traced through {glm4_moe_lite.TRACED}",
          flush=True)
    if on_chip and glm4_moe_lite.TRACED["core_kernel"] != 1:
        raise RuntimeError("glm4_moe_lite.mla did not take the kernel at "
                           "the latent-attention cell's shape on a TPU")
    from ..models import sambay
    bsz, t3, pairs, kvp = DIFF_SELF_CHECK_SHAPES[0 if on_chip else 1]
    dims = {"hd": _LANE // 2, "pairs": pairs, "kv_pairs": kvp, "eps": 1e-5}
    wide, all3 = pairs * _LANE, (pairs + 2 * kvp) * _LANE
    weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
        ("qkv", (d, all3)), ("qkv_b", (all3,)), ("lq1", (64,)),
        ("lk1", (64,)), ("lq2", (64,)), ("lk2", (64,)),
        ("sub_norm", (_LANE,)), ("o", (wide, d)), ("o_b", (d,)))}
    for window in (None, DIFF_WINDOW):
        jax.eval_shape(
            lambda w, x: sambay.self_attention(w, x, 15, dims, cd, window)[0],
            weights, jax.ShapeDtypeStruct((bsz, t3, d), cd))
    print(f"attention: sambay's cores traced through {sambay.TRACED}",
          flush=True)
    if on_chip and (sambay.TRACED["core_kernel"], sambay.TRACED["core_xla"]) \
            != (2, 0):
        raise RuntimeError("sambay.diff_core did not take the kernel at the "
                           "second token cell's shape on a TPU")
    _trace_laguna(on_chip, cd, d)
    print(f"attention: ok kernel={'pallas' if on_chip else 'interpret'} "
          + " ".join(f"P={a} R={b} T={c} hd={e}" for a, b, c, e in (
              s[0 if on_chip else 1] for s in SELF_CHECK_SHAPES))
          + "".join(f" P={a} R={b} T={c} hd={e} window={w}"
                     for a, b, c, e, w in (
                         s[0 if on_chip else 1]
                         for s in GQA_WINDOW_SELF_CHECK_SHAPES))
          + f" two maps B={bsz} T={t3} pairs={pairs} over {kvp} 64|128"
          + " within a quarter of the XLA path's distance from float32",
          flush=True)


def _trace_laguna(on_chip: bool, cd, d: int) -> None:
    """Which path ``laguna.gated_attention`` traces each kind of core
    through at the fourth token cell's shapes; on a TPU both must take the
    kernel."""
    from ..models import laguna
    rope = {"rope_theta": 1e4, "partial_rotary_factor": 1}
    for (p, r, t, hd, window), kind in zip(
            (s[0 if on_chip else 1] for s in GQA_WINDOW_SELF_CHECK_SHAPES),
            ("swa", "full")):
        dm = {"kv_heads": p // 2, "hd": hd, "window": window}
        heads = r * p // 2
        weights = {name: jax.ShapeDtypeStruct(shape, F32) for name, shape in (
            ("q", (d, heads * hd)), ("k", (d, p // 2 * hd)),
            ("v", (d, p // 2 * hd)), ("o", (heads * hd, d)),
            ("head_gate", (d, heads)))}
        jax.eval_shape(
            lambda w, x: laguna.gated_attention(
                w, x, kind, dm, cd, {kind: laguna.rope_tables(t, rope, hd)}),
            weights, jax.ShapeDtypeStruct((2, t, d), cd))
    print(f"attention: laguna's cores traced through {laguna.TRACED}",
          flush=True)
    if on_chip and (laguna.TRACED["full_kernel"], laguna.TRACED["swa_kernel"],
                    laguna.TRACED["full_xla"], laguna.TRACED["swa_xla"]) \
            != (1, 1, 0, 0):
        raise RuntimeError("laguna.gated_attention did not take the kernel "
                           "for both kinds of core at the fourth token "
                           "cell's shapes on a TPU")


def _check_shape(p: int, r: int, t: int, hd: int, *, on_chip: bool,
                 yardsticks: bool, window: Optional[int] = None) -> None:
    cd, scale = jnp.bfloat16, 1.0 / math.sqrt(hd)
    keys = jax.random.split(jax.random.key(0), 4)
    q, w = (jax.random.normal(key, (p, r, t, hd), F32)
            for key in (keys[0], keys[3]))
    k, v = (jax.random.normal(key, (p, t, hd), F32) for key in keys[1:3])
    print(f"attention: P={p} R={r} T={t} hd={hd} window={window} "
          f"{jnp.dtype(cd).name} blocks fwd={FWD_BLOCKS} bwd={BWD_BLOCKS}",
          flush=True)

    def vjp_of(path):
        def run(q, k, v, do):
            o, pull = jax.vjp(path, q, k, v)
            return (o,) + pull(do.astype(o.dtype))
        return run

    forward = {"kernel": lambda q, k, v: causal_gqa(q, k, v, scale,
                                                    not on_chip, window),
               "xla": functools.partial(_xla_path, scale=scale, cd=cd,
                                        window=window)}
    low = tuple(a.astype(cd) for a in (q, k, v, w))
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(vjp_of(functools.partial(
            _xla_path, scale=scale, cd=F32, window=window)))(q, k, v, w)
    dist = {name: [_rel(a, b) for a, b in zip(jax.jit(vjp_of(fn))(*low),
                                              exact)]
            for name, fn in forward.items()}
    del exact
    print("attention: distance from float32     o         dq        dk"
          "        dv")
    for name, d in dist.items():
        print(f"attention:   {name:<8}" + "".join(f"{x:10.5f}" for x in d),
              flush=True)
    for what, got, ref in zip(("o", "dq", "dk", "dv"), dist["kernel"],
                              dist["xla"]):
        if got > 1.25 * ref:
            raise RuntimeError(
                f"causal_gqa's {what} is {got:.5f} from the float32 answer, "
                f"the XLA path {ref:.5f}: further by more than a quarter")
    if not on_chip:
        return
    if yardsticks:
        forward["shipped"] = _shipped(r, t, scale, FWD_BLOCKS, BWD_BLOCKS)
    print("attention: ms            forward  forward+backward")
    for name, fn in forward.items():
        print(f"attention:   {name:<8}{_ms(jax.jit(fn), *low[:3]):11.2f}"
              f"{_ms(jax.jit(vjp_of(fn)), *low):11.2f}", flush=True)
    if not yardsticks:
        return
    _, res = jax.jit(_causal_gqa_fwd, static_argnums=(3, 4, 5))(
        *low[:3], scale, False, window)
    print("attention: sweep (query block, key tile): ms forward, "
          "ms backward")
    for blocks in SWEEP:
        f = jax.jit(functools.partial(
            _forward, scale=scale, blocks=blocks, interpret=False))
        b = jax.jit(functools.partial(
            _backward, scale=scale, blocks=blocks, interpret=False))
        print(f"attention:   {blocks!s:<14}{_ms(f, *low[:3]):9.2f}"
              f"{_ms(b, *res, low[3]):9.2f}", flush=True)


def _check_diff_shape(bsz: int, t: int, pairs: int, kvp: int, *, window,
                      on_chip: bool) -> None:
    """The two-map form as ``models/sambay.py`` runs it, norm and all:
    distances from float32 of the kernel's path and of the XLA loop for
    the result and its five gradients, then on a TPU milliseconds of both
    and the block sweep of the kernels alone."""
    from ..models import sambay
    cd, hd = jnp.bfloat16, _LANE // 2
    keys = jax.random.split(jax.random.key(1), 5)
    q = jax.random.normal(keys[0], (bsz, t, pairs, 2, hd), F32)
    k = jax.random.normal(keys[1], (bsz, t, kvp, 2, hd), F32)
    v = jax.random.normal(keys[2], (bsz, t, kvp, 2 * hd), F32)
    w = jax.random.normal(keys[3], (bsz, t, pairs * 2 * hd), F32)
    sub_norm = 1.0 + 0.1 * jax.random.normal(keys[4], (2 * hd,), F32)
    lam = jnp.float32(0.79)
    print(f"attention: two maps B={bsz} T={t} pairs={pairs} over {kvp} "
          f"64|128 window={window} {jnp.dtype(cd).name} blocks "
          f"fwd={DIFF_FWD_BLOCKS} bwd={DIFF_BWD_BLOCKS}", flush=True)
    kw = dict(window=window, scale=1.0 / math.sqrt(hd), gain=0.2, eps=1e-5)

    def vjp_of(path, cd):
        def run(q, k, v, lam, sub_norm, do):
            o, pull = jax.vjp(functools.partial(path, cd=cd, **kw), q, k, v,
                              lam, sub_norm)
            return (o,) + pull(do.astype(o.dtype))
        return run

    forward = {"kernel": functools.partial(sambay._core_kernel,
                                           interpret=not on_chip),
               "xla": sambay._core_loop}
    low = tuple(a.astype(cd) for a in (q, k, v)) + (lam, sub_norm, w)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(vjp_of(sambay._core_loop, F32))(q, k, v, lam,
                                                        sub_norm, w)
    dist = {name: [_rel(a, b) for a, b in zip(jax.jit(vjp_of(fn, cd))(*low),
                                              exact)]
            for name, fn in forward.items()}
    del exact
    names = ("o", "dq", "dk", "dv", "dlam", "dnorm")
    print("attention: distance from float32" + "".join(
        f"{n:>10}" for n in names))
    for name, d in dist.items():
        print(f"attention:   {name:<8}" + 21 * " "
              + "".join(f"{x:10.5f}" for x in d), flush=True)
    # lam's gradient is one nearly cancelling sum: shown, not held.
    for what, got, ref in zip(names, dist["kernel"], dist["xla"]):
        if what != "dlam" and got > 1.25 * ref:
            raise RuntimeError(
                f"diff_attention's {what} is {got:.5f} from the float32 "
                f"answer, the XLA path {ref:.5f}: further by more than a "
                "quarter")
    if not on_chip:
        return
    print("attention: ms            forward  forward+backward")
    for name, fn in forward.items():
        f = jax.jit(functools.partial(fn, cd=cd, **kw))
        print(f"attention:   {name:<8}{_ms(f, *low[:5]):11.2f}"
              f"{_ms(jax.jit(vjp_of(fn, cd)), *low):11.2f}", flush=True)
    flat = tuple(a.reshape(bsz, t, -1) for a in low[:3]) + (
        lam, sub_norm * kw["gain"])
    _, res = jax.jit(_diff_attention_fwd, static_argnums=(5, 6, 7, 8))(
        *flat, kw["scale"], kw["eps"], window, False)
    print("attention: sweep (query block, key tile): ms forward, "
          "ms backward")
    for blocks in SWEEP:
        tile = dict(scale=kw["scale"], eps=kw["eps"], window=window,
                    blocks=blocks, interpret=False)
        f = functools.partial(_diff_forward, **tile)
        b = functools.partial(_diff_backward, **tile)
        print(f"attention:   {blocks!s:<14}{_ms(f, *flat):9.2f}"
              f"{_ms(b, *res, low[5].astype(cd)):9.2f}", flush=True)


if __name__ == "__main__":
    # Through the module as the model imports it, not this second copy of
    # it: ``TRACED`` is the one the mixer adds to.
    from ddp_tpu.ops import attention
    attention._self_check()
