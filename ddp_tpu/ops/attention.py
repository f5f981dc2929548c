"""Causal grouped-query attention as a blocked Pallas kernel: a tile's
scores live and die in VMEM.

``causal_gqa(q [P,R,T,hd], k [P,T,hd], v [P,T,hd], scale)``: ``P`` is
(sequence, key-value head) pairs and ``R`` the query heads that share a
key-value head.  Nothing is repeated ``R`` times.  In plain XLA
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

Precision is ``_attend``'s: operands as they come, float32 accumulation
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

Who takes the kernel is read off the input (:func:`kernel_applies`): a
TPU backend, whole lanes, whole blocks, a pair's keys and values within
the VMEM budget.  ``python -m ddp_tpu.ops.attention`` checks it against
the XLA path and a float32 answer on whatever device the process sees.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

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


def tile_kinds(i, bq: int, bkv: int):
    """For query block ``i`` of ``bq`` rows against key tiles of ``bkv``:
    ``(clear, visited)``.  Tiles ``j < clear`` lie wholly at or before the
    block's first query (no mask), ``clear <= j < visited`` cross the
    diagonal (masked), ``j >= visited`` lie wholly past its last query
    (never computed).  ``i`` is an int or a traced int32."""
    return (i * bq + 1) // bkv, ((i + 1) * bq + bkv - 1) // bkv


def _vmem_bytes(t: int, hd: int, itemsize: int) -> int:
    """What the backward kernel holds at once: ``k``, ``v``, ``dk``, ``dv``
    blocks of a pair's whole length, each double-buffered by the pipeline,
    the float32 ``dk``/``dv`` scratch, and room for the tiles."""
    whole = t * hd
    tile = max(FWD_BLOCKS[0] * FWD_BLOCKS[1], BWD_BLOCKS[0] * BWD_BLOCKS[1])
    return 8 * whole * itemsize + 2 * whole * 4 + 8 * tile * 4


def kernel_applies(t: int, hd: int, itemsize: int = 4) -> bool:
    """Whether :func:`causal_gqa` can run a ``[.., t, hd]`` problem here:
    a TPU backend, ``hd`` whole lanes, ``t`` whole forward and backward
    blocks, and a pair's keys and values within the VMEM budget."""
    return (_use_pallas() and hd % _LANE == 0
            and all(t % b == 0 for b in FWD_BLOCKS + BWD_BLOCKS)
            and _vmem_bytes(t, hd, itemsize) <= VMEM_LIMIT_BYTES)


def _visible(i, j, bq: int, bkv: int, keys_first: bool):
    """The causal mask of tile (query block ``i``, key tile ``j``)."""
    shape = (bkv, bq) if keys_first else (bq, bkv)
    key = j * bkv + lax.broadcasted_iota(jnp.int32, shape,
                                         0 if keys_first else 1)
    query = i * bq + lax.broadcasted_iota(jnp.int32, shape,
                                          1 if keys_first else 0)
    return key <= query


def _walk(i, bq: int, bkv: int, step):
    """``step(j, masked)`` for the key tiles query block ``i`` sees."""
    clear, visited = tile_kinds(i, bq, bkv)
    lax.fori_loop(0, clear, lambda j, _: step(j, False), None)
    lax.fori_loop(clear, visited, lambda j, _: step(j, True), None)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, bq: int, bkv: int):
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
            s = jnp.where(_visible(i, j, bq, bkv, False), s, _MASKED)
        m_prev, l_prev = m_ref[...], l_ref[...]          # [bq, 128]
        m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
        p = jnp.exp(s - jnp.tile(m_next, (1, bkv // _LANE)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=1)[:, None]
        m_ref[...] = m_next
        v = v_ref[ks, :]
        acc_ref[...] = jnp.tile(alpha, (1, hd // _LANE)) * acc_ref[...] \
            + jnp.dot(p.astype(v.dtype), v, preferred_element_type=F32)

    _walk(i, bq, bkv, step)
    l = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.tile(l, (1, hd // _LANE))).astype(
        o_ref.dtype)
    # Every lane of a row holds the row's statistic; transposed, every
    # sublane holds all of them as one row.
    lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, scale: float, bq: int, bkv: int):
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
            s = jnp.where(_visible(i, j, bq, bkv, True), s, _MASKED)
        p = jnp.exp(s - lse)                             # [bkv, bq]
        dv_acc[ks, :] += jnp.dot(p.astype(do.dtype), do,
                                 preferred_element_type=F32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=F32)
        ds = (p * (dp - di) * scale).astype(q.dtype)
        dk_acc[ks, :] += jnp.dot(ds, q, preferred_element_type=F32)
        dq_acc[...] += lax.dot_general(ds, k, _TN,
                                       preferred_element_type=F32)

    _walk(i, bq, bkv, step)
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
             interpret: bool):
    """``(o [P,R,T,hd], lse f32[P,R,1,T])``."""
    bq, bkv = blocks
    p, r, t, hd = _check(q, k, v, bq, bkv)
    rows, stat, whole = _specs(t, hd, bq)
    vma = _varies(q, k, v)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bkv=bkv),
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
              interpret: bool):
    """``(dq, dk, dv)``; ``dk`` and ``dv`` are summed over the ``R``
    heads inside the kernel."""
    bq, bkv = blocks
    p, r, t, hd = _check(q, k, v, bq, bkv)
    rows, stat, whole = _specs(t, hd, bq)
    di = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)[:, :, None, :]
    vma = _varies(q, k, v, do, lse)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bkv=bkv),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_gqa(q, k, v, scale: float, interpret: bool = False):
    """Causal attention of ``R`` query heads against the one key-value
    head they share, for ``P`` (sequence, key-value head) pairs: ``q``
    [P,R,T,hd], ``k``/``v`` [P,T,hd] -> [P,R,T,hd].  ``interpret`` runs
    the kernels in the Pallas interpreter (the CPU tests)."""
    return _forward(q, k, v, scale, FWD_BLOCKS, interpret)[0]


def _causal_gqa_fwd(q, k, v, scale, interpret):
    o, lse = _forward(q, k, v, scale, FWD_BLOCKS, interpret)
    return o, (q, k, v, o, lse)


def _causal_gqa_bwd(scale, interpret, res, do):
    return _backward(*res, do, scale, BWD_BLOCKS, interpret)


causal_gqa.defvjp(_causal_gqa_fwd, _causal_gqa_bwd)


# -- python -m ddp_tpu.ops.attention ------------------------------------------

SWEEP = tuple((bq, bkv) for bq in (256, 512, 1024)
              for bkv in (256, 512, 1024))


def _xla_path(q, k, v, scale: float, cd):
    """The loop ``attention_mixer`` runs where the kernel does not apply."""
    from ..models.nemotron_h import _attend_head
    return lax.map(lambda a: _attend_head(*a, scale=scale, cd=cd), (q, k, v))


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


def _self_check() -> None:
    """On a TPU, at each cell's shape (:data:`SELF_CHECK_SHAPES`, bf16):
    each path's distance from the float32 answer (output and the three
    gradients) and milliseconds forward and forward plus backward for the
    kernel and the XLA loop; at the first shape also the shipped kernel
    and the block sweep.  Elsewhere: small shapes (two query blocks of two
    key tiles) through the interpreter, distances only.  Raises where the
    kernel is further from float32 than the XLA loop by more than a
    quarter, and where a model's mixer does not take the kernel at its
    cell's shape on a TPU."""
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = _use_pallas()
    for i, shapes in enumerate(SELF_CHECK_SHAPES):
        _check_shape(*shapes[0 if on_chip else 1], on_chip=on_chip,
                     yardsticks=on_chip and i == 0)
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
    print(f"attention: ok kernel={'pallas' if on_chip else 'interpret'} "
          + " ".join(f"P={a} R={b} T={c} hd={e}" for a, b, c, e in (
              s[0 if on_chip else 1] for s in SELF_CHECK_SHAPES))
          + " within a quarter of the XLA path's distance from float32",
          flush=True)


def _check_shape(p: int, r: int, t: int, hd: int, *, on_chip: bool,
                 yardsticks: bool) -> None:
    cd, scale = jnp.bfloat16, 1.0 / math.sqrt(hd)
    keys = jax.random.split(jax.random.key(0), 4)
    q, w = (jax.random.normal(key, (p, r, t, hd), F32)
            for key in (keys[0], keys[3]))
    k, v = (jax.random.normal(key, (p, t, hd), F32) for key in keys[1:3])
    print(f"attention: P={p} R={r} T={t} hd={hd} {jnp.dtype(cd).name} "
          f"blocks fwd={FWD_BLOCKS} bwd={BWD_BLOCKS}", flush=True)

    def vjp_of(path):
        def run(q, k, v, do):
            o, pull = jax.vjp(path, q, k, v)
            return (o,) + pull(do.astype(o.dtype))
        return run

    forward = {"kernel": lambda q, k, v: causal_gqa(q, k, v, scale,
                                                    not on_chip),
               "xla": functools.partial(_xla_path, scale=scale, cd=cd)}
    low = tuple(a.astype(cd) for a in (q, k, v, w))
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(vjp_of(functools.partial(
            _xla_path, scale=scale, cd=F32)))(q, k, v, w)
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
    _, res = jax.jit(_causal_gqa_fwd, static_argnums=(3, 4))(
        *low[:3], scale, False)
    print("attention: sweep (query block, key tile): ms forward, "
          "ms backward")
    for blocks in SWEEP:
        f = jax.jit(functools.partial(
            _forward, scale=scale, blocks=blocks, interpret=False))
        b = jax.jit(functools.partial(
            _backward, scale=scale, blocks=blocks, interpret=False))
        print(f"attention:   {blocks!s:<14}{_ms(f, *low[:3]):9.2f}"
              f"{_ms(b, *res, low[3]):9.2f}", flush=True)


if __name__ == "__main__":
    # Through the module as the model imports it, not this second copy of
    # it: ``TRACED`` is the one the mixer adds to.
    from ddp_tpu.ops import attention
    attention._self_check()
