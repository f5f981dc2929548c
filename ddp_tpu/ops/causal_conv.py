"""The state-space mixers' causal depthwise convolution, its bias and its
``silu`` as a Pallas kernel pair.

``conv_silu(x [B,T,C], w [k,C], b [C], cd)`` is ``ops/seq.py``'s
``causal_conv_silu``: ``silu(sum_i xpad[t+i] w[i] + b)`` with ``xpad``
``x`` after ``k - 1`` zero rows, in float32, cast to ``cd``.  In plain
XLA the input is padded as float32 and the four shifted products, their
sum, the ``silu`` and the cast are passes over ``[B,T+k-1,C]``; autodiff's
backward adds a padded float32 cotangent for every shifted slice and a sum
over batch and time for every tap.  Here:

- **Forward** (``causal_conv_fwd``), grid ``(sequence, channel block, time
  block)``.  A block of ``x`` comes in in ``x``'s type with the
  ``_HALO`` rows before it (zero before the first token); the taps, the
  bias and the ``silu`` are computed in float32 in VMEM and the block is
  written in ``cd``.
- **Backward** (``causal_conv_bwd``), grid ``(channel block, sequence,
  time block)``, time walked from the last block back.  The pre-activation
  is made again from ``x``'s block and the rows before it (no float32
  residual: the residuals are ``x``, ``w`` and ``b``), ``ds = dy
  silu'(s)`` in float32, and ``dx[t] = sum_i w[i] ds[t+k-1-i]`` reads the
  first ``_HALO`` rows of the later block's ``ds``, carried in scratch.
  ``dw`` and ``db`` are float32 sums that stay in VMEM over the sequences
  and the time blocks and are written once a channel block.

Inside a grid step both walk the block a strip of ``STRIP_ROWS`` rows by
``STRIP_LANES`` lanes at a time, so that a strip's float32 values stay in
vector registers (a whole block's values at once made every operation a
pass through VMEM: PERF.md, section 6).  A shift of rows is a rotation along
sublanes of a strip with the rows before it put beside it at a whole
tile, so no slice starts inside a tile.  The arithmetic is the XLA form's:
the same float32 products and sums, the order of a sum at most changed.

Who takes the kernel is read off the shapes (:func:`kernel_applies`); the
channels a grid step (:func:`_lanes`) are the widest whole-lane divisor of
``C`` whose blocks fit the VMEM budget.  ``python -m
ddp_tpu.ops.causal_conv`` times both against the XLA form.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _varies
from .gather import _use_pallas

F32 = jnp.float32
_LANE = 128
# Rows of the neighbouring block a grid step sees: one sublane tile of bf16.
_HALO = 16
# Rows a grid step, the VMEM a grid step's blocks may take, and the rows
# and lanes the kernels' arithmetic takes at a time (``_strips``): swept on
# the chip at the three cells' shapes (PERF.md, section 6).
TIME_BLOCK = 512
STRIP_ROWS = 32
STRIP_LANES = 256
VMEM_BUDGET_BYTES = 40 * 2**20
VMEM_LIMIT_BYTES = 64 * 2**20

# How many convolutions were traced through the kernels and through the
# XLA form (as ``ssd.TRACED``).
TRACED = {"kernel": 0, "xla": 0}


def _vmem_bytes(bt: int, bc: int, itemsize: int) -> int:
    """What the backward grid step holds at once: ``x``, ``dy`` and ``dx``
    blocks and ``x``'s rows before them, double-buffered, and the float32
    rows carried between blocks and the taps' rows (a strip's values live
    in vector registers)."""
    return 2 * (3 * bt + _HALO) * bc * itemsize + 4 * (_HALO + 32) * bc


def _lanes(c: int, bt: int, itemsize: int) -> int:
    """Channels a grid step: the widest ``128 d``, ``d`` dividing
    ``c / 128``, within the VMEM budget (0 where none is)."""
    fits = [_LANE * d for d in range(1, c // _LANE + 1)
            if (c // _LANE) % d == 0
            and _vmem_bytes(bt, _LANE * d, itemsize) <= VMEM_BUDGET_BYTES]
    return max(fits, default=0)


def kernel_applies(t: int, c: int, k: int, itemsize: int = 4) -> bool:
    """Whether :func:`conv_silu` can run this problem here: a TPU backend,
    whole lanes of channels, whole time blocks, fewer than a sublane tile
    of rows before a token, and a channel block within the budget."""
    return (_use_pallas() and c % _LANE == 0 and t % TIME_BLOCK == 0
            and k - 1 < 8 * 4 // itemsize
            and _lanes(c, TIME_BLOCK, itemsize) > 0)


# -- inside a grid step -------------------------------------------------------

def _halo(prev, block):
    """The ``_HALO`` rows before a block, float32, zero before the first
    token (``block`` 0)."""
    row = lax.broadcasted_iota(jnp.int32, prev.shape, 0) + block * _HALO
    return jnp.where(row >= _HALO, prev.astype(F32), 0.0)


def _taps(before, x, w_ref, b_ref, lanes, k: int):
    """``(s, xs)`` of a strip: the float32 pre-activation ``sum_i
    x[t-k+1+i] w[i] + b`` and the shifted strips ``xs[i]`` it multiplies.
    ``before`` is the ``_HALO`` rows before the strip ``x``; a shift of
    rows is a rotation of the two side by side."""
    xh = jnp.concatenate([before, x], axis=0)
    xs = [pltpu.roll(xh, k - 1 - i, 0)[_HALO:] for i in range(k - 1)] + [x]
    s = xs[0] * w_ref[0, :, lanes]
    for i in range(1, k):
        s = s + xs[i] * w_ref[i, :, lanes]
    return s + b_ref[:, lanes], xs


def _strips(bt: int, bc: int, strip=None):
    """Rows and lanes a strip of a ``[bt, bc]`` block: ``strip`` (else
    ``(STRIP_ROWS, STRIP_LANES)``) cut to divide the block."""
    rows, lanes = strip or (STRIP_ROWS, STRIP_LANES)
    return math.gcd(bt, rows), math.gcd(bc, lanes)


def _eighths(v):
    """``v`` [R,L] summed down to one sublane tile [8,L]."""
    return sum(v[r:r + 8] for r in range(0, v.shape[0], 8))


def _fwd_kernel(x_ref, prev_ref, w_ref, b_ref, o_ref, *, k: int, strip):
    bt, bc = x_ref.shape
    rs, ls = _strips(bt, bc, strip)
    block = pl.program_id(2)

    def lane_strip(m, carry):
        lanes = pl.ds(pl.multiple_of(m * ls, ls), ls)

        def strip(r, before):
            rows = pl.ds(pl.multiple_of(r * rs, rs), rs)
            s, _ = _taps(before, x_ref[rows, lanes].astype(F32), w_ref,
                         b_ref, lanes, k)
            o_ref[rows, lanes] = (s * jax.nn.sigmoid(s)).astype(o_ref.dtype)

        strip(0, _halo(prev_ref[:, lanes], block))

        def body(r, carry):
            start = pl.multiple_of(r * rs - _HALO, _HALO)
            strip(r, x_ref[pl.ds(start, _HALO), lanes].astype(F32))
            return carry

        return lax.fori_loop(1, bt // rs, body, carry)

    lax.fori_loop(0, bc // ls, lane_strip, 0)


def _bwd_kernel(x_ref, prev_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref,
                db_ref, later_ref, *, k: int, strip):
    bt, bc = x_ref.shape
    rs, ls = _strips(bt, bc, strip)
    seq_i, i = pl.program_id(1), pl.program_id(2)
    block = pl.num_programs(2) - 1 - i

    @pl.when((seq_i == 0) & (i == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    @pl.when(i == 0)
    def _():
        later_ref[...] = jnp.zeros_like(later_ref)

    def lane_strip(m, carry):
        lanes = pl.ds(pl.multiple_of(m * ls, ls), ls)

        def strip(r, before, later, sums):
            """Strip ``r`` of the block; ``later`` the first ``_HALO`` rows
            of the later strip's ``ds``, ``sums`` those of ``dw`` and
            ``db`` so far.  Returns this strip's first rows and the
            sums."""
            rows = pl.ds(pl.multiple_of(r * rs, rs), rs)
            s, xs = _taps(before, x_ref[rows, lanes].astype(F32), w_ref,
                          b_ref, lanes, k)
            sig = jax.nn.sigmoid(s)
            dy = dy_ref[rows, lanes].astype(F32)
            ds = dy * sig + (dy * s) * (sig * (1.0 - sig))
            dsl = jnp.concatenate([ds, later], axis=0)
            n = dsl.shape[0]
            dx = ds * w_ref[k - 1, :, lanes]
            for j in range(k - 2, -1, -1):
                dx = dx + pltpu.roll(dsl, n - (k - 1 - j), 0)[:rs] \
                    * w_ref[j, :, lanes]
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)
            sums = [a + _eighths(v * ds) for a, v in zip(sums, xs)] \
                + [sums[k] + _eighths(ds)]
            return ds[:_HALO], sums

        def body(j, carry):
            r = bt // rs - 1 - j
            start = pl.multiple_of(r * rs - _HALO, _HALO)
            return strip(r, x_ref[pl.ds(start, _HALO), lanes].astype(F32),
                         *carry)

        zero = jnp.zeros((8, ls), F32)
        later, sums = lax.fori_loop(0, bt // rs - 1, body,
                                    (later_ref[:, lanes], [zero] * (k + 1)))
        later, sums = strip(0, _halo(prev_ref[:, lanes], block), later, sums)
        later_ref[:, lanes] = later
        for j in range(k):
            dw_ref[j, :, lanes] += jnp.sum(sums[j], axis=0, keepdims=True)
        db_ref[:, lanes] += jnp.sum(sums[k], axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, bc // ls, lane_strip, 0)


# -- the calls ----------------------------------------------------------------

def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _forward(x, w3, b2, cd, bt: int, bc: int, interpret: bool, strip=None):
    """``silu`` of the convolution in ``cd``: ``x`` [B,T,C], ``w3``
    [k,1,C] and ``b2`` [1,C] float32; ``strip`` as :func:`_strips`'s."""
    bsz, t, c = x.shape
    k = w3.shape[0]
    block = pl.BlockSpec((None, bt, bc), lambda s, j, i: (s, i, j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, strip=strip),
        grid=(bsz, c // bc, t // bt),
        in_specs=[
            block,
            pl.BlockSpec((None, _HALO, bc), lambda s, j, i: (
                s, jnp.maximum(i * (bt // _HALO) - 1, 0), j)),
            pl.BlockSpec((k, 1, bc), lambda s, j, i: (0, 0, j)),
            pl.BlockSpec((1, bc), lambda s, j, i: (0, j))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, cd,
                                       vma=_varies(x, w3, b2)),
        compiler_params=_params("parallel", "parallel", "parallel"),
        name="causal_conv_fwd",
        interpret=interpret,
    )(x, x, w3, b2)


def _backward(x, w3, b2, dy, bt: int, bc: int, interpret: bool,
              strip=None):
    """``(dx, dw3, db2)``: ``dx`` in ``x``'s type, the others float32."""
    bsz, t, c = x.shape
    k, nt = w3.shape[0], t // bt
    vma = _varies(x, w3, b2, dy)
    block = pl.BlockSpec((None, bt, bc), lambda j, s, i: (s, nt - 1 - i, j))
    w_spec = pl.BlockSpec((k, 1, bc), lambda j, s, i: (0, 0, j))
    b_spec = pl.BlockSpec((1, bc), lambda j, s, i: (0, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, strip=strip),
        grid=(c // bc, bsz, nt),
        in_specs=[
            block,
            pl.BlockSpec((None, _HALO, bc), lambda j, s, i: (
                s, jnp.maximum((nt - 1 - i) * (bt // _HALO) - 1, 0), j)),
            block, w_spec, b_spec],
        out_specs=[block, w_spec, b_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
                   jax.ShapeDtypeStruct(w3.shape, F32, vma=vma),
                   jax.ShapeDtypeStruct(b2.shape, F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((_HALO, bc), F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="causal_conv_bwd",
        interpret=interpret,
    )(x, x, dy, w3, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(x, w3, b2, cd, bt: int, bc: int, interpret: bool):
    return _forward(x, w3, b2, cd, bt, bc, interpret)


def _conv_fwd(x, w3, b2, cd, bt, bc, interpret):
    return _forward(x, w3, b2, cd, bt, bc, interpret), (x, w3, b2)


def _conv_bwd(cd, bt, bc, interpret, res, dy):
    return _backward(*res, dy, bt, bc, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, w, b, cd, interpret: bool = False):
    """``ops/seq.py:causal_conv_silu`` through the kernels: ``x`` [B,T,C]
    (``T`` whole ``TIME_BLOCK``s, ``C`` whole lanes), ``w`` [k,C] (tap
    ``k - 1`` multiplies the current position), ``b`` [C]; the result in
    ``cd``.  ``interpret`` runs the kernels in the Pallas interpreter (the
    CPU tests)."""
    bsz, t, c = x.shape
    k = w.shape[0]
    bc = _lanes(c, TIME_BLOCK, jnp.dtype(x.dtype).itemsize)
    if w.shape != (k, c) or b.shape != (c,) or t % TIME_BLOCK or not bc \
            or k - 1 >= _HALO:
        raise ValueError(
            f"conv_silu: x {x.shape} wants w [k,{c}] with k <= {_HALO}, b "
            f"[{c}], whole blocks of {TIME_BLOCK} tokens and of {_LANE} "
            f"channels, got w {w.shape}, b {b.shape}")
    # Inside shard_map a parameter does not vary over the mesh; what the
    # kernels read beside the data must, and its cotangent is then summed
    # over the mesh by the cast's own transpose.
    vma = _varies(x)

    def varying(v):
        missing = tuple(vma - jax.typeof(v).vma)
        return lax.pcast(v, missing, to="varying") if missing else v

    w3 = varying(w.astype(F32).reshape(k, 1, c))
    b2 = varying(b.astype(F32).reshape(1, c))
    return _conv(x, w3, b2, jnp.dtype(cd), TIME_BLOCK, bc, interpret)


# -- python -m ddp_tpu.ops.causal_conv ----------------------------------------

# (cell, B, T, C) of the three cells that run the convolution, k = 4: the
# fifth token model's x, B, C (one group), the first's, the second's x.
CELL_SHAPES = (("granite4_h_micro", 2, 8192, 4352),
               ("nemotron3_nano", 2, 8192, 6144),
               ("phi4_mini_flash", 2, 8192, 5120))
# Off the chip, through the interpreter.
SMALL_SHAPE = ("interpret", 2, 1024, 384)
# The sweep: strips (rows, lanes) at the module's blocks, then blocks (rows
# a grid step, and the VMEM budget that gives the channels) at its strips.
SWEEP_STRIPS = ((16, 256), (32, 128), (32, 256), (32, 512), (64, 128),
                (64, 256))
SWEEP_BLOCKS = ((256, 40 * 2**20), (512, 8 * 2**20), (1024, 40 * 2**20))
_NAMES = ("o", "dx", "dw", "db")
# One TPU v5e chip's HBM (Google Cloud, "TPU v5e").
_HBM_BYTES_PER_S = 819e9
# Bytes an element of a layer moves a step at the least: the forward reads
# x and writes y in bf16 twice (again under the block's checkpoint), the
# backward reads x and dy and writes dx in bf16.
_FLOOR_BYTES = 14


def _operands(bsz: int, t: int, c: int, k: int = 4, seed: int = 0):
    """``(x, w, b)`` float32, the weights in the initialiser's range, and a
    cotangent for the result."""
    ks = jax.random.split(jax.random.key(seed), 4)
    bound = 1.0 / k ** 0.5
    x = jax.random.normal(ks[0], (bsz, t, c), F32)
    w = jax.random.uniform(ks[1], (k, c), F32, -bound, bound)
    b = jax.random.uniform(ks[2], (c,), F32, -bound, bound)
    return (x, w, b), jax.random.normal(ks[3], (bsz, t, c), F32)


def _vjp_of(path):
    def run(args, dy):
        o, pull = jax.vjp(path, *args)
        return (o,) + pull(dy.astype(o.dtype))
    return run


def _sweep(x, w, b, dy, cd) -> None:
    """Milliseconds of the kernels alone, forward and backward, at each
    strip of :data:`SWEEP_STRIPS` and each block of :data:`SWEEP_BLOCKS`."""
    from .attention import _ms
    bsz, t, c = x.shape
    k = w.shape[0]
    w3, b2 = w.reshape(k, 1, c), b.reshape(1, c)
    strip = (STRIP_ROWS, STRIP_LANES)
    default = (TIME_BLOCK, _lanes(c, TIME_BLOCK, 2))
    tries = [(default, s) for s in SWEEP_STRIPS]
    for bt, budget in SWEEP_BLOCKS:
        bc = max([_LANE * d for d in range(1, c // _LANE + 1)
                  if (c // _LANE) % d == 0
                  and _vmem_bytes(bt, _LANE * d, 2) <= budget], default=0)
        if bc and not t % bt and ((bt, bc), strip) not in tries:
            tries.append(((bt, bc), strip))
    print("causal_conv: sweep  block rows x lanes  strip rows x lanes  ms "
          "forward  backward")
    for (bt, bc), (rows, lanes) in tries:
        row = []
        kw = dict(bt=bt, bc=bc, interpret=False, strip=(rows, lanes))
        for fn, args in (
                (functools.partial(_forward, cd=cd, **kw), (x, w3, b2)),
                (functools.partial(_backward, **kw), (x, w3, b2, dy))):
            try:
                row.append(f"{_ms(jax.jit(fn), *args):9.3f}")
            except Exception as e:  # a block the compiler refuses
                row.append(f"  refused ({type(e).__name__})")
        print(f"causal_conv:   {bt:>12} x {bc:<6}{rows:>9} x {lanes:<6}"
              + "".join(row), flush=True)


def _check_shape(cell: str, bsz: int, t: int, c: int, *, on_chip: bool):
    """One shape of :func:`_self_check`: distances, milliseconds, the
    sweep, and which path ``seq.causal_conv_silu`` is traced through."""
    from . import seq
    from .attention import _ms, _rel

    cd = jnp.bfloat16
    print(f"causal_conv: {cell} B={bsz} T={t} C={c} k=4 "
          f"{jnp.dtype(cd).name}, blocks of {TIME_BLOCK} x "
          f"{_lanes(c, TIME_BLOCK, 2)}", flush=True)
    args, dy = _operands(bsz, t, c)
    forward = {
        "kernel": lambda x, w, b: conv_silu(x, w, b, cd,
                                            interpret=not on_chip),
        "xla": lambda x, w, b: seq.conv_silu_xla(x, w, b, cd)}
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(_vjp_of(lambda x, w, b: seq.conv_silu_xla(
            x, w, b, F32)))(args, dy)
    # The input and the cotangent arrive in the compute type, as the
    # mixer's do (cast inside the jitted function, XLA would keep the XLA
    # form's in float32).
    low, dy_low = (args[0].astype(cd),) + args[1:], dy.astype(cd)
    dist = {name: [_rel(a, e) for a, e in zip(
        jax.jit(_vjp_of(fn))(low, dy_low), exact)]
        for name, fn in forward.items()}
    del exact
    print("causal_conv: distance from float32" + "".join(
        f"{name:>10}" for name in _NAMES))
    for name, d in dist.items():
        print(f"causal_conv:   {name:<20}" + "".join(f"{v:10.6f}" for v in d),
              flush=True)
    for what, got, ref in zip(_NAMES, dist["kernel"], dist["xla"]):
        if got > 1.25 * ref + 1e-6:
            raise RuntimeError(
                f"conv_silu's {what} is {got:.6f} from the float32 answer, "
                f"the XLA form {ref:.6f}: further by more than a quarter")
    if on_chip:
        floor = _FLOOR_BYTES * bsz * t * c / _HBM_BYTES_PER_S * 1e3
        print(f"causal_conv: ms    forward  forward+backward  a layer's "
              f"step (twice forward, once backward)  share of its "
              f"{_FLOOR_BYTES}-byte floor ({floor:.3f} ms)")
        for name, fn in forward.items():
            f = _ms(jax.jit(fn), *low)
            fb = _ms(jax.jit(_vjp_of(fn)), low, dy_low)
            print(f"causal_conv:   {name:<8}{f:9.3f}{fb:12.3f}"
                  f"{f + fb:14.3f}{100 * floor / (f + fb):12.1f}%",
                  flush=True)
        _sweep(*low, dy_low, cd)
    before = dict(TRACED)
    jax.eval_shape(lambda x, w, b: seq.causal_conv_silu(x, w, b, cd), *low)
    path = {name: TRACED[name] - before[name] for name in TRACED}
    print(f"causal_conv: traced through {path}", flush=True)
    return path


def _self_check() -> None:
    """On a TPU, at the three cells' shapes (2 sequences of 8,192 tokens,
    4,352 / 6,144 / 5,120 channels, four taps, bf16): each path's distance
    from the float32 answer (the result and the three gradients),
    milliseconds of kernel and XLA form forward and forward plus backward
    with a layer's step against its bytes floor, and the sweep of blocks.
    Elsewhere: one small shape through the interpreter, distances only.
    Raises where the kernel is further from float32 than the XLA form by
    more than a quarter, or, on a TPU, where the convolution did not take
    it."""
    from ..parallel.mesh import make_mesh
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    print(device_line(make_mesh()), flush=True)
    on_chip = _use_pallas()
    for shape in CELL_SHAPES if on_chip else (SMALL_SHAPE,):
        path = _check_shape(*shape, on_chip=on_chip)
        if on_chip and path != {"kernel": 1, "xla": 0}:
            raise RuntimeError(f"causal_conv_silu did not take the kernel "
                               f"at {shape} on a TPU")
        print(f"causal_conv: ok kernel={'pallas' if on_chip else 'interpret'}"
              f" {shape} within a quarter of the XLA form's distance from "
              f"float32", flush=True)


if __name__ == "__main__":
    # Through the module as ``ops/seq.py`` imports it, not this second copy
    # of it: ``TRACED`` is the one the convolution adds to.
    from ddp_tpu.ops import causal_conv
    causal_conv._self_check()
