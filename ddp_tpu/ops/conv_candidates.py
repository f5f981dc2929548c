"""Alternative 3x3 SAME-conv implementations for the conv-probe seam.

The round-3 kernel-substitution analysis concluded "a hand kernel
can't win under fp32 semantics" from a fusion-barrier argument plus emitter
measurements — but the probe's pluggable ``conv=`` seam
(:func:`~ddp_tpu.ops.conv_probe.probe`) never had an actual candidate
plugged in (VERDICT r3 missing #3).  This module supplies three real
candidates and a CLI to measure them under the identical marginal-cost
harness, targeting the two shapes an earlier roofline flagged as
sub-peak (32x32 64->128 and 8x8 256->512; rates not measured on this
stack):

- ``conv2d_shift9``: pure-lax shift-and-matmul — nine accumulated
  ``[N*H*W, Cin] @ [Cin, Cout]`` matmuls on 1-pixel-shifted views.  No
  patch materialisation; K = Cin per pass.
- ``conv2d_im2col``: pure-lax im2col — materialise the ``[N,H,W,9*Cin]``
  patch tensor, one big matmul with K = 9*Cin (MXU-friendlier K at the
  cost of 9x activation HBM traffic).
- ``conv2d_pallas``: fused shift-and-matmul in a Pallas kernel — the
  padded input block is DMA'd to VMEM once per grid cell, and the nine
  shifted views are read from VMEM and accumulated through nine MXU dots
  (shifted patches never touch HBM).  An in-kernel im2col concat
  (one K = 9*Cin dot) was tried first and is NOT implementable today:
  Mosaic rejects concatenation of lane-offset shifted slices
  ("result/input offset mismatch on non-concat dimension").

All three are numerically the conv2d contract (same SAME padding, stride
1; fp32 accumulation) and carry a custom VJP routing dgrad through the
same fast forward (dgrad of a SAME 3x3 conv IS a SAME 3x3 conv with the
spatially-flipped, in/out-transposed kernel) and wgrad through a
shifted-matmul einsum.  Measure with::

    python -m ddp_tpu.ops.conv_candidates [--bf16] [--all_shapes]

One JSON line per (candidate, shape, direction) — the evidence row, win
or negative result.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np


def _pad_hw(x):
    return jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))


def _shift9_fwd(x, w):
    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    xp = _pad_hw(x)
    acc = jnp.zeros((n, h, wd, cout), jnp.float32)
    for ky in range(3):
        for kx in range(3):
            acc = acc + jax.lax.dot_general(
                xp[:, ky:ky + h, kx:kx + wd, :], w[ky, kx],
                (((3,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


def _im2col_patches(x):
    """[N,H,W,Cin] -> [N,H,W,9*Cin] patch tensor (ky-major, kx, cin-minor
    — matching w.reshape(9*cin, cout))."""
    n, h, wd, cin = x.shape
    xp = _pad_hw(x)
    return jnp.concatenate(
        [xp[:, ky:ky + h, kx:kx + wd, :]
         for ky in range(3) for kx in range(3)], axis=-1)


def _im2col_fwd(x, w):
    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    p = _im2col_patches(x).reshape(n * h * wd, 9 * cin)
    y = jax.lax.dot_general(p, w.reshape(9 * cin, cout),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.reshape(n, h, wd, cout).astype(x.dtype)


def _pick_block_n(n, h, cin, cout, bytes_per_el):
    """Largest batch tile whose ESTIMATED VMEM footprint (padded input
    block + one shifted-slice copy + fp32 accumulator + weights) fits a
    6 MiB budget — Mosaic's actual stack allocation measured ~2x this
    estimate (double-buffered blocks + live dot operands), and the scoped
    limit is 16 MiB, so 6 MiB estimated keeps the real footprint inside."""
    budget = 6 * 2 ** 20
    w_bytes = 9 * cin * cout * bytes_per_el
    for bn in (128, 64, 32, 16, 8, 4, 2, 1):
        if n % bn:
            continue
        in_b = bn * (h + 2) * (h + 2) * cin * bytes_per_el
        slice_b = bn * h * h * cin * bytes_per_el
        acc_b = bn * h * h * cout * 4
        if w_bytes + in_b + slice_b + acc_b <= budget:
            return bn
    return 1


def _pallas_fwd(x, w):
    """Fused shift-and-matmul forward as a Pallas TPU kernel: nine
    accumulated K=Cin MXU dots over VMEM-resident shifted views."""
    from jax.experimental import pallas as pl

    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    dtype = x.dtype
    bn = _pick_block_n(n, h, cin, cout, np.dtype(dtype).itemsize)
    xp = _pad_hw(x)
    w2 = w.reshape(9, cin, cout)

    def kernel(xp_ref, w_ref, out_ref):
        acc = jnp.zeros((bn * h * wd, cout), jnp.float32)
        for ky in range(3):
            for kx in range(3):
                xs = xp_ref[:, ky:ky + h, kx:kx + wd, :]
                acc = acc + jax.lax.dot_general(
                    xs.reshape(bn * h * wd, cin), w_ref[3 * ky + kx],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        out_ref[:] = acc.reshape(bn, h, wd, cout).astype(dtype)

    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h + 2, wd + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((9, cin, cout), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, h, wd, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, wd, cout), dtype),
    )(xp, w2)


def _flip_transpose(w):
    """dgrad kernel: spatial flip + in/out channel transpose, so dgrad is
    the SAME fast forward conv applied to dy."""
    return jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2)


def _wgrad(x, dy):
    """dw[ky,kx,cin,cout] = sum_nhw xpad[n, h+ky, w+kx, cin] * dy[n,h,w,cout]
    — nine [Cin, N*H*W] @ [N*H*W, Cout] matmuls."""
    n, h, wd, cin = x.shape
    cout = dy.shape[-1]
    xp = _pad_hw(x)
    dyf = dy.reshape(n * h * wd, cout)
    rows = []
    for ky in range(3):
        for kx in range(3):
            xs = xp[:, ky:ky + h, kx:kx + wd, :].reshape(n * h * wd, cin)
            rows.append(jax.lax.dot_general(
                xs, dyf, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
    return jnp.stack(rows).reshape(3, 3, cin, cout).astype(x.dtype)


def _xla_bwd(res, dy):
    """Backward delegated to XLA's own dgrad/wgrad conv emitters (which
    beat the hand shifted-matmul wgrad in measurement).  jax.vjp runs the
    primal forward too, but its output feeds nothing and XLA DCEs it
    under jit — the backward program that remains is the baseline's."""
    from .layers import conv2d
    x, w = res
    _, vjp = jax.vjp(conv2d, x, w)
    return vjp(dy)


def _with_vjp(fwd, bwd=None):
    """Wrap a forward into the probe's conv contract.  Default backward:
    dgrad via the same fast forward (a SAME 3x3 conv of dy with the
    flipped, transposed kernel), wgrad via shifted matmuls."""

    @jax.custom_vjp
    def conv(x, w):
        return fwd(x, w)

    def conv_fwd(x, w):
        return fwd(x, w), (x, w)

    def conv_bwd(res, dy):
        x, w = res
        return fwd(dy, _flip_transpose(w)), _wgrad(x, dy)

    conv.defvjp(conv_fwd, bwd or conv_bwd)
    return conv


conv2d_shift9 = _with_vjp(_shift9_fwd)
conv2d_im2col = _with_vjp(_im2col_fwd)
conv2d_pallas = _with_vjp(_pallas_fwd)
# The hybrid an early single-candidate run suggested could win; a later
# same-process head-to-head had it losing every cell (records deleted,
# not re-measured on this stack).  Kept as a candidate for the seam.
conv2d_pallas_fwd_xla_bwd = _with_vjp(_pallas_fwd, bwd=_xla_bwd)

CANDIDATES = {
    "baseline_xla_conv": None,  # conv_probe's default conv2d
    "shift9_lax": conv2d_shift9,
    "im2col_lax": conv2d_im2col,
    "shift9_fused_pallas": conv2d_pallas,
    "pallas_fwd_xla_bwd": conv2d_pallas_fwd_xla_bwd,
}

# The two sub-peak shapes the round-3 roofline flagged (plus reps=1).
TARGET_SHAPES = [(32, 64, 128, 1), (8, 256, 512, 1)]


def main() -> None:
    from . import conv_probe

    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--repeats", type=int, default=6)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--all_shapes", action="store_true",
                   help="Probe every VGG conv shape, not just the two "
                        "sub-peak targets")
    p.add_argument("--candidates", default=None,
                   help="Comma list (default: all)")
    args = p.parse_args()
    shapes = (conv_probe.VGG_CONV_SHAPES if args.all_shapes
              else TARGET_SHAPES)
    names = (args.candidates.split(",") if args.candidates
             else list(CANDIDATES))
    unknown = [n for n in names if n not in CANDIDATES]
    if unknown:
        p.error(f"unknown candidate(s) {unknown}; "
                f"valid: {', '.join(CANDIDATES)}")
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    for name in names:
        cand = CANDIDATES[name]
        kw = {} if cand is None else {"conv": cand}
        print(json.dumps({"candidate": name}), flush=True)
        conv_probe.probe(args.batch, args.repeats, dtype, shapes=shapes,
                         **kw)


if __name__ == "__main__":
    main()
