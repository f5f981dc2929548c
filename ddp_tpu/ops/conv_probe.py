"""Per-layer convolution emitter probe (round 3).

The fp32 roofline attributes the step's residual gap to "conv
emitter efficiency at CIFAR-scale spatial shapes" — this tool makes that
claim *measurable per shape*: it times every distinct VGG conv layer
(forward, and backward as one dgrad+wgrad program) in isolation on the
real chip and reports achieved TFLOP/s, so the inefficiency localizes to
specific (H, C_in, C_out) combinations instead of remaining a step-level
aggregate.  Two rows per shape: pure forward, and the full trained cost
(``train(fwd+dgrad+wgrad)`` — ``jax.vjp`` executes the primal inside the
chain, so that window's FLOP multiplier is 3).

Method: each measurement jits an UNROLLED chain of N dependency-linked
convs (dependency through the tiny weight, so the activation's layout
conversion hoists out of the chain exactly as it amortizes in the real
step) and takes the best-of-repeats wall time at two chain lengths; the
reported per-call time is the MARGINAL (t_long - t_short)/(N_long -
N_short).  The differencing cancels the fixed cost of one dispatch plus
one host value read, which at any single chain length would be counted
into a sub-millisecond kernel.  A ``lax.scan`` chain was tried and
rejected: the while-loop drains the pipeline at each iteration boundary,
where the unrolled chain overlaps each conv with the previous
mean-reduction.

Usage: ``python -m ddp_tpu.ops.conv_probe [--batch 512] [--bf16]``
— prints one JSON line per (shape, direction).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .layers import conv2d

# (H=W, C_in, C_out, reps) for each conv in VGG.ARCH (reference
# singlegpu.py:48) at the spatial size it actually sees; 'reps' folds the
# two identical 4x4 512->512 layers into one row.
VGG_CONV_SHAPES = [
    (32, 3, 64, 1),
    (32, 64, 128, 1),
    (16, 128, 256, 1),
    (16, 256, 256, 1),
    (8, 256, 512, 1),
    (8, 512, 512, 1),
    (4, 512, 512, 2),
]

N_SHORT, N_LONG = 10, 50


def conv_flops(n: int, h: int, cin: int, cout: int) -> float:
    """MAC-pair FLOPs of a SAME-padded 3x3 stride-1 conv (interior
    approximation, matching the roofline accounting)."""
    return 2.0 * n * h * h * cout * 9 * cin


def _fwd_chain(n: int, conv):
    def win(x, w):
        acc = jnp.zeros((), x.dtype)
        for _ in range(n):
            acc = jnp.mean(conv(x, w + acc * 1e-30))
        return acc

    return jax.jit(win)


def _train_chain(n: int, conv):
    # NOTE: jax.vjp executes the PRIMAL forward inside the chain, so this
    # window times fwd+dgrad+wgrad — the full per-layer trained cost —
    # and its FLOP multiplier is 3, not 2.  (An earlier revision labeled
    # this row "bwd" with fmult=2.0, inflating bwd ms and deflating bwd
    # TFLOP/s by the forward's share.)
    def win(x, w):
        acc = jnp.zeros((), x.dtype)
        for _ in range(n):
            y, vjp = jax.vjp(conv, x, w + acc * 1e-30)
            dx, dw = vjp(y)
            acc = jnp.mean(dx) + jnp.mean(dw)
        return acc

    return jax.jit(win)


# Noise threshold: a marginal below 0.1 ms/call cannot be distinguished
# from dispatch jitter at these chain lengths (shared by the pool probe
# so the two methodologies cannot drift).
NOISE_S_PER_CALL = 1e-4


def best_of(fn, args, repeats: int) -> float:
    """Best-of-``repeats`` wall time of ``fn(*args)``, synced via a host
    value read.  The shared timing core of every probe in this package."""
    float(fn(*args))  # compile + warm
    dt = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(fn(*args))
        dt = min(dt, time.perf_counter() - t0)
    return dt


def probe(batch: int = 512, repeats: int = 6, dtype=jnp.float32,
          conv=conv2d, shapes=None) -> list:
    """Marginal per-call ms and achieved TFLOP/s for each VGG conv shape.

    ``conv`` is pluggable (signature ``conv(x, w) -> y``) so alternative
    implementations (e.g. Pallas kernels) can be measured under the
    identical harness for an apples-to-apples comparison (the candidates
    live in :mod:`~ddp_tpu.ops.conv_candidates`); ``shapes`` restricts the
    sweep (default: every VGG conv shape).
    """
    records = []
    for h, cin, cout, reps in (VGG_CONV_SHAPES if shapes is None
                               else shapes):
        x = jax.random.normal(jax.random.key(0), (batch, h, h, cin), dtype)
        # .astype: the numpy scalar is strongly typed, so the bare product
        # would silently promote a bfloat16 w back to float32.
        w = (jax.random.normal(jax.random.key(1), (3, 3, cin, cout), dtype)
             * np.sqrt(2.0 / (9 * cin))).astype(dtype)
        for name, chain, fmult in (("fwd", _fwd_chain, 1.0),
                                   ("train(fwd+dgrad+wgrad)", _train_chain,
                                    3.0)):
            t_s = best_of(chain(N_SHORT, conv), (x, w), repeats)
            t_l = best_of(chain(N_LONG, conv), (x, w), repeats)
            per_call = max((t_l - t_s) / (N_LONG - N_SHORT), 1e-9)
            fl = conv_flops(batch, h, cin, cout) * fmult
            # Jitter can make t_long <= t_short when the true marginal
            # cost is tiny; flag those rows instead of printing an absurd
            # TFLOP/s as fact.
            noise_limited = (t_l - t_s) < NOISE_S_PER_CALL * (N_LONG
                                                             - N_SHORT)
            rec = {
                "shape": f"{h}x{h} {cin}->{cout}" + (f" x{reps}" if reps > 1
                                                     else ""),
                "dir": name,
                "marginal_ms_per_call": round(per_call * 1e3, 3),
                "tflops": (None if noise_limited
                           else round(fl / per_call / 1e12, 1)),
                "noise_limited": noise_limited,
                "reps_in_vgg": reps,
            }
            records.append(rec)
            print(json.dumps(rec), flush=True)
    return records


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--repeats", type=int, default=6)
    p.add_argument("--bf16", action="store_true")
    args = p.parse_args()
    recs = probe(args.batch, args.repeats,
                 jnp.bfloat16 if args.bf16 else jnp.float32)
    # The train rows already contain the forward (jax.vjp runs the
    # primal), so summing them alone gives the per-step trained total.
    # Caveats carried on the summary line: clamped noise-limited rows
    # contribute ~0 (the sum is a lower bound when any are flagged), and
    # every train row includes dgrad — for the FIRST layer the real step
    # never computes the input gradient, so the sum slightly overstates
    # the in-step trained total by conv1's dgrad share.
    train_rows = [r for r in recs if r["dir"].startswith("train")]
    total = sum(r["marginal_ms_per_call"] * r["reps_in_vgg"]
                for r in train_rows)
    print(json.dumps({
        "sum_marginal_train_ms_per_step": round(total, 2),
        "noise_limited_train_rows": sum(r["noise_limited"]
                                        for r in train_rows),
    }), flush=True)


if __name__ == "__main__":
    main()
