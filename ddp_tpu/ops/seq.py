"""What the token models' mixers share, in plain XLA: RMSNorm
(``models/nemotron_h.py``, ``models/glm4_moe_lite.py``,
``models/laguna.py``), the causal depthwise convolution of a state-space
mixer and the blocked keys-first attention loop, causal or under a window
(``nemotron_h``, ``glm4_moe_lite`` and ``laguna`` where the kernel does
not apply, ``models/sambay.py`` always).  ``ops/__init__.py`` does not
import this module: a classifier's process never sees it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import causal_conv

F32 = jnp.float32


def rms_norm(x, weight, eps: float, out_dtype):
    """``x / rms(x) * weight``, the statistics in float32."""
    xf = x.astype(F32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(out_dtype)


def causal_conv_silu(x, w, b, cd):
    """``silu`` of the causal depthwise convolution over time, in float32:
    ``x`` [B,T,C], ``w`` [k,C] (tap k-1 multiplies the current position),
    ``b`` [C].  Through the kernel pair of ``ops/causal_conv.py`` where it
    applies, else in plain XLA."""
    k, t = w.shape[0], x.shape[1]
    if causal_conv.kernel_applies(t, x.shape[2], k,
                                  jnp.dtype(x.dtype).itemsize):
        causal_conv.TRACED["kernel"] += 1
        return causal_conv.conv_silu(x, w, b, cd)
    causal_conv.TRACED["xla"] += 1
    return conv_silu_xla(x, w, b, cd)


def conv_silu_xla(x, w, b, cd):
    """:func:`causal_conv_silu` in plain XLA: the input padded as float32,
    four shifted products summed, ``silu``, the cast."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    x = sum(padded[:, i:i + t] * w[i] for i in range(k)) + b
    return jax.nn.silu(x).astype(cd)


def block_probs(q, k, *, start: int, scale: float, lo: int = 0,
                window: Optional[int] = None):
    """The float32 softmax of one block of queries over their visible
    keys, held keys-first, ``[S, R*bq]``: with the queries as the minor
    dimension XLA:TPU runs both products as plain matrix products (10.6 ms
    a head forward at T = 8,192); queries-first, the same float32 scores
    cost 49 ms a block once S passes 4,096 (PERF.md, findings of PR 28).
    ``q`` [R,bq,hd]: ``R`` query heads' queries ``start ...``; ``k``
    [S,hd]: the keys ``lo ... start + bq``.  A query sees the keys up to
    its own position and, under ``window``, only the last ``window`` of
    them, itself counted."""
    r, bq, hd = q.shape
    scores = jnp.dot(k, q.reshape(r * bq, hd).T,
                     preferred_element_type=F32) * scale
    qi = start + (jnp.arange(r * bq) % bq)[None, :]
    si = jnp.arange(k.shape[0])[:, None]
    if lo:
        si = si + lo
    seen = si <= qi
    if window is not None:
        seen = seen & (si > qi - window)
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=0)


def attend(q, k, v, *, start: int, scale: float, cd, **seen):
    """One key-value head of one sequence: ``softmax(q k^T) v`` for a
    block of queries against the keys up to their own position (``seen``:
    :func:`block_probs`'s ``lo`` and ``window``).  ``v`` [S,vd] may be
    wider than the keys; the result is [R,bq,vd]."""
    r, bq, _ = q.shape
    probs = block_probs(q, k, start=start, scale=scale, **seen)
    out = lax.dot_general(probs.astype(cd), v, (((0,), (0,)), ((), ())))
    return out.reshape(r, bq, v.shape[-1])


def attend_head(q, k, v, *, scale: float, cd, block: int,
                window: Optional[int] = None):
    """A block of queries at a time, each under its own checkpoint: the
    scores at [T,T] never exist at once, forward or backward.  ``q``
    [R,T,hd], ``k`` [T,hd], ``v`` [T,vd].  Under ``window`` a block takes
    the keys from its first query's window on."""
    out = []
    for s in range(0, q.shape[1], block):
        lo = max(0, s - window) if window else 0
        seen = dict(lo=lo, window=window) if window else {}
        out.append(jax.checkpoint(functools.partial(
            attend, start=s, scale=scale, cd=cd, **seen))(
                q[:, s:s + block], k[lo:s + block], v[lo:s + block]))
    return jnp.concatenate(out, axis=1)
