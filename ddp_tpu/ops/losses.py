"""Losses. The reference uses only ``F.cross_entropy`` with default mean
reduction (singlegpu.py:105)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# A target that counts for nothing (a sequence's last position, padding):
# F.cross_entropy's ``ignore_index``, for per-position labels.
IGNORE = -1


def cross_entropy_per_example(logits: jax.Array,
                              labels: jax.Array) -> jax.Array:
    """Per-example softmax cross-entropy, computed in fp32 for stability.

    Matches ``F.cross_entropy(..., reduction='none')``; ``logits``
    ``[..., C]`` against ``labels`` ``[...]``.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def cross_entropy_sum_count(logits: jax.Array, labels: jax.Array,
                            mask: Optional[jax.Array] = None,
                            ) -> Tuple[jax.Array, jax.Array]:
    """(sum of CE over valid examples, valid count).

    The mean is taken as a *global* psum(sum)/psum(count) in the train step so
    ragged final batches (padded+masked to keep XLA shapes static,
    SURVEY.md section 7 hard-part #3) don't perturb the loss, and so the
    distributed mean matches DDP's gradient averaging exactly (with torch's
    ``DistributedSampler`` every rank has an equal count, making
    mean-of-rank-means == global mean).

    Per-position labels (``logits`` ``[B,T,V]`` against ``labels``
    ``[B,T]``, a token model's) may hold :data:`IGNORE`: those positions
    are left out of the sum and of the count.
    """
    if labels.ndim > 1:
        valid = labels != IGNORE
        ce = cross_entropy_per_example(logits, jnp.where(valid, labels, 0))
        return (jnp.where(valid, ce, 0.0).sum(),
                valid.sum().astype(jnp.float32))
    ce = cross_entropy_per_example(logits, labels)
    if mask is None:
        return ce.sum(), jnp.asarray(ce.shape[0], jnp.float32)
    maskf = mask.astype(jnp.float32)
    return (ce * maskf).sum(), maskf.sum()
