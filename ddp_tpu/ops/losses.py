"""Losses. The reference uses only ``F.cross_entropy`` with default mean
reduction (singlegpu.py:105)."""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

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


# The float leaf of a model's state that the loss core fills with each
# prediction depth's own mean loss (train/step.py), so that they reach the
# host where the Trainer flushes losses and no step pays a device read.
LM_LOSS = "lm_loss"


class DepthLogits(NamedTuple):
    """What a model with more than one prediction depth yields in place of
    logits when it trains: each depth's output after its final norm
    (``hidden[k]`` ``[B,T,d]``), the ONE head they share (``[d,V]``) and
    each depth's weight in the loss.  Depth ``k`` at position ``i``
    predicts the token ``k + 1`` further on, so it is scored against the
    labels shifted by ``k`` (:func:`shift_labels`).  The head stays out of
    the model so that the loss core can take a depth's head and loss at a
    time (:func:`depth_cross_entropy`): ``[B,T,V]`` float32 logits exist
    for one depth at once."""
    hidden: Tuple[jax.Array, ...]
    head: jax.Array
    weights: Tuple[float, ...]

    def logits(self, depth: int) -> jax.Array:
        return jnp.matmul(self.hidden[depth], self.head,
                          preferred_element_type=jnp.float32)


def shift_labels(labels: jax.Array, k: int) -> jax.Array:
    """Per-position labels ``[B,T]`` moved ``k`` positions earlier; the
    last ``k`` positions have none and read :data:`IGNORE`."""
    if k == 0:
        return labels
    return jnp.concatenate(
        [labels[:, k:], jnp.full_like(labels[:, :k], IGNORE)], axis=1)


def depth_cross_entropy(out: DepthLogits, labels: jax.Array):
    """``(sums [D], counts [D])``: :func:`cross_entropy_sum_count` of each
    depth against its own shift of ``labels``.  A depth's head and loss
    run under one checkpoint, a sequence at a time: its logits are made
    again for the backward pass, never kept beside another depth's, and
    float32 ``[T,V]`` of ONE sequence and its cotangent are what lives at
    once (at 2 x 8,192 x 19,360 that is 2.4 GB less at the step's peak
    than a batch's: PERF.md, findings of PR 35)."""
    @jax.checkpoint
    def one(hidden, head, labels):
        def sequence(row):
            return cross_entropy_sum_count(
                jnp.matmul(row[0][None], head,
                           preferred_element_type=jnp.float32),
                row[1][None])
        sums, counts = jax.lax.map(sequence, (hidden, labels))
        return sums.sum(), counts.sum()

    sums, counts = zip(*(one(h, out.head, shift_labels(labels, k))
                         for k, h in enumerate(out.hidden)))
    return jnp.stack(sums), jnp.stack(counts)
