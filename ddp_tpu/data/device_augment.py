"""On-device train-time augmentation (RandomCrop(32, pad 4) + HFlip).

TPU-first alternative to the host-side ``augment.py`` path: raw uint8
batches go over the host->device link and the crop/flip happens inside the
jitted train step.  At pod scale the host augmentation thread pool is the
classic input bottleneck (SURVEY.md §7 hard-part #4); on device the cost is
noise next to the convolutions.

The crop+flip is expressed as two one-hot MATMULS (row-select, then
col-select with the flip folded in) rather than a gather: XLA:TPU lowers
per-sample advanced-indexing gathers to a generic gather, while the
equivalent one-hot einsum rides the MXU (cost of either on the chip: not
measured on this stack).  Out-of-range one-hot rows are all-zero, which
supplies the
reference's zero padding (torchvision RandomCrop fill=0) for free.  The
selection is numerically exact (each output pixel is 1*value + 0*rest with
fp32 accumulation), so the result is cast back to the input dtype
losslessly.

Distributional parity with torchvision's transforms (singlegpu.py:154-160):
offsets uniform over [0, 8], flip probability 0.5, zero padding.  The
concrete RNG stream differs (JAX threefry vs torch Philox vs numpy PCG64) —
as with the samplers, only the distribution is load-bearing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.gather import RowTable, gather_rows

PAD = 4
SIZE = 32


def random_crop_flip(rng: jax.Array, imgs: jax.Array) -> jax.Array:
    """[N,32,32,3] (any dtype) -> same shape/dtype, cropped+flipped.

    Same RNG draws as :func:`gather_crop_flip` (which is exactly this after
    a batch gather), so the per-step and resident paths augment
    bit-identically on the same key."""
    return _crop_flip_onehot(rng, imgs)


def gather_crop_flip(rng: jax.Array, table: RowTable,
                     idx_row: jax.Array) -> jax.Array:
    """Dataset-gather + RandomCrop(32, pad 4) + HFlip for the
    device-resident path (train/epoch.py).

    ``table`` is the whole resident dataset as a
    :class:`~ddp_tpu.ops.gather.RowTable` (``[M,24,128]`` on the device,
    rows of ``[32,32,3]``); the batch is pulled by the Pallas DMA row
    gather (ops/gather.py), reshaped to ``[N,32,32,3]`` and augmented by
    the one-hot matmuls below, in place of a fused clamped-gather
    formulation."""
    return _crop_flip_onehot(rng, gather_rows(table, idx_row))


def _crop_flip_onehot(rng: jax.Array, imgs: jax.Array) -> jax.Array:
    """Crop+flip as two one-hot contractions; zero-fill via OOB one-hots."""
    n = imgs.shape[0]
    k_off, k_flip = jax.random.split(rng)
    ys, xs = jax.random.randint(k_off, (2, n), 0, 2 * PAD + 1)
    flip = jax.random.bernoulli(k_flip, 0.5, (n,))
    row = jnp.arange(SIZE)
    y_src = ys[:, None] + row[None, :] - PAD                 # [N, 32]
    x_cols = jnp.where(flip[:, None], SIZE - 1 - row[None, :],
                       row[None, :])
    x_src = xs[:, None] + x_cols - PAD                       # [N, 32]
    # one_hot yields an all-zero row for out-of-range sources == zero fill.
    ysel = jax.nn.one_hot(y_src, SIZE, dtype=jnp.float32)    # [N, 32, 32]
    xsel = jax.nn.one_hot(x_src, SIZE, dtype=jnp.float32)
    x = imgs.astype(jnp.float32)
    # uint8-origin values (<= 255) are exact in the MXU's bf16 multiplies;
    # arbitrary float images need full-precision passes to stay lossless.
    prec = ("highest" if jnp.issubdtype(imgs.dtype, jnp.floating) else None)
    y1 = jnp.einsum("nio,nohc->nihc", ysel, x, precision=prec)
    out = jnp.einsum("njw,niwc->nijc", xsel, y1, precision=prec)
    return out.astype(imgs.dtype)
