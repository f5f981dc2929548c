"""What every configuration's plain reference shares: float32 layers in
``jax.numpy``/``jax.lax``, the loss, the learning-rate schedule and one
SGD step — written from the reference scripts' semantics
(torch.nn.BatchNorm2d, F.cross_entropy, torch.optim.SGD, LambdaLR), not
from ``ddp_tpu/``.  Nothing here imports the program.

Callers run these under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 convolution otherwise takes bf16 passes.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

BN_MOMENTUM = 0.1
BN_EPS = 1e-5


def conv(x, kernel, stride: int, padding: int):
    """NHWC x HWIO convolution, no bias."""
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), ((padding, padding),) * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def batch_norm_train(x, scale, bias, mean, var):
    """torch.nn.BatchNorm2d in training mode: normalise with the biased
    batch variance, move the running variance by the unbiased one."""
    n = x.shape[0] * x.shape[1] * x.shape[2]
    mu = x.mean(axis=(0, 1, 2))
    v = ((x - mu) ** 2).mean(axis=(0, 1, 2))
    y = (x - mu) / jnp.sqrt(v + BN_EPS) * scale + bias
    new_mean = (1 - BN_MOMENTUM) * mean + BN_MOMENTUM * mu
    new_var = (1 - BN_MOMENTUM) * var + BN_MOMENTUM * v * n / max(n - 1, 1)
    return y, {"mean": new_mean, "var": new_var}


def max_pool(x, window: int, stride: int, padding: int):
    """torch.nn.MaxPool2d: the largest of the window's shifted views."""
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)), constant_values=-jnp.inf)
    h_out = (x.shape[1] - window) // stride + 1
    w_out = (x.shape[2] - window) // stride + 1
    views = [x[:, i:i + stride * (h_out - 1) + 1:stride,
               j:j + stride * (w_out - 1) + 1:stride, :]
             for i in range(window) for j in range(window)]
    return jnp.max(jnp.stack(views), axis=0)


def cross_entropy_sum(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - picked).sum()


def triangular_lr(step, *, peak_lr: float, schedule_epochs: float,
                  steps_per_epoch: int, peak_frac: float) -> float:
    """LambdaLR of the reference scripts: 0 at batch 0, ``peak_lr`` at
    ``peak_frac * schedule_epochs`` epochs, 0 again at the end, moved
    once a batch."""
    e = step / steps_per_epoch
    peak = schedule_epochs * peak_frac
    return peak_lr * max(0.0, min(e / peak, (schedule_epochs - e)
                                  / (schedule_epochs - peak), 1.0))


def sgd_step(forward: Callable, params, stats, momentum_buf, images_u8,
             labels, *, lr: float, momentum: float, weight_decay: float,
             n_shards: int) -> Tuple:
    """One optimizer step as ``n_shards`` DDP ranks would take it: each
    rank normalises with its own shard's batch statistics, the loss is
    the mean over the global batch, gradients are that mean's, the
    running statistics returned are the ranks' average.  torch.optim.SGD:
    ``g += wd * p``; ``buf = momentum * buf + g``; ``p -= lr * buf``.

    Returns ``(loss, grads, new_params, new_momentum_buf, new_stats)``.
    """
    x = images_u8.astype(jnp.float32) / 255.0
    xs = jnp.split(x, n_shards)
    ys = jnp.split(labels, n_shards)

    def loss_fn(p):
        total, new_stats = 0.0, []
        for xi, yi in zip(xs, ys):
            logits, ns = forward(p, stats, xi)
            total = total + cross_entropy_sum(logits, yi)
            new_stats.append(ns)
        mean_stats = jax.tree_util.tree_map(
            lambda *s: sum(s) / n_shards, *new_stats)
        return total / labels.shape[0], mean_stats

    (loss, new_stats), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    new_buf = jax.tree_util.tree_map(
        lambda b, g, p: momentum * b + g + weight_decay * p,
        momentum_buf, grads, params)
    new_params = jax.tree_util.tree_map(
        lambda p, b: p - lr * b, params, new_buf)
    return loss, grads, new_params, new_buf, new_stats


# Largest disagreement the first-step check accepts between the system
# (bf16 activations and matmul inputs; float32 parameters, statistics and
# loss) and this float32 reference, at 128 samples a chip.  What was read
# on the chip at the published widths is in PERF.md (findings of PR 22);
# each bound is 1.5 to 10 times the largest of 46 readings.  In float32 the system
# agrees with the reference to 3e-5 (ResNet-18) and 1e-2 (VGG) on the
# same measures (tests/test_reference.py), so what is left is bf16.
#   loss_abs           the mean loss, absolute.
#   momentum_rel       |buf_sys - buf_ref| / |buf_ref| (L2) over all
#                      leaves as one vector.  bf16 moves ReLU masks and
#                      max-pooling choices that float32 does not follow,
#                      and the error grows by some 3% a layer on the way
#                      back to the input, so this bound is loose; it
#                      catches a wrong formula (a sum for a mean, a
#                      missing 1/255: errors of 1 and more).
#   momentum_rel_best  the same of the best single leaf, which sits next
#                      to the loss and sees one layer's rounding: the
#                      bound that a type narrower than bf16 breaks (8
#                      mantissa bits against 2 or 3: 30 to 60 times the
#                      error).
#   update_rel         as momentum_rel, of the parameters' change: the
#                      learning rate and the sign too.
#   stats_rel          per leaf, the new running statistics, the worst
#                      leaf (a biased variance in the running average, or
#                      statistics taken over the global batch and not the
#                      replica's, fail it).
#   decay_rel          the error of sum <buf, p> / sum <p, p> over the
#                      scale-free leaves, as a share of the weight decay:
#                      1.0 where the decay term is dropped, 1e-3 where it
#                      is there (reference_check._decay_share).
TOLERANCE = {
    "loss_abs": 0.02,
    "momentum_rel": 0.7,
    "momentum_rel_best": 0.03,
    "update_rel": 0.7,
    "stats_rel": 0.02,
    "decay_rel": 0.05,
}
