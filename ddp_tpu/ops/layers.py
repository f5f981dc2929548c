"""Low-level NN ops in the TPU-native layout (NHWC activations, HWIO kernels).

These are the building blocks for the models in ``ddp_tpu.models``; each op's
numerics are tested for parity against the equivalent torch CPU op
(tests/test_ops.py).  The reference gets these from torch.nn / cuDNN
(singlegpu.py:64-73); on TPU we express them so XLA can tile the convolutions
onto the MXU and fuse the elementwise BN/ReLU chains into them.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# NHWC / HWIO are the layouts XLA:TPU convolutions are natively tiled for.
CONV_DIMS = ("NHWC", "HWIO", "NHWC")

# Ambient trace-time BN context, THREAD-LOCAL so concurrent traces (async
# compiles, threaded tests) can each set their own axes without
# cross-contamination.  Two fields:
#
# ``sync_axis`` — mesh axis over which batch_norm synchronises its batch
# statistics (the TPU-native SyncBatchNorm the reference keeps commented
# out, multigpu.py:127).  A trace-time context rather than a per-call
# argument so model code stays signature-identical whether BN is synced or
# not; the step builders (train/step.py) set it from their sync_bn flag.
#
# ``grad_axis`` — mesh axis over which bn_relu's hand-written VJP
# all-reduces its scale/bias cotangents.  Autodiff-generated backward gets
# this psum inserted by shard_map's replication-transpose machinery; a
# custom_vjp opts out of that machinery, so the gradient collective must be
# explicit.  Set by the REPLICATED-params cores (train/step.py
# make_loss_and_grads); deliberately NOT set by the ZeRO path
# (train/zero.py _make_local_grads), whose contract is collective-free
# LOCAL gradients reduced later by psum_scatter.
_BN_CTX = threading.local()


def _bn_sync_axis() -> Optional[str]:
    return getattr(_BN_CTX, "sync_axis", None)


def _bn_grad_axis() -> Optional[str]:
    return getattr(_BN_CTX, "grad_axis", None)


@contextlib.contextmanager
def bn_sync_axis(axis_name: Optional[str]):
    """Within this context (and thread), training-mode batch_norm psums its
    statistics over ``axis_name`` (must be inside shard_map over that
    axis)."""
    prev = _bn_sync_axis()
    _BN_CTX.sync_axis = axis_name
    try:
        yield
    finally:
        _BN_CTX.sync_axis = prev


@contextlib.contextmanager
def bn_grad_axis(axis_name: Optional[str]):
    """Within this context (and thread), bn_relu's VJP psums dγ/dβ over
    ``axis_name`` (the DDP gradient all-reduce for the fused op's
    parameters)."""
    prev = _bn_grad_axis()
    _BN_CTX.grad_axis = axis_name
    try:
        yield
    finally:
        _BN_CTX.grad_axis = prev


def conv2d(x: jax.Array, kernel: jax.Array, bias: Optional[jax.Array] = None,
           stride: int = 1, padding: int = 1) -> jax.Array:
    """3x3-style 2-D convolution. x: [N,H,W,C_in], kernel: [kh,kw,C_in,C_out]."""
    y = lax.conv_general_dilated(
        x, kernel,
        window_strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        dimension_numbers=CONV_DIMS,
    )
    if bias is not None:
        y = y + bias
    return y


def max_pool(x: jax.Array, window: int = 2, stride: int = 2,
             padding: int = 0) -> jax.Array:
    """MaxPool2d(window, stride, padding) — reference singlegpu.py:70 uses
    (2, 2, 0); ResNet-18's stem uses (3, 2, 1).

    Deliberately the ``reduce_window`` form: a reshape-max alternative
    with an elementwise first-tie VJP (``ops/pool_candidates.py``)
    forces activation relayouts in the composed step (its window-view
    transposes fight the conv layouts); not measured on this stack."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding=((0, 0), (padding, padding), (padding, padding), (0, 0)),
    )


def linear(x: jax.Array, weight: jax.Array,
           bias: Optional[jax.Array] = None) -> jax.Array:
    """x @ weight (+ bias). weight: [in, out]."""
    y = x @ weight
    if bias is not None:
        y = y + bias
    return y


def global_avg_pool(x: jax.Array) -> jax.Array:
    """[N,H,W,C] -> [N,C] mean over spatial dims (reference x.mean([2,3]),
    singlegpu.py:79)."""
    return x.mean(axis=(1, 2))


class BatchNormState(NamedTuple):
    """Running statistics (the reference's BN buffers)."""
    mean: jax.Array
    var: jax.Array


def batch_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               state: BatchNormState, *, train: bool,
               momentum: float = 0.1, eps: float = 1e-5,
               ) -> Tuple[jax.Array, BatchNormState]:
    """BatchNorm2d with exact torch semantics.

    Training normalises with the *biased* batch variance but updates the
    running variance with the *unbiased* one (Bessel-corrected), momentum 0.1,
    eps 1e-5 — the torch defaults the reference relies on (singlegpu.py:65).
    Under data parallelism the batch statistics are per-replica: the reference
    deliberately leaves SyncBatchNorm commented out (multigpu.py:127), and
    shard_map gives the same per-shard semantics for free.

    Statistics are accumulated in fp32 even when ``x`` is bf16 so the
    mixed-precision path stays stable.  The statistics encoding (one-pass
    per-shard variance, centered two-pass under sync) lives in
    :func:`_bn_stats`, shared with the fused :func:`bn_relu` so the two
    ops cannot drift.
    """
    if train:
        batch_mean, batch_var, count = _bn_stats(x.astype(jnp.float32),
                                                 _bn_sync_axis())
        unbiased = batch_var * (count / max(count - 1.0, 1.0))
        new_state = _blend_running_stats(state, batch_mean, unbiased,
                                         momentum)
        mean, var = batch_mean, batch_var
    else:
        new_state = state
        mean, var = state.mean, state.var
    inv = lax.rsqrt(var + eps) * scale
    y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + bias.astype(x.dtype)
    return y, new_state


def _blend_running_stats(state: BatchNormState, batch_mean, unbiased_var,
                         momentum: float) -> BatchNormState:
    """The torch running-buffer EMA (momentum 0.1 default) — one encoding
    shared by :func:`batch_norm` and :func:`bn_relu` so the fused and
    unfused ops' checkpointed BN buffers cannot drift."""
    return BatchNormState(
        mean=(1.0 - momentum) * state.mean + momentum * batch_mean,
        var=(1.0 - momentum) * state.var + momentum * unbiased_var,
    )


def _bn_stats(xf: jax.Array, axis: Optional[str]):
    """Batch statistics in fp32 — the ONE encoding of the trade-off both
    :func:`batch_norm` and :func:`bn_relu` use: one-pass ``E[x^2]-E[x]^2``
    per-shard (XLA fuses both channel reductions into a single read of the
    activation — BN is bandwidth-bound on TPU; the whole-step effect on
    the chip is not measured on this stack), or the better-conditioned centered
    two-pass form when syncing over ``axis`` (under cancellation the
    one-pass form amplifies the psum's rounding ~10x more than centering
    does, verified against an f64 reference — sync-BN is opt-in, so the
    extra read of x buys the better statistics, the same choice torch's
    SyncBatchNorm makes).  Returns (mean, biased_var, count); ``count`` is
    the total reduced element count, always a Python float (shapes and
    mesh axis sizes are static at trace time)."""
    n = float(xf.shape[0] * xf.shape[1] * xf.shape[2])
    if axis is None:
        mean = xf.mean(axis=(0, 1, 2))
        var = jnp.maximum((xf * xf).mean(axis=(0, 1, 2)) - mean * mean, 0.0)
        return mean, var, n
    r = lax.axis_size(axis)
    mean = lax.psum(xf.mean(axis=(0, 1, 2)), axis) / r
    d = xf - mean
    var = lax.psum((d * d).mean(axis=(0, 1, 2)), axis) / r
    return mean, var, n * r


def _bn_relu_fwd_impl(eps: float, axis: Optional[str], x, scale, bias):
    xf = x.astype(jnp.float32)
    mean, var, count = _bn_stats(xf, axis)
    inv = lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    z = jnp.maximum(xhat * scale + bias, 0.0).astype(x.dtype)
    unbiased = var * (count / max(count - 1.0, 1.0))
    return z, mean, unbiased, (x, mean, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_relu_train(eps: float, axis: Optional[str], grad_axis: Optional[str],
                   x, scale, bias):
    """Fused training-mode BatchNorm+ReLU with a hand-written VJP.

    The VJP recomputes the ReLU mask (``x̂·γ+β > 0``) and x̂ from ``x``
    alone, so the whole backward touches only ``(x, dz)``: one fused
    reduction pass (dβ, dγ) and one fused elementwise pass (dx) — the
    5-activation-pass minimum, exact fp32 math (the mask recompute is
    bit-exact against the forward's own ŷ).  NB autodiff does not need
    more passes here: the optimized HLO shows XLA:TPU reaching the same
    structure by fusing the reductions into the conv epilogues, so no
    step-level gain is claimed (not measured on this stack) and the op is
    kept for the explicit structure + collective semantics.

    Returns ``(z, batch_mean, unbiased_var)``; the running-stats blend
    happens outside in plain JAX so its (normally zero) cotangents stay
    differentiable — the bwd folds them in as the exact dμ/dσ² terms.
    """
    z, mean, unbiased, _ = _bn_relu_fwd_impl(eps, axis, x, scale, bias)
    return z, mean, unbiased


def _bn_relu_fwd(eps, axis, grad_axis, x, scale, bias):
    z, mean, unbiased, res = _bn_relu_fwd_impl(eps, axis, x, scale, bias)
    return (z, mean, unbiased), (*res, scale, bias)


def _bn_relu_bwd(eps, axis, grad_axis, res, cts):
    x, mean, inv, scale, bias = res
    ct_z, ct_mean, ct_unb = cts
    xf = x.astype(jnp.float32)
    n = float(xf.shape[0] * xf.shape[1] * xf.shape[2])
    count = n if axis is None else n * lax.axis_size(axis)
    xhat = (xf - mean) * inv
    # ReLU mask recomputed from x — identical expression to the forward's
    # ŷ, so the mask is bit-consistent and z is never read here.
    dy = jnp.where(xhat * scale + bias > 0.0,
                   ct_z.astype(jnp.float32), 0.0)
    dbeta = dy.sum(axis=(0, 1, 2))
    dgamma = (dy * xhat).sum(axis=(0, 1, 2))
    # Two distinct reductions share these sums — keep them apart:
    # 1. dx's mean-subtraction terms need the sums over the STATISTICS
    #    batch: local for per-shard BN, psum'd over ``axis`` for sync-BN
    #    (each shard's dx then carries the cross-shard terms the stats
    #    psum's transpose would have produced).
    # 2. The RETURNED dγ/dβ are the cotangents of the local objective —
    #    psum'd over ``grad_axis`` only under a replicated-params core
    #    (the DDP all-reduce); the ZeRO local-grads core leaves grad_axis
    #    unset and does its own psum_scatter later, sync-BN or not (γ/β
    #    reach the local loss only through the local normalize, so their
    #    local cotangents contain no cross-shard terms even under sync).
    sbeta, sgamma = dbeta, dgamma
    if axis is not None:
        assert grad_axis is None or grad_axis == axis, (grad_axis, axis)
        sbeta = lax.psum(dbeta, axis)
        sgamma = lax.psum(dgamma, axis)
    # dx through the normalisation (biased-var form), plus the exact terms
    # for the running-stats outputs' cotangents (zeros in training — the
    # stats are aux outputs — so XLA folds them away).
    dvar = ct_unb * (count / max(count - 1.0, 1.0))
    dx = (inv * (dy * scale - (sbeta * scale) / count
                 - xhat * ((sgamma * scale) / count))
          + ct_mean / count + dvar * (2.0 / count) * (xf - mean))
    if grad_axis is not None:
        dbeta = sbeta if axis is not None else lax.psum(dbeta, grad_axis)
        dgamma = sgamma if axis is not None else lax.psum(dgamma, grad_axis)
    return dx.astype(x.dtype), dgamma, dbeta


_bn_relu_train.defvjp(_bn_relu_fwd, _bn_relu_bwd)


def bn_relu(x: jax.Array, scale: jax.Array, bias: jax.Array,
            state: BatchNormState, *, train: bool,
            momentum: float = 0.1, eps: float = 1e-5,
            ) -> Tuple[jax.Array, BatchNormState]:
    """``relu(batch_norm(x))`` as one op — semantics identical to
    :func:`batch_norm` followed by ``jax.nn.relu`` (torch defaults, same
    sync-BN context), with the hand-written backward of
    :func:`_bn_relu_train` (reads only ``(x, dz)`` — the 5-activation-pass
    minimum).  No step-level gain over the autodiff composition is
    claimed (XLA:TPU already fuses the BN reductions into conv epilogues
    and reaches the same pass structure; chip time not measured on this
    stack); the op is kept because it makes that
    traffic structure explicit and pins the collective semantics
    (bn_grad_axis) the ZeRO/replicated cores rely on.  Use for
    conv→BN→ReLU chains; use :func:`batch_norm` where no ReLU immediately
    follows (e.g. ResNet shortcut branches)."""
    if not train:
        # Delegate so eval numerics stay BIT-identical to the composition
        # (tests/test_bn_relu.py::test_eval_mode_bit_identical).
        y, _ = batch_norm(x, scale, bias, state, train=False,
                          momentum=momentum, eps=eps)
        return jax.nn.relu(y), state
    z, batch_mean, unbiased = _bn_relu_train(eps, _bn_sync_axis(),
                                             _bn_grad_axis(), x, scale, bias)
    return z, _blend_running_stats(state, batch_mean, unbiased, momentum)


def dropout(key: jax.Array, x: jax.Array, rate: float,
            train: bool) -> jax.Array:
    """Inverted dropout (torch convention) — DeepNN uses rate 0.1
    (singlegpu.py:36)."""
    if not train or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))
