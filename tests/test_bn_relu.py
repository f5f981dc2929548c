"""The fused BN+ReLU custom VJP (ops/layers.py:bn_relu) — the round-3
fp32-roofline attack (VERDICT r2 #2).  Semantics must be indistinguishable
from ``relu(batch_norm(x))``; the win is backward HBM traffic (the VJP
reads only (x, dz) — never z, never a materialised dŷ), so these tests pin
the numerics against the autodiff composition in every mode the step
builders use it: train/eval, unsynced/sync-BN, fp32/bf16, and gradients
flowing through the running-stats outputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ddp_tpu.ops.layers import (BatchNormState, batch_norm, bn_grad_axis,
                                bn_relu, bn_sync_axis)


def _inputs(shape=(8, 4, 4, 6), dtype=jnp.float32):
    c = shape[-1]
    x = (jax.random.normal(jax.random.key(1), shape) * 2 + 0.3).astype(dtype)
    scale = jax.random.normal(jax.random.key(2), (c,)) * 0.5 + 1.0
    bias = jax.random.normal(jax.random.key(3), (c,)) * 0.2
    st = BatchNormState(jnp.zeros(c), jnp.ones(c))
    return x, scale, bias, st


def _ref(x, scale, bias, st, train=True):
    y, ns = batch_norm(x, scale, bias, st, train=train)
    return jax.nn.relu(y), ns


def test_forward_matches_composition():
    x, scale, bias, st = _inputs()
    z1, ns1 = _ref(x, scale, bias, st)
    z2, ns2 = bn_relu(x, scale, bias, st, train=True)
    np.testing.assert_allclose(z1, z2, atol=2e-6)
    np.testing.assert_allclose(ns1.mean, ns2.mean, atol=1e-6)
    np.testing.assert_allclose(ns1.var, ns2.var, atol=1e-6)


def test_eval_mode_bit_identical():
    """Eval keeps the exact batch_norm association (no custom VJP in play),
    so recorded eval numerics cannot move."""
    x, scale, bias, st = _inputs()
    st = BatchNormState(st.mean + 0.1, st.var * 1.3)
    z1, _ = _ref(x, scale, bias, st, train=False)
    z2, ns = bn_relu(x, scale, bias, st, train=False)
    assert np.array_equal(np.asarray(z1), np.asarray(z2))
    assert ns is st  # state untouched in eval


def test_backward_matches_autodiff_including_stats_path():
    """Gradients through z AND through the running-stats outputs (the
    normally-zero cotangents the VJP folds in as exact dμ/dσ² terms)."""
    x, scale, bias, st = _inputs()
    w = jax.random.normal(jax.random.key(4), x.shape)

    def loss(op, x, scale, bias):
        z, ns = op(x, scale, bias, st, train=True)
        return (z * w).sum() + 3.0 * ns.mean.sum() + 0.7 * ns.var.sum()

    g1 = jax.grad(lambda *a: loss(_ref_op, *a), argnums=(0, 1, 2))(
        x, scale, bias)
    g2 = jax.grad(lambda *a: loss(bn_relu, *a), argnums=(0, 1, 2))(
        x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def _ref_op(x, scale, bias, st, *, train):
    return _ref(x, scale, bias, st, train=train)


def test_relu_mask_consistent_at_clip_boundary():
    """The backward recomputes the mask from x; forward and backward must
    agree even when ŷ lands exactly on 0 (grad there is 0, torch/jax
    convention)."""
    # Engineer ŷ == 0 for one element: x == mean gives x̂ == 0; bias 0.
    x = jnp.zeros((4, 1, 1, 1), jnp.float32)
    scale = jnp.ones((1,))
    bias = jnp.zeros((1,))
    st = BatchNormState(jnp.zeros(1), jnp.ones(1))
    g = jax.grad(lambda x: bn_relu(x, scale, bias, st, train=True)[0].sum())(x)
    # All ŷ == 0 -> all masked -> zero gradient everywhere.
    np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_bf16_close_to_fp32(dtype):
    x, scale, bias, st = _inputs(dtype=jnp.float32)
    zf, _ = bn_relu(x, scale, bias, st, train=True)
    zb, _ = bn_relu(x.astype(dtype), scale, bias, st, train=True)
    assert zb.dtype == dtype
    np.testing.assert_allclose(np.asarray(zf),
                               np.asarray(zb).astype(np.float32),
                               atol=0.05, rtol=0.05)
    gb = jax.grad(lambda x: bn_relu(x, scale, bias, st,
                                    train=True)[0].astype(jnp.float32).sum())(
        x.astype(dtype))
    assert gb.dtype == dtype and bool(jnp.isfinite(
        gb.astype(jnp.float32)).all())


def test_sync_bn_matches_composition_under_shard_map():
    """Sync-BN: psum'd statistics and psum'd dγ/dβ inside the custom VJP
    must match the autodiff of the psum'd composition, per shard."""
    mesh = jax.make_mesh((8,), ("data",))
    x, scale, bias, st = _inputs(shape=(16, 4, 4, 6))
    w = jax.random.normal(jax.random.key(6), x.shape)

    def make(op):
        def body(x, scale, bias, w):
            # Mirror the replicated-params core's contexts (step.py):
            # sync the statistics AND mark the gradient all-reduce axis
            # exactly as the core does: under vma semantics the custom
            # VJP must psum dγ/dβ itself to match what autodiff's
            # composition gets from the replication transpose.
            with bn_sync_axis("data"), bn_grad_axis("data"):
                def lf(x, scale, bias):
                    z, ns = op(x, scale, bias, st, train=True)
                    # Running-stats cotangents are identically zero in
                    # real training (the stats are EMA aux outputs) and
                    # the hand-written VJP's terms for them encode the vma
                    # transpose scaling.
                    extra = ns.mean.sum() + 0.1 * ns.var.sum()
                    return lax.psum((z * w).sum(), "data") + extra
                return jax.value_and_grad(lf, argnums=(0, 1, 2))(
                    x, scale, bias)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P(), P(), P("data")),
            out_specs=(P(), (P("data"), P(), P()))))

    l1, g1 = make(bn_relu)(x, scale, bias, w)
    l2, g2 = make(_ref_op)(x, scale, bias, w)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_vgg_fused_grads_match_unfused_composition():
    """End-to-end through the full VGG: gradients with the fused bn_relu
    must match an unfused batch_norm+relu clone of the model to float
    precision, for every parameter.  (jax-vs-TORCH parity lives in
    tests/test_train_step.py's golden traces; at this depth raw torch conv
    backward drift is ~1e-3 and would mask a VJP bug.)"""
    import ddp_tpu.models.vgg as vgg_mod
    from ddp_tpu.ops.layers import (conv2d, global_avg_pool, linear,
                                    max_pool)

    params, stats = vgg_mod.init(jax.random.key(0))
    x = np.random.default_rng(0).standard_normal((8, 32, 32, 3),
                                                 np.float32) * 0.5
    y = np.arange(8) % 10

    def apply_unfused(params, xx):
        backbone = params["backbone"]
        i = 0
        for a in vgg_mod.ARCH:
            if a == "M":
                xx = max_pool(xx, 2, 2)
                continue
            xx = conv2d(xx, backbone[f"conv{i}"]["kernel"], stride=1,
                        padding=1)
            bn, st = backbone[f"bn{i}"], stats[f"bn{i}"]
            xx, _ = batch_norm(xx, bn["scale"], bn["bias"],
                               BatchNormState(st["mean"], st["var"]),
                               train=True)
            xx = jax.nn.relu(xx)
            i += 1
        cls = params["classifier"]
        return linear(global_avg_pool(xx), cls["weight"], cls["bias"])

    def loss_fused(params):
        logits, _ = vgg_mod.apply(params, stats, jnp.asarray(x), train=True)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(8), y])

    def loss_unfused(params):
        logits = apply_unfused(params, jnp.asarray(x))
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(8), y])

    g1 = jax.grad(loss_fused)(params)
    g2 = jax.grad(loss_unfused)(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g1),
                                 jax.tree_util.tree_leaves_with_path(g2)):
        scale = max(float(np.abs(np.asarray(b)).max()), 1e-12)
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))
