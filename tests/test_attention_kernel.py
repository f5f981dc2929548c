"""The token models' attention kernels (ops/attention.py) on the CPU,
through the Pallas interpreter at small shapes: against the XLA loop they
replace on the chip and against a dense float32 answer, forward and the
gradients; the sum over a key-value head's query heads; the same under a
window at ``laguna``'s groups (R = 6 and 8); the tile classifier, with and
without a window; a call inside ``shard_map``; who
takes which path; the two-map form (``diff_attention``: a 64-wide key pair
beside a 128-wide value, a window) as ``models/sambay.py`` runs it; that
``causal_gqa``'s kernels at the accepted cells' shapes are the parent's;
and that the classifier cells' processes cannot see any of it.  (The
kernels at the cell's shape through the TPU's compiler:
tests/test_pallas_gather.py, where the described chip's fixture lives.)"""
import functools
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ddp_tpu.models import nemotron_h as sysm
from ddp_tpu.models import sambay
from ddp_tpu.ops import attention
from ddp_tpu.ops.layers import linear
from ddp_tpu.parallel.mesh import DATA_AXIS, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAIRS, R, HD = 2, 4, 128
SCALE = 1.0 / math.sqrt(HD)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def operands(t, dtype=jnp.float32, pairs=PAIRS, r=R, seed=0):
    """``q, k, v`` and a cotangent for ``o``."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q, do = (jax.random.normal(key, (pairs, r, t, HD)).astype(dtype)
             for key in (ks[0], ks[3]))
    k, v = (jax.random.normal(key, (pairs, t, HD)).astype(dtype)
            for key in ks[1:3])
    return q, k, v, do


def dense(q, k, v):
    """Float32, the whole [T,T] square at once."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("prtd,psd->prts", q, k) * SCALE
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("prts,psd->prtd", jax.nn.softmax(s, axis=-1), v)


def xla_loop(q, k, v):
    return attention._xla_path(q, k, v, SCALE, q.dtype)


def kernel(q, k, v):
    return attention.causal_gqa(q, k, v, SCALE, True)


def out_and_grads(path, q, k, v, do):
    o, pull = jax.vjp(path, q, k, v)
    return (o,) + pull(do.astype(o.dtype))


@pytest.fixture
def blocks(monkeypatch, request):
    fwd, bwd = getattr(request, "param", ((128, 128), (128, 128)))
    monkeypatch.setattr(attention, "FWD_BLOCKS", fwd)
    monkeypatch.setattr(attention, "BWD_BLOCKS", bwd)
    monkeypatch.setattr(sysm, "ATTN_QUERY_BLOCK", 128)
    return fwd, bwd


UNEQUAL = ((128, 256), (256, 128))


@pytest.mark.parametrize("t,blocks", [
    (256, ((128, 128), (128, 128))), (384, ((128, 128), (128, 128))),
    (512, UNEQUAL), (512, UNEQUAL[::-1])], indirect=["blocks"])
def test_float32_matches_the_loop_and_the_dense_answer(t, blocks):
    q, k, v, do = operands(t)
    got = out_and_grads(kernel, q, k, v, do)
    for name, g, loop, exact in zip(
            ("o", "dq", "dk", "dv"), got,
            out_and_grads(xla_loop, q, k, v, do),
            out_and_grads(dense, q, k, v, do)):
        assert g.dtype == jnp.float32 and g.shape == exact.shape
        assert rel(g, exact) < 2e-6, (name, rel(g, exact))
        assert rel(g, loop) < 2e-6, (name, rel(g, loop))


@pytest.mark.parametrize("t,blocks", [
    (256, ((128, 128), (128, 128))), (384, ((128, 128), (128, 128))),
    (512, UNEQUAL)], indirect=["blocks"])
def test_bf16_is_no_further_from_float32_than_the_loop(t, blocks):
    """The kernel's precision is the loop's: bf16 operands, float32
    accumulation and statistics.  Neither is held to a number, only the
    kernel to the loop: no further from the float32 answer by more than a
    quarter."""
    q, k, v, do = operands(t)
    exact = out_and_grads(dense, q, k, v, do)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v, do))
    got = out_and_grads(kernel, *low)
    loop = out_and_grads(xla_loop, *low)
    for name, g, lo, ex in zip(("o", "dq", "dk", "dv"), got, loop, exact):
        assert g.dtype == jnp.bfloat16
        assert 0 < rel(g, ex) < 1.25 * rel(lo, ex) < 0.02, \
            (name, rel(g, ex), rel(lo, ex))


def test_dk_dv_are_summed_over_the_query_heads(blocks):
    """One call over ``R`` heads against the unrepeated key-value head
    gives the sum of ``R`` one-head calls' dK and dV."""
    q, k, v, do = operands(256)
    _, dq, dk, dv = out_and_grads(kernel, q, k, v, do)
    heads = [out_and_grads(kernel, q[:, h:h + 1], k, v, do[:, h:h + 1])
             for h in range(R)]
    assert rel(dq, jnp.concatenate([h[1] for h in heads], axis=1)) < 1e-6
    assert rel(dk, sum(h[2] for h in heads)) < 1e-6
    assert rel(dv, sum(h[3] for h in heads)) < 1e-6
    assert rel(dk, heads[0][2]) > 0.1


# -- causal_gqa under a window: laguna's window layers -------------------------

def windowed_dense(q, k, v, window):
    """Float32, the whole [T,T] square, a query's last ``window`` keys."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    t = q.shape[2]
    qi, si = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = jnp.asarray((si <= qi) & (si > qi - window))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("prtd,psd->prts", q, k) * SCALE
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("prts,psd->prtd", jax.nn.softmax(s, axis=-1), v)


def windowed_loop(q, k, v, window):
    """The XLA loop ``laguna`` runs where the kernel does not apply."""
    from ddp_tpu.ops import seq
    return jax.lax.map(lambda a: seq.attend_head(
        *a, scale=SCALE, cd=q.dtype, block=128, window=window), (q, k, v))


@pytest.mark.parametrize("window", [128, 256, 200, 100, 1000])
@pytest.mark.parametrize("r", [6, 8])
def test_windowed_kernel_matches_the_loop_and_the_dense_answer(
        blocks, r, window):
    """``causal_gqa(..., window=w)`` in float32 at the fourth token cell's
    groups (``R`` = 6 and 8), forward and the three gradients, against
    the XLA loop and the dense masked answer: windows that are whole key
    tiles and ones that are not, and one wider than the sequence."""
    q, k, v, do = operands(512, r=r, seed=r)
    got = out_and_grads(lambda q, k, v: attention.causal_gqa(
        q, k, v, SCALE, True, window), q, k, v, do)
    loop = out_and_grads(functools.partial(windowed_loop, window=window),
                         q, k, v, do)
    exact = out_and_grads(functools.partial(windowed_dense, window=window),
                          q, k, v, do)
    for name, g, lo, ex in zip(("o", "dq", "dk", "dv"), got, loop, exact):
        assert g.shape == ex.shape
        assert rel(g, ex) < 2e-6, (name, rel(g, ex))
        assert rel(g, lo) < 2e-6, (name, rel(g, lo))
    if window >= 512:
        causal = out_and_grads(kernel, q, k, v, do)
        for g, c in zip(got, causal):
            assert rel(g, c) < 1e-6


@pytest.mark.parametrize("r", [6, 8])
def test_windowed_kernel_in_bf16_is_no_further_than_the_loop(blocks, r):
    """In bf16 operands, the windowed kernel's distance from float32 is
    within a quarter of the loop's, as the self-check holds it."""
    q, k, v, do = operands(512, r=r, seed=r)
    exact = out_and_grads(functools.partial(windowed_dense, window=200),
                          q, k, v, do)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v, do)]
    got = out_and_grads(lambda q, k, v: attention.causal_gqa(
        q, k, v, SCALE, True, 200), *low)
    loop = out_and_grads(functools.partial(windowed_loop, window=200), *low)
    for name, g, lo, ex in zip(("o", "dq", "dk", "dv"), got, loop, exact):
        assert g.dtype == jnp.bfloat16
        assert 0 < rel(g, ex) < 1.25 * rel(lo, ex) < 0.02, \
            (name, rel(g, ex), rel(lo, ex))


def test_windowed_kernel_walks_only_the_windows_tiles(blocks, monkeypatch):
    """At 128 x 128 blocks and a window of 128 a query block visits two
    key tiles (the one across the window's lower edge and its diagonal),
    whatever the sequence's length: what the forward kernel's walk calls,
    counted at trace time."""
    visits = []
    real = attention._walk

    def counted(i, bq, bkv, step, window=None):
        visits.append(window)
        return real(i, bq, bkv, step, window)

    monkeypatch.setattr(attention, "_walk", counted)
    q, k, v, _ = operands(512, r=2)
    jax.make_jaxpr(lambda q, k, v: attention.causal_gqa(
        q, k, v, SCALE, True, 128))(q, k, v)
    assert visits and set(visits) == {128}
    for i in range(4):
        clear, visited, first, inside = attention.tile_kinds(i, 128, 128,
                                                             128)
        assert visited - first == (1 if i == 0 else 2)


@pytest.mark.parametrize("bq,bkv", [(128, 128), (128, 256), (256, 128),
                                    (512, 128), (128, 1024), (384, 256)])
def test_tile_kinds_against_the_mask(bq, bkv):
    """Clear, crossing and skipped tiles, for equal and unequal blocks,
    against the causal mask written out."""
    t = 3072
    mask = np.tril(np.ones((t, t), bool))
    for i in range(t // bq):
        clear, visited = attention.tile_kinds(i, bq, bkv)
        for j in range(t // bkv):
            tile = mask[i * bq:(i + 1) * bq, j * bkv:(j + 1) * bkv]
            kind = ("clear" if j < clear else
                    "crossing" if j < visited else "skipped")
            assert kind == ("clear" if tile.all() else
                            "crossing" if tile.any() else "skipped"), (i, j)
        traced = jax.jit(lambda i: attention.tile_kinds(i, bq, bkv))(
            jnp.int32(i))
        assert (int(traced[0]), int(traced[1])) == (clear, visited)


def test_inside_shard_map_with_check_vma(blocks):
    """The training step's setting: the kernels' results declare the mesh
    axes they vary over, forward and backward."""
    q, k, v, do = operands(256, pairs=4)
    mesh = make_mesh(2)
    sharded = jax.jit(jax.shard_map(
        functools.partial(out_and_grads, kernel), mesh=mesh,
        in_specs=(P(DATA_AXIS),) * 4, out_specs=(P(DATA_AXIS),) * 4,
        check_vma=True))
    for g, want in zip(sharded(q, k, v, do),
                       out_and_grads(kernel, q, k, v, do)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


def test_malformed_operands_are_refused(blocks):
    q, k, v, _ = operands(256)
    with pytest.raises(ValueError, match="wants k and v"):
        kernel(q, k[:, :128], v)
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(q[:, :, :192], k[:, :192], v[:, :192])
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(q[..., :64], k[..., :64], v[..., :64])


# -- who takes which path --------------------------------------------------------

def _parents_attention_mixer(p, x, dm, cd):
    """``attention_mixer`` as it stood before the kernel (b1b5cdc)."""
    bsz, t, _ = x.shape
    hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    with jax.named_scope("attn_proj"):
        q = linear(x, p["q"].astype(cd)).reshape(
            bsz, t, hkv, hq // hkv, hd).transpose(0, 2, 3, 1, 4)
        k = linear(x, p["k"].astype(cd)).reshape(
            bsz, t, hkv, hd).transpose(0, 2, 1, 3)
        v = linear(x, p["v"].astype(cd)).reshape(
            bsz, t, hkv, hd).transpose(0, 2, 1, 3)
    with jax.named_scope("attn_core"):
        o = lax.map(
            lambda qkv: sysm._attend_head(*qkv, scale=1.0 / math.sqrt(hd),
                                          cd=cd),
            (q.reshape(bsz * hkv, hq // hkv, t, hd),
             k.reshape(bsz * hkv, t, hd), v.reshape(bsz * hkv, t, hd)))
        o = o.reshape(bsz, hkv, hq // hkv, t, hd).transpose(
            0, 3, 1, 2, 4).reshape(bsz, t, hq * hd)
    with jax.named_scope("attn_proj"):
        return linear(o, p["o"].astype(cd))


def _mixer_grad(mixer, t, hd, cd=jnp.bfloat16):
    """``(function, arguments)``: the mixer's gradient at shapes."""
    dm = {"heads": 8, "kv_heads": 2, "head_dim": hd}
    d = 64
    p = {"q": jax.ShapeDtypeStruct((d, 8 * hd), jnp.float32),
         "k": jax.ShapeDtypeStruct((d, 2 * hd), jnp.float32),
         "v": jax.ShapeDtypeStruct((d, 2 * hd), jnp.float32),
         "o": jax.ShapeDtypeStruct((8 * hd, d), jnp.float32)}
    x = jax.ShapeDtypeStruct((2, t, d), cd)
    grad = jax.grad(lambda p, x: mixer(p, x, dm, cd).astype(
        jnp.float32).sum())
    return grad, (p, x)


def _mixer_text(mixer, t, hd):
    """Lowered, locations (the only place a scope's name shows) stripped."""
    grad, args = _mixer_grad(mixer, t, hd)
    return re.sub(r"loc\(.*?\)", "", jax.jit(grad).lower(*args).as_text())


@pytest.mark.parametrize("why,t,hd,tpu,budget", [
    ("not a TPU backend", 256, 128, False, None),
    ("hd is half a lane", 256, 64, True, None),
    ("t is no whole block", 320, 128, True, None),
    ("keys and values over the VMEM budget", 256, 128, True, 2**20),
])
def test_kernel_applies_refuses_and_the_mixer_is_the_parents(
        why, t, hd, tpu, budget, blocks, monkeypatch):
    """Where the kernel does not apply the mixer lowers to the parent's
    text, forward and backward: one algorithm chosen by shape, and the
    loop it falls back to is untouched."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: tpu)
    if budget:
        monkeypatch.setattr(attention, "VMEM_LIMIT_BYTES", budget)
    monkeypatch.setattr(attention, "TRACED", {"kernel": 0, "xla": 0})
    assert not attention.kernel_applies(t, hd, 2), why
    assert _mixer_text(sysm.attention_mixer, t, hd) \
        == _mixer_text(_parents_attention_mixer, t, hd)
    assert attention.TRACED == {"kernel": 0, "xla": 1}


def test_kernel_applies_at_the_cells_shape_and_the_mixer_takes_it(
        blocks, monkeypatch):
    """At the real constants the cell's shape (8,192 tokens, 128 a head,
    bf16) passes and 32,768 tokens in float32 do not; a mixer the kernel
    applies to holds both kernels and counts itself."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "TRACED", {"kernel": 0, "xla": 0})
    assert attention.kernel_applies(256, 128, 2)
    # Traced, not lowered: off the chip only the interpreter lowers.
    grad, args = _mixer_grad(sysm.attention_mixer, 256, 128)
    text = str(jax.make_jaxpr(grad)(*args))
    assert "causal_gqa_fwd" in text and "causal_gqa_bwd" in text
    assert attention.TRACED == {"kernel": 1, "xla": 0}
    monkeypatch.undo()
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    assert attention.kernel_applies(8192, 128, 2)
    assert attention.kernel_applies(8192, 128, 4)
    assert not attention.kernel_applies(32768, 128, 4)
    assert not attention.kernel_applies(8192 + 128, 128, 2)


# -- two maps a pair, one value: models/sambay.py's cores --------------------------------

DIFF_KW = dict(scale=1.0 / 8.0, gain=0.2, eps=1e-5)
DIFF_NAMES = ("o", "dq", "dk", "dv", "dlam", "dnorm")


def diff_operands(t, bsz=1, pairs=4, kvp=2, seed=3):
    """``q, k, v, lam, sub_norm`` as ``sambay.diff_core`` holds them (a
    pair's two 64-wide maps, a 128-wide value) and a cotangent."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (bsz, t, pairs, 2, 64)),
            jax.random.normal(ks[1], (bsz, t, kvp, 2, 64)),
            jax.random.normal(ks[2], (bsz, t, kvp, 128)),
            jnp.float32(0.79),
            1.0 + 0.1 * jax.random.normal(ks[3], (128,)),
            jax.random.normal(ks[4], (bsz, t, pairs * 128)))


def diff_dense(q, k, v, lam, sub_norm, *, window, cd, scale, gain, eps):
    """Float32, every map's whole [T,T] square at once."""
    del cd
    bsz, t, pairs, _, hd = q.shape
    kvp = k.shape[2]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("btgrmd,bsgmd->bgrmts",
                       q.reshape(bsz, t, kvp, pairs // kvp, 2, hd), k) * scale
        qi, si = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = si <= qi
        if window is not None:
            seen = seen & (si > qi - window)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrts,bsgd->btgrd", a[:, :, :, 0] - lam * a[:, :, :, 1],
                       v)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * (sub_norm * gain)).reshape(bsz, t, pairs * 2 * hd)


def diff_kernel(*a, **kw):
    return sambay._core_kernel(*a, interpret=True, **kw)


def diff_out_and_grads(path, window, cd, q, k, v, lam, sub_norm, do):
    o, pull = jax.vjp(functools.partial(path, window=window, cd=cd,
                                        **DIFF_KW), q, k, v, lam, sub_norm)
    return (o,) + pull(do.astype(o.dtype))


@pytest.fixture
def diff_blocks(monkeypatch, request):
    fwd, bwd = getattr(request, "param", ((128, 128), (128, 128)))
    monkeypatch.setattr(attention, "DIFF_FWD_BLOCKS", fwd)
    monkeypatch.setattr(attention, "DIFF_BWD_BLOCKS", bwd)
    monkeypatch.setattr(sambay, "ATTN_QUERY_BLOCK", 128)
    return fwd, bwd


# A window smaller than, equal to and larger than a key tile, one that no
# block divides, and the cell's 512 at its own blocks over 2,048 tokens.
DIFF_CASES = [
    (256, None, ((128, 128), (128, 128))), (384, None, ((128, 128),) * 2),
    (512, None, UNEQUAL), (512, None, UNEQUAL[::-1]),
    (512, 96, ((128, 128),) * 2), (512, 128, ((128, 128),) * 2),
    (512, 200, ((128, 128),) * 2), (512, 384, UNEQUAL),
    (512, 128, UNEQUAL[::-1]), (2048, 512, ((512, 512), (512, 512)))]


@pytest.mark.parametrize("t,window,diff_blocks", DIFF_CASES,
                         indirect=["diff_blocks"])
def test_two_maps_float32_match_the_loop_and_the_dense_answer(
        t, window, diff_blocks):
    """``RMSNorm((A1 - lam A2) V)`` and its five gradients: the kernel
    pair's path against ``_diff_block``'s loop and the dense answer."""
    args = diff_operands(t, pairs=2, kvp=1) if t > 512 else diff_operands(t)
    got = diff_out_and_grads(diff_kernel, window, jnp.float32, *args)
    for name, g, loop, exact in zip(
            DIFF_NAMES, got,
            diff_out_and_grads(sambay._core_loop, window, jnp.float32, *args),
            diff_out_and_grads(diff_dense, window, jnp.float32, *args)):
        # lam's gradient is one nearly cancelling sum over everything.
        tol = 1e-4 if name == "dlam" else 3e-6
        assert g.dtype == jnp.float32 and g.shape == exact.shape
        assert rel(g, exact) < tol, (name, rel(g, exact))
        assert rel(g, loop) < tol, (name, rel(g, loop))


@pytest.mark.parametrize("t,window,diff_blocks", [
    (256, None, ((128, 128),) * 2), (512, None, UNEQUAL),
    (512, 128, ((128, 128),) * 2), (512, 200, UNEQUAL[::-1])],
    indirect=["diff_blocks"])
def test_two_maps_bf16_are_no_further_from_float32_than_the_loop(
        t, window, diff_blocks):
    """Forward the kernel casts each map's probabilities apart where the
    loop casts their difference; backward it subtracts them in float32 as
    the loop does.  Neither is held to a number, only the kernel to the
    loop (``lam``'s gradient, a nearly cancelling sum, to three times)."""
    q, k, v, lam, sub_norm, do = diff_operands(t)
    exact = diff_out_and_grads(diff_dense, window, jnp.float32, q, k, v, lam,
                               sub_norm, do)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (lam, sub_norm,
                                                              do)
    got = diff_out_and_grads(diff_kernel, window, jnp.bfloat16, *low)
    loop = diff_out_and_grads(sambay._core_loop, window, jnp.bfloat16, *low)
    for name, g, lo, ex in zip(DIFF_NAMES, got, loop, exact):
        if name == "dlam":
            assert max(rel(g, ex), rel(lo, ex)) < 0.02, (rel(g, ex),
                                                         rel(lo, ex))
        else:
            assert 0 < rel(g, ex) < 1.25 * rel(lo, ex) < 0.02, \
                (name, rel(g, ex), rel(lo, ex))
    assert got[0].dtype == got[1].dtype == jnp.bfloat16


def test_two_maps_dk_dv_are_summed_over_the_query_pairs(diff_blocks):
    """One call over ``R`` query pairs against the unrepeated key-value
    pair gives the sum of ``R`` one-pair calls' dK and dV, and each query
    pair's own lanes of dQ."""
    q, k, v, lam, w, do = diff_operands(256, pairs=2, kvp=1)
    flat = tuple(a.reshape(1, 256, -1) for a in (q, k, v))

    def grads(q, k, v, do):
        _, pull = jax.vjp(lambda q, k, v: attention.diff_attention(
            q, k, v, lam, w, 0.125, 1e-5, None, True), q, k, v)
        return pull(do)

    dq, dk, dv = grads(*flat, do)
    halves = [grads(flat[0][..., h:h + 128], flat[1], flat[2],
                    do[..., h:h + 128]) for h in (0, 128)]
    assert rel(dq, jnp.concatenate([h[0] for h in halves], axis=-1)) < 1e-6
    assert rel(dk, sum(h[1] for h in halves)) < 1e-6
    assert rel(dv, sum(h[2] for h in halves)) < 1e-6
    assert rel(dk, halves[0][1]) > 0.1


@pytest.mark.parametrize("window", [100, 256, 512, 1000])
@pytest.mark.parametrize("bq,bkv", attention.SWEEP + ((128, 128), (384, 256)))
def test_tile_kinds_with_a_window_against_the_mask(bq, bkv, window):
    """Skipped, clear and crossing tiles under a window, for every block
    pair of the chip's sweep, against the mask written out; and that
    :func:`_walk` visits exactly the tiles that hold a visible key, the
    clear ones without a mask."""
    t = 3072
    qi, si = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = (si <= qi) & (si > qi - window)
    for i in range(t // bq):
        clear, visited, first, inside = attention.tile_kinds(i, bq, bkv,
                                                             window)
        assert (clear, visited) == attention.tile_kinds(i, bq, bkv)
        walked = {}
        attention_walk(i, bq, bkv, window, walked)
        for j in range(t // bkv):
            tile = mask[i * bq:(i + 1) * bq, j * bkv:(j + 1) * bkv]
            want = ("clear" if tile.all() else
                    "crossing" if tile.any() else "skipped")
            kind = ("skipped" if j < first or j >= visited else
                    "clear" if inside <= j < clear else "crossing")
            assert kind == want, (i, j, kind, want)
            assert walked.get(j, "skipped") == want, (i, j, walked)
    traced = jax.jit(lambda i: attention.tile_kinds(i, bq, bkv, window))(
        jnp.int32(1))
    assert tuple(int(x) for x in traced) == attention.tile_kinds(1, bq, bkv,
                                                                 window)


def attention_walk(i, bq, bkv, window, walked):
    """``_walk``'s loops run eagerly: which tiles, and whether masked."""
    def step(j, masked):
        assert int(j) not in walked
        walked[int(j)] = "crossing" if masked else "clear"

    with jax.disable_jit():
        attention._walk(i, bq, bkv, step, window)


def test_two_maps_inside_shard_map_with_check_vma(diff_blocks):
    """The training step's setting: the data split over the mesh, ``lam``
    and the norm's weight replicated, their gradients summed over it."""
    args = diff_operands(256, bsz=2)
    mesh = make_mesh(2)
    run = functools.partial(diff_out_and_grads, diff_kernel, None,
                            jnp.float32)
    data, whole = P(DATA_AXIS), P()
    specs = (data, data, data, whole, whole, data)
    sharded = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=specs,
        out_specs=(data, data, data, data, whole, whole), check_vma=True))
    for name, g, want in zip(DIFF_NAMES, sharded(*args), jax.jit(run)(*args)):
        assert g.shape == want.shape
        assert rel(g, want) < (1e-5 if name == "dlam" else 1e-6), name


def test_two_maps_malformed_operands_are_refused(diff_blocks):
    q, k, v, lam, w, _ = diff_operands(256)
    q, k, v = (a.reshape(1, 256, -1) for a in (q, k, v))
    rest = (lam, w, 0.125, 1e-5, None, True)
    with pytest.raises(ValueError, match="wants k and v"):
        attention.diff_attention(q, k[:, :128], v, *rest)
    with pytest.raises(ValueError, match="wants k and v"):
        attention.diff_attention(q[..., :192], k, v, *rest)
    with pytest.raises(ValueError, match="whole blocks"):
        attention.diff_attention(q[:, :192], k[:, :192], v[:, :192], *rest)


def test_kernel_applies_to_two_maps_beside_a_value(monkeypatch):
    """At the real constants: the second token cell's shape (8,192 tokens,
    64-wide keys beside a 128-wide value, bf16 and float32) passes, and so
    do whole lanes of equal width as before; other pairings of widths, a
    length that is no whole block, a budget too small and a process
    without a TPU do not."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    assert attention.kernel_applies(8192, 64, 2, 128)
    assert attention.kernel_applies(8192, 64, 4, 128)
    assert attention.kernel_applies(8192, 128, 2, 128)
    assert attention.kernel_applies(8192, 256, 2) \
        == attention.kernel_applies(8192, 256, 2, 256)
    assert not attention.kernel_applies(8192, 64, 2, 64)
    assert not attention.kernel_applies(8192, 32, 2, 64)
    assert not attention.kernel_applies(8192, 128, 2, 256)
    assert not attention.kernel_applies(8192 + 128, 64, 2, 128)
    assert not attention.kernel_applies(256, 8, 2, 16)       # the tiny preset
    monkeypatch.setattr(attention, "VMEM_LIMIT_BYTES", 2**20)
    assert not attention.kernel_applies(8192, 64, 2, 128)
    monkeypatch.undo()
    monkeypatch.setattr(attention, "_use_pallas", lambda: False)
    assert not attention.kernel_applies(8192, 64, 2, 128)


# -- causal_gqa at the accepted cells' shapes is the parent's ---------------------------

GOLDEN = os.path.join(ROOT, "tests", "golden", "causal_gqa_kernels.json")
# (pairs, query heads a pair, tokens, head width): the first token cell's
# and the third's (attention.SELF_CHECK_SHAPES), in bf16.
ACCEPTED_SHAPES = {"nemotron_h": (4, 16, 8192, 128),
                   "glm4_moe_lite": (40, 1, 8192, 256)}


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


def kernel_fingerprint(shape):
    """What ``causal_gqa``'s two ``pallas_call`` sites hold at ``shape``,
    forward and backward: each kernel's body as a jaxpr (which prints no
    source location), its grid, every operand's block shape and index map,
    the scratch (the body's trailing references) and the compiler's
    parameters."""
    p, r, t, hd = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((p, t, hd), jnp.bfloat16)

    def run(q, k, v, do):
        o, pull = jax.vjp(lambda q, k, v: attention.causal_gqa(
            q, k, v, 1.0 / math.sqrt(hd)), q, k, v)
        return (o,) + pull(do)

    calls = {}
    for eqn in _pallas_calls(jax.make_jaxpr(run)(q, kv, kv, q).jaxpr):
        grid = eqn.params["grid_mapping"]
        calls[str(eqn.params["name"])] = {
            "grid": [int(g) for g in grid.grid],
            "blocks": [[str(b.block_shape), str(b.index_map_jaxpr)]
                       for b in grid.block_mappings],
            "operands": [str(v.aval) for v in eqn.params["jaxpr"].invars],
            "scratch_operands": int(grid.num_scratch_operands),
            "results": [str(a) for a in eqn.params["out_avals"]],
            "compiler_params": str(eqn.params["compiler_params"]),
            "body": str(eqn.params["jaxpr"]),
        }
    return calls


@pytest.mark.parametrize("model", sorted(ACCEPTED_SHAPES))
def test_accepted_callers_kernels_are_the_parents(model):
    """``nemotron_h``'s and ``glm4_moe_lite``'s cells run the kernels they
    ran before the two-map form and the window arrived: body, grid, block
    specs and scratch equal the golden written from the parent commit
    (``python tests/test_attention_kernel.py`` rewrites it from whatever
    ``ddp_tpu`` is on the path)."""
    with open(GOLDEN) as f:
        golden = json.load(f)[model]
    got = kernel_fingerprint(ACCEPTED_SHAPES[model])
    assert sorted(got) == sorted(golden) == ["causal_gqa_bwd",
                                             "causal_gqa_fwd"]
    for name in got:
        for part, value in got[name].items():
            assert value == golden[name][part], (name, part)


# -- the classifier cells cannot see the change ------------------------------------------

_CLASSIFIER_PROCESS = """
import sys
import jax, jax.numpy as jnp
import ddp_tpu
from ddp_tpu.models import get_model
from ddp_tpu.optim.sgd import SGDConfig
from ddp_tpu.parallel.mesh import make_mesh
from ddp_tpu.train import Trainer
from ddp_tpu.train.step import init_train_state, make_train_step
for name in ("vgg", "resnet18"):
    model = get_model(name)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    step = make_train_step(model, SGDConfig(), lambda s: 0.1, make_mesh(1),
                           compute_dtype=jnp.bfloat16)
    batch = {"image": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((8,), jnp.int32)}
    assert "stablehlo" in step.lower(state, batch,
                                     jax.random.key(0)).as_text()
seen = sorted(m for m in sys.modules if m in (
    "ddp_tpu.ops.attention", "ddp_tpu.ops.ssd", "ddp_tpu.models.nemotron_h",
    "jax.experimental.pallas"))
print("SEEN", seen)
"""


def test_classifier_processes_never_import_the_kernel():
    """The benchmark's classifier cells build ``vgg`` and ``resnet18``
    through ``get_model`` and the Trainer: a fresh process that does so,
    and lowers a train step of each, has imported neither kernel's module
    (attention, the scan of ops/ssd.py) nor the token model's (nor
    Pallas), so nothing these kernels bring can reach their set-up, their
    programs or their cache keys."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run([sys.executable, "-c", _CLASSIFIER_PROCESS],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "SEEN []"


if __name__ == "__main__":
    with open(GOLDEN, "w") as f:
        json.dump({m: kernel_fingerprint(s) for m, s in
                   ACCEPTED_SHAPES.items()}, f, indent=1, sort_keys=True)
        f.write("\n")
    print("wrote", GOLDEN, "from", attention.__file__)
