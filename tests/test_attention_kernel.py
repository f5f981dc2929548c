"""The token model's attention kernel (ops/attention.py) on the CPU,
through the Pallas interpreter at small shapes: against the XLA loop it
replaces on the chip and against a dense float32 answer, forward and the
three gradients; the sum over a key-value head's query heads; the tile
classifier; a call inside ``shard_map``; who takes which path; and that
the classifier cells' processes cannot see any of it.  (The kernels at
the cell's shape through the TPU's compiler: tests/test_pallas_gather.py,
where the described chip's fixture lives.)"""
import functools
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
