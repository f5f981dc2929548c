"""The second token model's selective-scan kernels (ops/selscan.py) on the
CPU, through the Pallas interpreter at small shapes with whole lane tiles:
against ``sambay.selective_scan`` with its softplus, skip and gate, which
they replace on the chip, and against the reference's token-a-step
recurrence, both results and the eight gradients, with a cotangent on both
results; several blocks of tokens and several channel tiles; a call inside
``shard_map``; who takes which path; and the five other cells' fresh
process, which imports none of it."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import sambay as ref  # noqa: E402
from ddp_tpu.models import sambay as sysm  # noqa: E402
from ddp_tpu.ops import selscan  # noqa: E402
from ddp_tpu.ops.layers import linear  # noqa: E402
from ddp_tpu.ops import seq  # noqa: E402
from ddp_tpu.parallel.mesh import DATA_AXIS, make_mesh  # noqa: E402

F32 = jnp.float32
N = 16
NAMES = selscan._NAMES  # gated, y, dx, dz, ddt_raw, ddt_bias, da, db, dc, dd
CELL = os.path.join(ROOT, "benchmark", "configs",
                    "phi4_mini_flash_stage14_19.json")


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def operands(t, ch, bsz=2, seed=0):
    return selscan._operands(bsz, t, ch, N, seed)


def kernel(*args):
    return selscan.selscan(*args, interpret=True)


def recurrence(x, z, dt_raw, dt_bias, a, b, c, d):
    """The reference, a token a step, a sequence at a time, then the gate."""
    dt = jax.nn.softplus(dt_raw + dt_bias)
    y = jax.vmap(lambda x, dt, b, c: ref.selective_scan(
        x, dt, a.T, b, c, d))(x, dt, b, c)
    return y * jax.nn.silu(z), y


def out_and_grads(path, args, w):
    return jax.jit(selscan._vjp_of(path))(args, w)


@pytest.fixture
def tiles(monkeypatch, request):
    fwd, bwd = getattr(request, "param", (128, 128))
    monkeypatch.setattr(selscan, "FWD_TILE", fwd)
    monkeypatch.setattr(selscan, "BWD_TILE", bwd)
    return fwd, bwd


@pytest.mark.parametrize("t,ch,tiles", [
    (128, 128, (128, 128)), (256, 256, (128, 128)), (384, 512, (256, 128)),
    (256, 384, (512, 512)), (256, 512, (128, 256))], indirect=["tiles"])
def test_float32_matches_the_xla_path_and_the_recurrence(t, ch, tiles):
    """Several blocks a sequence and several tiles of channels: the state
    is carried in scratch forward and its cotangent backward, the entering
    states are written and read, and ``dB``, ``dC`` (over tiles), ``dA``,
    ``dD`` and the bias's gradient (over blocks and sequences) are summed
    from partial sums.  BOTH results carry a cotangent."""
    args, w = operands(t, ch)
    got = out_and_grads(kernel, args, w)
    for name, g, xla, exact in zip(
            NAMES, got, out_and_grads(selscan._xla_path, args, w),
            out_and_grads(recurrence, args, w)):
        assert g.dtype == jnp.float32 and g.shape == exact.shape
        assert rel(g, exact) < 2e-5, (name, rel(g, exact))
        assert rel(g, xla) < 2e-5, (name, rel(g, xla))


@pytest.mark.parametrize("which", [0, 1])
def test_each_result_carries_its_own_cotangent(which, tiles):
    """A cotangent on ``gated`` alone and on ``y`` alone (the GMU layers'
    read of layer 16's scan): each gives the XLA path's gradients."""
    args, w = operands(256, 256, seed=3)
    w = tuple(v if i == which else jnp.zeros_like(v)
              for i, v in enumerate(w))
    for name, g, xla in zip(NAMES, out_and_grads(kernel, args, w),
                            out_and_grads(selscan._xla_path, args, w)):
        if name == "dz" and which == 1:  # the gate sees gated alone
            assert not np.asarray(g).any() and not np.asarray(xla).any()
        else:
            assert rel(g, xla) < 2e-5, (name, rel(g, xla))


@pytest.mark.parametrize("t,ch,tiles", [
    (256, 256, (128, 128)), (384, 256, (256, 256)),
    (128, 512, (256, 128))], indirect=["tiles"])
def test_bf16_is_no_further_from_float32_than_the_xla_path(t, ch, tiles):
    """The kernel's precision is the scope's: ``x``, ``z``, ``B``, ``C``
    read in bf16, ``dt``, ``A``, the state and ``y`` float32, the results
    written in bf16.  Neither is held to a number, only the kernel to the
    XLA path: no further from the float32 answer by more than a quarter."""
    args, w = operands(t, ch)
    exact = out_and_grads(selscan._xla_path, args, w)
    low = selscan._low(args, jnp.bfloat16)
    got = out_and_grads(kernel, low, w)
    xla = out_and_grads(selscan._xla_path, low, w)
    for name, g, lo, ex in zip(NAMES, got, xla, exact):
        assert g.dtype == lo.dtype and g.shape == lo.shape
        assert 0 < rel(g, ex) < 1.25 * rel(lo, ex) < 0.05, \
            (name, rel(g, ex), rel(lo, ex))


def test_inside_shard_map_with_check_vma(tiles):
    """The training step's setting, traced as the chip traces it (the
    compiled kernels, not the interpreter, whose jitted helpers forget
    what varies): forward and backward the kernels' results declare the
    mesh axes they vary over, the results and the data's gradients come
    out varying, and ``dt_bias``, ``a`` and ``d_skip``, parameters that do
    not vary, get gradients summed over the mesh."""
    args, w = operands(256, 256, bsz=4)
    mesh = make_mesh(2)
    data, whole = P(DATA_AXIS), P()
    specs = (data, data, data, whole, whole, data, data, whole)
    params = ("ddt_bias", "da", "dd")
    seen = {}

    def inside(args, w):
        out = selscan._vjp_of(selscan.selscan)(args, w)
        seen.update({name: jax.typeof(g).vma for name, g in zip(NAMES, out)})
        return out

    sharded = jax.shard_map(inside, mesh=mesh, in_specs=(specs, (data, data)),
                            out_specs=(data, data) + specs, check_vma=True)
    text = str(jax.make_jaxpr(sharded)(args, w))
    assert "selscan_fwd" in text and "selscan_bwd" in text
    assert seen == {name: frozenset() if name in params
                    else frozenset({DATA_AXIS}) for name in NAMES}
    # And the same function's values, outside shard_map, are the sum of
    # its halves' (through the interpreter).
    whole_batch = out_and_grads(kernel, args, w)
    halves = [out_and_grads(
        kernel, tuple(v[i:i + 2] if v.ndim == 3 else v for v in args),
        tuple(v[i:i + 2] for v in w)) for i in (0, 2)]
    for name, g, lo, hi in zip(NAMES, whole_batch, *halves):
        joined = lo + hi if name in params else jnp.concatenate([lo, hi])
        assert rel(g, joined) < 1e-5, name


def test_malformed_operands_are_refused(tiles):
    (x, z, dtr, bias, a, b, c, d), _ = operands(256, 256)
    with pytest.raises(ValueError, match="wants z and dt_raw"):
        kernel(x, z[:, :128], dtr, bias, a, b, c, d)
    with pytest.raises(ValueError, match="wants z and dt_raw"):
        kernel(x, z, dtr[..., :128], bias, a, b, c, d)
    with pytest.raises(ValueError, match="b and c"):
        kernel(x, z, dtr, bias, a, b, c[:, :128], d)
    with pytest.raises(ValueError, match="dt_bias and D"):
        kernel(x, z, dtr, bias[:128], a, b, c, d)
    with pytest.raises(ValueError, match="a "):
        kernel(x, z, dtr, bias, a.T, b, c, d)
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(x[:, :192], z[:, :192], dtr[:, :192], bias, a, b[:, :192],
               c[:, :192], d)
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(x[..., :192], z[..., :192], dtr[..., :192], bias[:192],
               a[:, :192], b, c, d[:192])
    with pytest.raises(ValueError, match="whole blocks"):
        kernel(x, z, dtr, bias, a[:12], b[..., :12], c[..., :12], d)


# -- who takes which path --------------------------------------------------------

def _parents_mamba_mixer(p, u, dm, cd):
    """``mamba_mixer`` as it stood before the kernel (6bc033b)."""
    n, r = dm["n"], dm["dt_rank"]
    with jax.named_scope("ssm_proj"):
        xs, z = jnp.split(linear(u, p["in_proj"].astype(cd)), 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        xs = seq.causal_conv_silu(xs, p["conv_w"], p["conv_b"], cd)
    with jax.named_scope("ssm_proj"):
        rbc = linear(xs, p["x_proj"].astype(cd))
        dt = jnp.matmul(rbc[..., :r], p["dt_proj"].astype(cd),
                        preferred_element_type=F32)
    with jax.named_scope("sel_scan"):
        dt = jax.nn.softplus(dt + p["dt_bias"])
        a = -jnp.exp(p["A_log"]).T
        y = sysm.selective_scan(xs, dt, a, rbc[..., r:r + n],
                                rbc[..., r + n:], sysm.SCAN_CHUNK) \
            + p["D"] * xs.astype(F32)
        gated = (y * jax.nn.silu(z.astype(F32))).astype(cd)
    with jax.named_scope("ssm_proj"):
        return linear(gated, p["out_proj"].astype(cd)), y.astype(cd)


def _mixer_grad(mixer, t, ch, n, cd=jnp.bfloat16):
    """``(function, arguments)``: the gradient of the mixer's two results
    at shapes."""
    dm, weights, u = selscan._mixer_operands(2, t, ch, n, cd)

    def loss(w, u):
        out, y = mixer(w, u, dm, cd)
        return out.astype(F32).sum() + y.astype(F32).sum()

    return jax.grad(loss), (weights, u)


def _mixer_text(mixer, *shape):
    """Lowered, locations (the only place a scope's name shows) stripped."""
    grad, args = _mixer_grad(mixer, *shape)
    return re.sub(r"loc\(.*?\)", "", jax.jit(grad).lower(*args).as_text())


@pytest.mark.parametrize("why,shape,tpu,budget", [
    ("not a TPU backend", (256, 256, 16), False, None),
    ("a ragged T", (320, 256, 16), True, None),
    ("channels that are not whole lanes", (256, 192, 16), True, None),
    ("the tests' 24 channels", (256, 24, 16), True, None),
    ("a state of half a sublane tile", (256, 256, 4), True, None),
    ("the blocks over the VMEM budget", (256, 256, 16), True, 2**19),
])
def test_kernel_applies_refuses_and_the_mixer_is_the_parents(
        why, shape, tpu, budget, monkeypatch):
    """Where the kernel does not apply the mixer lowers to the parent's
    text, forward and backward: one algorithm chosen by shape, and the
    ``selective_scan`` it falls back to is untouched."""
    monkeypatch.setattr(selscan, "_use_pallas", lambda: tpu)
    if budget:
        monkeypatch.setattr(selscan, "VMEM_LIMIT_BYTES", budget)
    monkeypatch.setattr(selscan, "TRACED", {"kernel": 0, "xla": 0})
    assert not selscan.kernel_applies(*shape, 2), why
    assert _mixer_text(sysm.mamba_mixer, *shape) \
        == _mixer_text(_parents_mamba_mixer, *shape)
    assert selscan.TRACED == {"kernel": 0, "xla": 1}


def test_kernel_applies_at_the_cells_shape_and_the_mixer_takes_it(
        monkeypatch):
    """At the real constants the cell's shape (8,192 tokens, 5,120
    channels, state 16) passes in bf16 and float32; a mixer the kernel
    applies to holds both kernels and counts itself."""
    monkeypatch.setattr(selscan, "_use_pallas", lambda: True)
    monkeypatch.setattr(selscan, "TRACED", {"kernel": 0, "xla": 0})
    assert selscan.kernel_applies(256, 256, 16, 2)
    # Traced, not lowered: off the chip only the interpreter lowers.
    grad, args = _mixer_grad(sysm.mamba_mixer, 256, 256, 16)
    text = str(jax.make_jaxpr(grad)(*args))
    assert "selscan_fwd" in text and "selscan_bwd" in text
    assert selscan.TRACED == {"kernel": 1, "xla": 0}
    assert selscan.kernel_applies(8192, 5120, 16, 2)
    assert selscan.kernel_applies(8192, 5120, 16, 4)
    assert not selscan.kernel_applies(8192 + 64, 5120, 16, 2)
    assert not selscan.kernel_applies(8192, 5120 + 64, 16, 2)
    monkeypatch.setattr(selscan, "_use_pallas", lambda: False)
    assert not selscan.kernel_applies(8192, 5120, 16, 2)


def test_the_cells_model_traces_two_mamba_layers_through_the_kernel(
        monkeypatch):
    """The cell's configuration at its own widths and length (2 sequences
    of 8,192 tokens, bf16), traced with the backend check patched: both
    Mamba layers of the stage take the kernel, none the XLA path."""
    monkeypatch.setattr(selscan, "_use_pallas", lambda: True)
    monkeypatch.setattr(selscan, "TRACED", {"kernel": 0, "xla": 0})
    with open(CELL) as f:
        config = json.load(f)
    init, apply, (vocab, t) = sysm.build(config)
    params = jax.eval_shape(lambda: init(jax.random.key(0))[0])
    logits = jax.eval_shape(
        lambda p, x: apply(p, {}, x, compute_dtype=jnp.bfloat16)[0], params,
        jax.ShapeDtypeStruct((2, t), jnp.int32))
    assert logits.shape == (2, t, vocab)
    assert selscan.TRACED == {"kernel": 2, "xla": 0}


# -- the five other cells cannot see the change ---------------------------------------

_OTHER_CELLS_PROCESS = """
import json, sys
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
with open("benchmark/configs/nemotron3_nano_30b_a3b_ep16.json") as f:
    config = json.load(f)
with open("benchmark/tests/tiny/train_lm.json") as f:
    config.update(json.load(f)["config"])
model = get_model("nemotron_h", config)
params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
seen = sorted(m for m in sys.modules if m in (
    "ddp_tpu.ops.selscan", "ddp_tpu.models.sambay"))
print("SEEN", seen, "ddp_tpu.models.nemotron_h" in sys.modules)
"""


def test_the_other_cells_processes_never_import_the_kernel():
    """The four classifier cells build ``vgg`` and ``resnet18`` and the
    first token cell ``nemotron_h`` through ``get_model``: a fresh process
    that does so has imported neither this kernel's module nor
    ``models/sambay.py``, so nothing this PR brings can reach their
    set-up, their programs or their cache keys."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run([sys.executable, "-c", _OTHER_CELLS_PROCESS],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "SEEN [] True"
