"""The state-space mixers' causal convolution kernels (ops/causal_conv.py)
on the CPU, through the Pallas interpreter: against the XLA form of
``ops/seq.py``, which they replace on the chip, the result and the three
gradients at the three cells' channel counts and channel blocks over
several time blocks; where the kernels do not apply the convolution lowers
to the parent's text; a call inside ``shard_map``; which path each model's
tally names.  (The kernels at the cells' shapes through the TPU's
compiler: tests/test_pallas_gather.py, where the described chip's fixture
lives.)"""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import granite_hybrid, nemotron_h, sambay  # noqa: E402
from ddp_tpu.ops import causal_conv, seq  # noqa: E402
from ddp_tpu.ops.losses import cross_entropy_sum_count  # noqa: E402
from ddp_tpu.parallel.mesh import DATA_AXIS, make_mesh  # noqa: E402

NAMES = causal_conv._NAMES   # o, dx, dw, db
# Rows a grid step in these tests: three time blocks of a short sequence,
# so both halos cross a block boundary.
BT = 64
T = 3 * BT
CELL_CHANNELS = {"granite": 4352, "nemotron": 6144, "sambay": 5120}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def out_and_grads(fn, args, dy):
    return jax.jit(causal_conv._vjp_of(fn))(args, dy)


def _parents_causal_conv_silu(x, w, b, cd):
    """``ops/seq.py:causal_conv_silu`` as the parent commit had it."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    x = sum(padded[:, i:i + t] * w[i] for i in range(k)) + b
    return jax.nn.silu(x).astype(cd)


@pytest.fixture
def short_blocks(monkeypatch):
    monkeypatch.setattr(causal_conv, "TIME_BLOCK", BT)


@pytest.mark.parametrize("blocks", ["cell", "narrow"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cell", sorted(CELL_CHANNELS))
def test_kernels_match_the_xla_form(cell, dtype, blocks, short_blocks,
                                    monkeypatch):
    """The result and ``dx``, ``dw``, ``db`` through the interpreter at a
    cell's channel count, in the channel blocks the real constants give it
    (``cell``) and in blocks of 256 (``narrow``: 17, 24 or 20 of them), two
    sequences, three time blocks: in float32 to rounding, in bf16 within a
    rounding of a few results (the same float32 sums, in another order,
    rounded to bf16)."""
    c = CELL_CHANNELS[cell]
    itemsize = jnp.dtype(dtype).itemsize
    lanes = causal_conv._lanes(c, causal_conv.TIME_BLOCK, itemsize) \
        if blocks == "cell" else 256
    monkeypatch.setattr(causal_conv, "_lanes", lambda *_: lanes)
    (x, w, b), dy = causal_conv._operands(2, T, c, seed=len(cell))
    x, dy = x.astype(dtype), dy.astype(dtype)
    got = out_and_grads(functools.partial(
        causal_conv.conv_silu, cd=dtype, interpret=True), (x, w, b), dy)
    want = out_and_grads(functools.partial(seq.conv_silu_xla, cd=dtype),
                         (x, w, b), dy)
    for name, g, r in zip(NAMES, got, want):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        tol = 1e-6 if dtype == jnp.float32 or name in ("dw", "db") else 1e-4
        assert rel(g, r) < tol, (name, rel(g, r))


@pytest.mark.parametrize("why,t,c,k,itemsize,tpu", [
    ("not a TPU backend", 8192, 6144, 4, 2, False),
    ("channels not whole lanes", 8192, 6144 + 64, 4, 2, True),
    ("a ragged T", 8192 + 256, 6144, 4, 2, True),
    ("k - 1 a bf16 sublane tile", 8192, 6144, 17, 2, True),
    ("k - 1 a float32 sublane tile", 8192, 6144, 9, 4, True),
])
def test_kernel_applies_refuses_and_the_conv_is_the_parents(
        why, t, c, k, itemsize, tpu, monkeypatch):
    """Where the kernels do not apply the convolution lowers to the
    parent's text, forward and backward, and counts itself as the XLA
    form's."""
    monkeypatch.setattr(causal_conv, "_use_pallas", lambda: tpu)
    monkeypatch.setattr(causal_conv, "TRACED", {"kernel": 0, "xla": 0})
    assert not causal_conv.kernel_applies(t, c, k, itemsize), why
    cd = jnp.bfloat16 if itemsize == 2 else jnp.float32
    shapes = (jax.ShapeDtypeStruct((1, t, c), cd),
              jax.ShapeDtypeStruct((k, c), jnp.float32),
              jax.ShapeDtypeStruct((c,), jnp.float32))

    def text(fn):
        grad = jax.grad(lambda x, w, b: fn(x, w, b, cd).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        return jax.jit(grad).lower(*shapes).as_text(debug_info=False)

    assert text(seq.causal_conv_silu) == text(_parents_causal_conv_silu)
    assert causal_conv.TRACED == {"kernel": 0, "xla": 1}


def test_kernel_applies_at_the_cells_shapes(monkeypatch):
    """At the real constants the three cells' shapes (8,192 tokens, four
    taps) pass in bf16 and float32, each with a channel block of whole
    lanes that divides its channels and fits the VMEM budget; under a
    budget that a block of 512 lanes passes, granite's 4,352 = 17 x 256
    takes 256."""
    monkeypatch.setattr(causal_conv, "_use_pallas", lambda: True)
    bt = causal_conv.TIME_BLOCK
    for c in CELL_CHANNELS.values():
        for itemsize in (2, 4):
            assert causal_conv.kernel_applies(8192, c, 4, itemsize)
            lanes = causal_conv._lanes(c, bt, itemsize)
            assert lanes % 128 == 0 and c % lanes == 0
            assert causal_conv._vmem_bytes(bt, lanes, itemsize) \
                <= causal_conv.VMEM_BUDGET_BYTES
    monkeypatch.setattr(causal_conv, "VMEM_BUDGET_BYTES",
                        causal_conv._vmem_bytes(bt, 512, 2))
    assert causal_conv._lanes(4352, bt, 2) == 256
    assert causal_conv._lanes(6144, bt, 2) == 512


def test_malformed_operands_are_refused(short_blocks):
    (x, w, b), _ = causal_conv._operands(2, T, 256)
    for bad in ((x[:, :T - 8], w, b), (x, w[:, :128], b), (x, w, b[:128])):
        with pytest.raises(ValueError, match="conv_silu"):
            causal_conv.conv_silu(*bad, jnp.float32, interpret=True)


def test_inside_shard_map_with_check_vma(short_blocks):
    """The training step's setting, traced as the chip traces it (the
    compiled kernels, not the interpreter): forward and backward the
    kernels' results declare the mesh axes they vary over, the result and
    ``dx`` come out varying and ``w``, ``b``, parameters that do not vary,
    get gradients summed over the mesh."""
    (x, w, b), dy = causal_conv._operands(4, T, 256)
    mesh = make_mesh(2)
    data, whole = P(DATA_AXIS), P()
    seen = {}

    def inside(args, dy):
        out = causal_conv._vjp_of(functools.partial(
            causal_conv.conv_silu, cd=jnp.float32))(args, dy)
        seen.update({name: jax.typeof(g).vma for name, g in zip(NAMES, out)})
        return out

    sharded = jax.shard_map(inside, mesh=mesh,
                            in_specs=((data, whole, whole), data),
                            out_specs=(data, data, whole, whole),
                            check_vma=True)
    text = str(jax.make_jaxpr(sharded)((x, w, b), dy))
    assert "causal_conv_fwd" in text and "causal_conv_bwd" in text
    assert seen == {"o": frozenset({DATA_AXIS}), "dx": frozenset({DATA_AXIS}),
                    "dw": frozenset(), "db": frozenset()}
    # And the same function's values, outside shard_map, are the sum of
    # its halves' (through the interpreter).
    kernel = functools.partial(causal_conv.conv_silu, cd=jnp.float32,
                               interpret=True)
    whole_batch = out_and_grads(kernel, (x, w, b), dy)
    halves = [out_and_grads(kernel, (x[i:i + 2], w, b), dy[i:i + 2])
              for i in (0, 2)]
    for name, g, lo, hi in zip(NAMES, whole_batch, *halves):
        joined = lo + hi if name in ("dw", "db") else jnp.concatenate(
            [lo, hi])
        assert rel(g, joined) < 1e-5, name


# -- which path each model's tally names -----------------------------------------

def _nemotron_config():
    """Four Mamba-2 mixers of 16 heads of 64, two groups, state 64: a
    convolution over 1,280 channels."""
    return dict(
        hidden_size=64, norm_eps=1e-5, mamba_num_heads=16, mamba_head_dim=64,
        n_groups=2, ssm_state_size=64, conv_kernel=4, chunk_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        n_routed_experts=4, router_experts=16, experts_held=[4, 4],
        num_experts_per_tok=6, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
        norm_topk_prob=True, vocab_size=256, num_hidden_layers=9,
        hybrid_override_pattern="MEMEM*EME", time_step_min=0.001,
        time_step_max=0.1, time_step_floor=1e-4)


def _granite_config():
    """The file's tiny preset at two layers, one Mamba-2 mixer of 16 heads
    of 64 in one group, state 128: 1,280 channels."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite4_h_micro_stage0.json")) as f:
        pub = json.load(f)
    return overlay(pub, {**{k: v for k, v in pub["tiny"].items()
                            if not k.startswith("_")},
                         "num_hidden_layers": 2,
                         "layer_types": ["mamba", "attention"],
                         "mamba_n_heads": 16, "mamba_d_head": 64,
                         "mamba_d_state": 128, "mamba_chunk_size": 64})


def _sambay_config():
    """Layers 4-9 of 12 at hidden 64: two Mamba-1 mixers of 128 channels."""
    return dict(hidden_size=64, layer_norm_eps=1e-5, intermediate_size=96,
                num_attention_heads=8, num_key_value_heads=4,
                sliding_window=8, vocab_size=256, num_hidden_layers=6,
                layers_held=[4, 10], published={"num_hidden_layers": 12})


MODELS = {"nemotron_h": (nemotron_h, _nemotron_config, None, 4),
          "granite_hybrid": (granite_hybrid, _granite_config, "conv", 1),
          "sambay": (sambay, _sambay_config, "conv", 2)}


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_models_tallies_name_the_conv_path(model, tpu, short_blocks,
                                               monkeypatch):
    """A training step's forward and backward, traced (off the chip only
    the interpreter lowers) with a TPU backend stood in for the
    convolution alone: every Mamba mixer's convolution goes through the
    kernel pair and each model's tally says so (``nemotron_h`` has none of
    its own: ``causal_conv.TRACED`` is its); on this backend every one
    takes the XLA form."""
    module, config, key, mixers = MODELS[model]
    monkeypatch.setattr(causal_conv, "_use_pallas", lambda: tpu)
    monkeypatch.setattr(causal_conv, "TRACED", {"kernel": 0, "xla": 0})
    if key:
        monkeypatch.setattr(module, "TRACED",
                            dict.fromkeys(module.TRACED, 0))
    init, apply, (vocab, _) = module.build(config())
    params, state = jax.eval_shape(lambda: init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 2 * BT), jnp.int32)

    def loss(p, ids):
        logits, _ = apply(p, state, ids, train=True,
                          compute_dtype=jnp.bfloat16)
        s, n = cross_entropy_sum_count(logits, ids)
        return s / n

    text = str(jax.make_jaxpr(jax.grad(loss))(params, ids))
    want = {"kernel": mixers, "xla": 0} if tpu else {"kernel": 0,
                                                     "xla": mixers}
    assert causal_conv.TRACED == want
    if key:
        assert {p: module.TRACED[f"{key}_{p}"] for p in want} == want
    for name in ("causal_conv_fwd", "causal_conv_bwd"):
        assert (name in text) == tpu, name
