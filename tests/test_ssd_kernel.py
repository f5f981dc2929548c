"""The token model's scan kernels (ops/ssd.py) on the CPU, through the
Pallas interpreter at small shapes: against ``ssd_chunked`` and
``gated_norm``, which they replace on the chip, and against the
reference's step-by-step recurrence, the result and the eight gradients;
the sum over a group's heads; a group wider than a grid step, a tile of
its heads at a time, one group of 16 heads as the cell's one group of 64;
the lane tiles;
a call inside ``shard_map``; who takes which path.  (The kernels at the
cell's shape through the TPU's compiler: tests/test_pallas_gather.py,
where the described chip's fixture lives; the classifier cells' fresh
process: tests/test_attention_kernel.py.)"""
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h as ref  # noqa: E402
from ddp_tpu.models import nemotron_h as sysm  # noqa: E402
from ddp_tpu.ops import ssd  # noqa: E402
from ddp_tpu.ops.layers import linear  # noqa: E402
from ddp_tpu.parallel.mesh import DATA_AXIS, make_mesh  # noqa: E402

Q, N = 128, 128
NAMES = ssd._NAMES   # o, dx, ddt, da, db, dc, dd, dz, dw
EPS = ssd._EPS
# Heads of 64 two a lane tile, a head a tile, four a tile; one group and two;
# one group of 16 heads of 64, two grid steps of ``ssd.HEAD_TILE`` heads.
SHAPES = {"p64": (4, 64, 2), "p128": (2, 128, 1), "p32": (8, 32, 2),
          "g1": (16, 64, 1)}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def operands(t, shape="p64", bsz=2, seed=0):
    h, p, g = SHAPES[shape]
    return ssd._operands(bsz, t, h, p, g, N, seed)


def kernel(*args):
    return ssd.ssd_scan(*args, Q, EPS, True)


def xla(cd):
    return functools.partial(ssd._xla_path, chunk=Q, cd=cd)


def recurrence(x, dt, a, b, c, d, z, weight):
    """The reference, a token a step, a sequence at a time, then the
    gate and the norm."""
    with jax.default_matmul_precision("highest"):
        y = jax.vmap(lambda x, dt, b, c: ref.recurrence(
            x, dt, a, b, c, d))(x, dt, b, c)
    return sysm.gated_norm(y.reshape(z.shape), z, weight, b.shape[2], EPS,
                           jnp.float32)


def out_and_grads(path, args, w):
    return jax.jit(ssd._vjp_of(path))(args, w)


@pytest.fixture
def steps(monkeypatch, request):
    fwd, bwd = getattr(request, "param", (2, 2))
    monkeypatch.setattr(ssd, "FWD_CHUNKS", fwd)
    monkeypatch.setattr(ssd, "BWD_CHUNKS", bwd)
    return fwd, bwd


@pytest.mark.parametrize("t,shape,steps", [
    (128, "p64", (1, 1)), (384, "p64", (1, 1)), (512, "p64", (2, 4)),
    (384, "p128", (4, 2)), (256, "p32", (2, 2)), (384, "g1", (1, 1)),
    (512, "g1", (2, 4))], indirect=["steps"])
def test_float32_matches_the_chunked_form_and_the_recurrence(t, shape,
                                                             steps):
    """Several chunks a sequence and several grid steps: the state is
    carried in scratch forward and its cotangent backward.  The gradients
    of ``dt`` and ``a`` sum terms of both signs over a chunk, so float32
    leaves them a digit less than the rest."""
    args, w = operands(t, shape)
    got = out_and_grads(kernel, args, w)
    for name, g, chunked, exact in zip(
            NAMES, got, out_and_grads(xla(jnp.float32), args, w),
            out_and_grads(recurrence, args, w)):
        assert g.dtype == jnp.float32 and g.shape == exact.shape
        tol = 1e-4 if name in ("ddt", "da") else 2e-5
        assert rel(g, exact) < tol, (name, rel(g, exact))
        assert rel(g, chunked) < tol, (name, rel(g, chunked))


@pytest.mark.parametrize("t,shape,steps", [
    (384, "p64", (1, 1)), (512, "p64", (4, 2)), (256, "p128", (2, 2)),
    (256, "p32", (2, 2)), (384, "g1", (1, 1))], indirect=["steps"])
def test_bf16_is_no_further_from_float32_than_the_chunked_form(t, shape,
                                                               steps):
    """The kernel's precision is ``ssd_chunked``'s: operands in bf16 where
    it casts, float32 decays, state and accumulation.  Neither is held to
    a number, only the kernel to the XLA path: no further from the float32
    answer by more than a quarter (twice, for the few numbers of ``da`` and
    ``dd``)."""
    args, w = operands(t, shape)
    exact = out_and_grads(xla(jnp.float32), args, w)
    low = ssd._low(args, jnp.bfloat16)
    got = out_and_grads(kernel, low, w)
    chunked = out_and_grads(xla(jnp.bfloat16), low, w)
    for name, g, lo, ex in zip(NAMES, got, chunked, exact):
        assert g.dtype == lo.dtype
        # A number a head, of four heads: two roundings of the same sum
        # differ by more than a quarter, by chance.
        room = 2.0 if name in ("da", "dd") else 1.25
        assert 0 < rel(g, ex) < room * rel(lo, ex) < 0.05, \
            (name, rel(g, ex), rel(lo, ex))


def test_db_dc_are_summed_over_the_groups_heads(steps):
    """The kernels' dB and dC, one ``[Q,N]`` a group, are the sums over
    the group's ``R`` heads of what each head's own copy of ``B``, ``C``
    would get (the XLA path with ``B``, ``C`` repeated a head, the norm's
    groups left as they are)."""
    (x, dt, a, b, c, d, z, weight), w = operands(256, "p64", seed=2)
    g, r = b.shape[2], x.shape[2] // b.shape[2]
    _, _, _, _, db, dc, *_ = out_and_grads(
        kernel, (x, dt, a, b, c, d, z, weight), w)

    def a_copy_a_head(b_h, c_h):
        y = sysm.ssd_chunked(x, dt, a, b_h, c_h, d, Q, jnp.float32)
        return sysm.gated_norm(y.reshape(z.shape), z, weight, g, EPS,
                               jnp.float32)

    _, pull = jax.vjp(a_copy_a_head, jnp.repeat(b, r, axis=2),
                      jnp.repeat(c, r, axis=2))
    db_h, dc_h = (v.reshape(*b.shape[:2], g, r, N) for v in pull(w))
    assert rel(db, db_h.sum(axis=3)) < 1e-5
    assert rel(dc, dc_h.sum(axis=3)) < 1e-5
    assert rel(db, db_h[:, :, :, 0]) > 0.1


@pytest.mark.parametrize("h,tile", [(16, 8), (8, 2), (8, 4)])
def test_one_group_wider_than_a_grid_step(h, tile, steps, monkeypatch):
    """One group of ``h`` heads in tiles of ``tile`` heads (2 to 4 grid
    steps a group): every tile reads the group's one ``B``, ``C``, whose
    ``dB``/``dC`` are the sums over EVERY head of the group (the XLA path
    with ``B``, ``C`` repeated a head, the norm's one group over all
    ``h * 64`` lanes left as it is), and the result and the eight
    gradients are the recurrence's."""
    monkeypatch.setattr(ssd, "HEAD_TILE", tile)
    assert ssd._head_tile(h, 64) == tile
    args, w = ssd._operands(2, 256, h, 64, 1, N, seed=3)
    got = out_and_grads(kernel, args, w)
    for name, g, exact in zip(NAMES, got, out_and_grads(recurrence, args,
                                                        w)):
        assert rel(g, exact) < (1e-4 if name in ("ddt", "da") else 2e-5), \
            (name, rel(g, exact))
    x, dt, a, b, c, d, z, weight = args

    def a_copy_a_head(b_h, c_h):
        y = sysm.ssd_chunked(x, dt, a, b_h, c_h, d, Q, jnp.float32)
        return sysm.gated_norm(y.reshape(z.shape), z, weight, 1, EPS,
                               jnp.float32)

    _, pull = jax.vjp(a_copy_a_head, jnp.repeat(b, h, axis=2),
                      jnp.repeat(c, h, axis=2))
    db_h, dc_h = pull(w)
    db, dc = got[NAMES.index("db")], got[NAMES.index("dc")]
    assert rel(db[:, :, 0], db_h.sum(axis=2)) < 1e-5
    assert rel(dc[:, :, 0], dc_h.sum(axis=2)) < 1e-5
    # A tile's heads alone are not the sum.
    assert rel(db[:, :, 0], db_h[:, :, :tile].sum(axis=2)) > 0.1


@pytest.mark.parametrize("r,p", [(8, 64), (2, 128), (1, 256), (16, 32),
                                 (4, 32), (2, 64)])
def test_lane_tiles_cover_every_head_once(r, p):
    tiles = ssd._tiles(r, p)
    assert sorted(h for _, _, heads in tiles for h in heads) \
        == list(range(r))
    lanes = np.zeros(r * p, int)
    for start, width, heads in tiles:
        assert start % 128 == 0 and width % 128 == 0
        assert len(heads) * p == width or (len(heads) == 1 and p == width)
        assert heads[0] * p == start
        lanes[start:start + width] += 1
    assert (lanes == 1).all()


def test_inside_shard_map_with_check_vma(steps):
    """The training step's setting, traced as the chip traces it (the
    compiled kernels, not the interpreter, whose jitted helpers forget
    what varies): forward and backward the kernels' results declare the
    mesh axes they vary over, the result and the data's gradients come out
    varying, and ``a``, ``d_skip`` and the norm's weight, parameters that
    do not vary, get gradients summed over the mesh."""
    args, w = operands(256, bsz=4)
    mesh = make_mesh(2)
    data, whole = P(DATA_AXIS), P()
    specs = (data, data, whole, data, data, whole, data, whole)
    params = ("da", "dd", "dw")
    seen = {}

    def inside(args, w):
        out = ssd._vjp_of(lambda *a: ssd.ssd_scan(*a, Q, EPS))(args, w)
        seen.update({name: jax.typeof(g).vma for name, g in zip(NAMES, out)})
        return out

    sharded = jax.shard_map(inside, mesh=mesh, in_specs=(specs, data),
                            out_specs=(data,) + specs, check_vma=True)
    text = str(jax.make_jaxpr(sharded)(args, w))
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert seen == {name: frozenset() if name in params
                    else frozenset({DATA_AXIS}) for name in NAMES}
    # And the same function's values, outside shard_map, are the sum of
    # its halves' (through the interpreter).
    whole_batch = out_and_grads(kernel, args, w)
    halves = [out_and_grads(kernel, tuple(
        v[i:i + 2] if v.ndim > 1 else v for v in args), w[i:i + 2])
        for i in (0, 2)]
    for name, g, lo, hi in zip(NAMES, whole_batch, *halves):
        joined = lo + hi if name in params else jnp.concatenate([lo, hi])
        assert rel(g, joined) < 1e-5, name


def test_malformed_operands_are_refused(steps):
    (x, dt, a, b, c, d, z, weight), _ = operands(256)
    with pytest.raises(ValueError, match="wants dt"):
        kernel(x, dt[:, :128], a, b, c, d, z, weight)
    with pytest.raises(ValueError, match="whole chunks of 128"):
        kernel(x[:, :192], dt[:, :192], a, b[:, :192], c[:, :192], d,
               z[:, :192], weight)
    with pytest.raises(ValueError, match="b and c"):
        kernel(x, dt, a, b, c[:, :128], d, z, weight)
    with pytest.raises(ValueError, match="the norm's weight"):
        kernel(x, dt, a, b, c, d, z, weight[:64])
    with pytest.raises(ValueError, match="whole chunks and lanes"):
        kernel(x, dt, a, b[..., :64], c[..., :64], d, z, weight)
    with pytest.raises(ValueError, match="whole chunks and lanes"):
        kernel(x[..., :48], dt, a, b, c, d, z[..., :192], weight[:192])


# -- who takes which path --------------------------------------------------------

def _parents_mamba_mixer(p, x, dm, cd):
    """``mamba_mixer`` as it stood before the kernel (524b846)."""
    F32 = jnp.float32
    bsz, t, _ = x.shape
    d_inner, g, n, k = dm["d_inner"], dm["g"], dm["n"], dm["k"]
    with jax.named_scope("ssm_proj"):
        zxbcdt = linear(x, p["in_proj"].astype(cd))
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + dm["conv_dim"]]
    dt = zxbcdt[..., d_inner + dm["conv_dim"]:]
    with jax.named_scope("ssm_conv"):
        padded = jnp.pad(xbc.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
        xbc = sum(padded[:, i:i + t] * p["conv_w"][i] for i in range(k)) \
            + p["conv_b"]
        xbc = jax.nn.silu(xbc).astype(cd)
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :d_inner].reshape(bsz, t, dm["h"], dm["p"])
        b = xbc[..., d_inner:d_inner + g * n].reshape(bsz, t, g, n)
        c = xbc[..., d_inner + g * n:].reshape(bsz, t, g, n)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"])
        y = sysm.ssd_chunked(xs, dt, a, b, c, p["D"], dm["chunk"], cd)
        y = sysm.gated_norm(y.reshape(bsz, t, d_inner), z, p["gate_norm"],
                            g, dm["eps"], cd)
    with jax.named_scope("ssm_proj"):
        return linear(y, p["out_proj"].astype(cd))


def _mixer_grad(mixer, t, h, p, g, n, chunk, cd=jnp.bfloat16):
    """``(function, arguments)``: the mixer's gradient at shapes."""
    dm, weights, x = ssd._mixer_operands(2, t, h, p, g, n, chunk, cd)
    grad = jax.grad(lambda w, x: mixer(w, x, dm, cd).astype(
        jnp.float32).sum())
    return grad, (weights, x)


def _mixer_text(mixer, *shape):
    """Lowered, locations (the only place a scope's name shows) stripped."""
    grad, args = _mixer_grad(mixer, *shape)
    return re.sub(r"loc\(.*?\)", "", jax.jit(grad).lower(*args).as_text())


@pytest.mark.parametrize("why,shape,tpu,budget", [
    ("not a TPU backend", (256, 16, 64, 2, 128, 128), False, None),
    ("a ragged T", (320, 16, 64, 2, 128, 128), True, None),
    ("a state of half a lane", (256, 16, 64, 2, 64, 128), True, None),
    ("a group's heads are part of a lane tile", (256, 16, 8, 2, 128, 128),
     True, None),
    ("a head that shares no lane tile evenly", (256, 8, 48, 1, 128, 128),
     True, None),
    ("a group's heads are part of a sublane tile", (256, 4, 64, 2, 128,
                                                    128), True, None),
    ("a chunk of half a lane tile", (256, 16, 64, 2, 128, 64), True, None),
    ("the blocks over the VMEM budget", (256, 16, 64, 2, 128, 128), True,
     2**19),
])
def test_kernel_applies_refuses_and_the_mixer_is_the_parents(
        why, shape, tpu, budget, steps, monkeypatch):
    """Where the kernel does not apply the mixer lowers to the parent's
    text, forward and backward: one algorithm chosen by shape, and the
    chunked form it falls back to is untouched."""
    monkeypatch.setattr(ssd, "_use_pallas", lambda: tpu)
    if budget:
        monkeypatch.setattr(ssd, "VMEM_LIMIT_BYTES", budget)
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    assert not ssd.kernel_applies(*shape, 2), why
    assert _mixer_text(sysm.mamba_mixer, *shape) \
        == _mixer_text(_parents_mamba_mixer, *shape)
    assert ssd.TRACED == {"kernel": 0, "xla": 1}


def test_kernel_applies_at_the_cells_shape_and_the_mixer_takes_it(
        monkeypatch):
    """At the real constants the cell's shape (8,192 tokens, 64 heads of
    64 in 8 groups, state 128, chunks of 128) passes in bf16 and float32;
    a mixer the kernel applies to holds both kernels and counts itself."""
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    shape = (256, 16, 64, 2, 128, 128)
    assert ssd.kernel_applies(*shape, 2)
    # Traced, not lowered: off the chip only the interpreter lowers.
    grad, args = _mixer_grad(sysm.mamba_mixer, *shape)
    text = str(jax.make_jaxpr(grad)(*args))
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert ssd.TRACED == {"kernel": 1, "xla": 0}
    assert ssd.kernel_applies(8192, 64, 64, 8, 128, 128, 2)
    assert ssd.kernel_applies(8192, 64, 64, 8, 128, 128, 4)
    assert not ssd.kernel_applies(8192 + 64, 64, 64, 8, 128, 128, 2)
    assert not ssd.kernel_applies(8192, 64, 64, 8, 128, 1024, 4)
    monkeypatch.setattr(ssd, "_use_pallas", lambda: False)
    assert not ssd.kernel_applies(8192, 64, 64, 8, 128, 128, 2)


def test_kernel_applies_at_one_group_and_the_mixer_takes_it(monkeypatch):
    """The one-group cell's shape (8,192 tokens, 64 heads of 64 in ONE
    group, state 128, chunks of 256) passes in bf16 at the real constants,
    a grid step holding ``HEAD_TILE`` heads (26 MiB by the VMEM count
    against the whole group's 196 MiB); the mixer at one group takes the
    tiled kernels and gates and norms over all of a token's lanes after
    them."""
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    assert ssd.kernel_applies(8192, 64, 64, 1, 128, 256, 2)
    assert ssd._head_tile(64, 64) == ssd.HEAD_TILE == 8
    assert ssd._vmem_bytes(256, 8 * 64, 128, 4, 2) < 27 * 2**20 \
        < 3 * ssd.VMEM_LIMIT_BYTES < ssd._vmem_bytes(256, 64 * 64, 128, 4, 2)
    grad, args = _mixer_grad(sysm.mamba_mixer, 256, 16, 64, 1, 128, 128)
    text = str(jax.make_jaxpr(grad)(*args))
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert ssd.TRACED == {"kernel": 1, "xla": 0}


# -- the first token cell's programs are the parent's ------------------------------

GOLDEN = os.path.join(ROOT, "tests", "golden", "ssd_kernels.json")
# (tokens, heads, head width, groups, state, chunk) of the first token
# cell's layer, in bf16: 8 groups of 8 heads, a group within a grid step.
FIRST_CELL = (8192, 64, 64, 8, 128, 128)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


def kernel_fingerprint(t, h, p, g, n, q):
    """What ``ssd_scan``'s two ``pallas_call`` sites hold at this shape,
    forward and backward (tests/test_attention_kernel.py's measure): each
    kernel's body as a jaxpr (which prints no source location), its grid,
    every operand's block shape and index map, the scratch and the
    compiler's parameters."""
    bf16, f32 = jnp.bfloat16, jnp.float32

    def spec(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    args = (spec((2, t, h, p)), spec((2, t, h), f32), spec((h,), f32),
            spec((2, t, g, n)), spec((2, t, g, n)), spec((h,), f32),
            spec((2, t, h * p)), spec((h * p,), f32))
    jaxpr = jax.make_jaxpr(ssd._vjp_of(lambda *a: ssd.ssd_scan(
        *a, q, EPS)))(args, spec((2, t, h * p))).jaxpr
    calls = {}
    for eqn in _pallas_calls(jaxpr):
        grid = eqn.params["grid_mapping"]
        calls[str(eqn.params["name"])] = {
            "grid": [int(s) for s in grid.grid],
            "blocks": [[str(b.block_shape), str(b.index_map_jaxpr)]
                       for b in grid.block_mappings],
            "operands": [str(v.aval) for v in eqn.params["jaxpr"].invars],
            "scratch_operands": int(grid.num_scratch_operands),
            "results": [str(a) for a in eqn.params["out_avals"]],
            "compiler_params": str(eqn.params["compiler_params"]),
            "body": str(eqn.params["jaxpr"]),
        }
    return calls


def nemotron_step_text(cd):
    """The lowered text of the tiny ``nemotron_h`` (9 layers, every kind)
    training step on one device, locations stripped: what the model runs
    off the chip, where no kernel applies."""
    from ddp_tpu.models import get_model
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.train.step import init_train_state, make_train_step
    config = dict(
        hidden_size=64, norm_eps=1e-5, mamba_num_heads=8, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        n_routed_experts=4, router_experts=16, experts_held=[4, 4],
        num_experts_per_tok=6, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
        norm_topk_prob=True, vocab_size=256, num_hidden_layers=9,
        hybrid_override_pattern="MEMEM*EME", time_step_min=0.001,
        time_step_max=0.1, time_step_floor=1e-4)
    model = get_model("nemotron_h", config)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    step = make_train_step(model, SGDConfig(), lambda s: 0.1, make_mesh(1),
                           compute_dtype=cd)
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = step.lower(state, {"image": ids, "label": ids},
                      jax.random.key(0)).as_text()
    return re.sub(r"loc\(.*?\)", "", text)


def _golden():
    import hashlib
    return {"first_cell_kernels": kernel_fingerprint(*FIRST_CELL),
            "nemotron_h_tiny_step_sha256": {
                name: hashlib.sha256(nemotron_step_text(cd).encode())
                .hexdigest() for name, cd in (("f32", None),
                                              ("bf16", jnp.bfloat16))}}


def test_the_first_cells_kernels_are_the_parents():
    """A group within a grid step (the first token cell's 8 heads) runs the
    kernels it ran before groups wider than a grid step arrived: body,
    grid, block specs and scratch equal the golden written from the parent
    commit (``python tests/test_ssd_kernel.py`` rewrites it from whatever
    ``ddp_tpu`` is on the path)."""
    import json
    with open(GOLDEN) as f:
        golden = json.load(f)["first_cell_kernels"]
    got = kernel_fingerprint(*FIRST_CELL)
    assert sorted(got) == sorted(golden) == ["ssd_bwd", "ssd_fwd"]
    for name in got:
        for part, value in got[name].items():
            assert value == golden[name][part], (name, part)


@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
def test_nemotron_h_tiny_step_is_the_parents(cd):
    """``nemotron_h``'s whole training step at its tiny size lowers to the
    parent's text, in float32 and bf16: the mixers it shares with
    ``granite_hybrid`` took sizes and the attention's scale through
    ``dims`` without moving it."""
    import hashlib
    import json
    with open(GOLDEN) as f:
        golden = json.load(f)["nemotron_h_tiny_step_sha256"]
    name = "f32" if cd is None else "bf16"
    assert hashlib.sha256(nemotron_step_text(cd).encode()).hexdigest() \
        == golden[name]


if __name__ == "__main__":
    import json
    with open(GOLDEN, "w") as f:
        json.dump(_golden(), f, indent=1, sort_keys=True)
        f.write("\n")
    print("wrote", GOLDEN, "from", ssd.__file__)
