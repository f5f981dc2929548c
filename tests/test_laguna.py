"""``laguna`` (models/laguna.py) against its plain reference
(benchmark/reference/laguna.py), at a small size on the CPU: logits, loss
and every gradient leaf with a full and a window layer in the stack,
through the XLA loop and through the attention kernel in the Pallas
interpreter; YaRN's frequencies against the formula; the rotary embedding
sees ``i - j`` alone; the window hides what it should and no more; planted
faults; the shares of experts and of the vocabulary add up to the uncut
model; the step builder, the Trainer and the CLI; the operation count,
the configuration file and the rehearsed benchmark cell."""
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_laguna  # noqa: E402
from benchmark.reference import laguna as ref  # noqa: E402
from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import get_model  # noqa: E402
from ddp_tpu.models import laguna as sysm  # noqa: E402
from ddp_tpu.models import moe  # noqa: E402
from ddp_tpu.ops import attention  # noqa: E402
from ddp_tpu.ops.losses import IGNORE, cross_entropy_sum_count  # noqa: E402

CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "laguna_xs2_ep8.json")
T = 96


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny(**over):
    """The configuration file's own tiny preset (16 experts routed top-8,
    4 held, the five layers: full, window x 3, full) with ``over`` on
    top."""
    pub = published()
    return overlay(pub, {**{k: v for k, v in pub["tiny"].items()
                            if not k.startswith("_")}, **over})


def wide(**over):
    """The tiny preset with heads of 128 lanes, the kernel's width."""
    return tiny(head_dim=128, **over)


def seeded(config, seed=0, scale=4.0):
    """Weights from the program's initialiser, the matrices scaled up so
    that every part moves the result, the norms' weights moved off 1."""
    params, state = sysm.build(config)[0](jax.random.key(seed))
    key = jax.random.key(seed + 99)

    def move(path, p):
        if p.ndim == 1:
            k = jax.random.fold_in(key, sum(map(ord, jax.tree_util.keystr(
                path))))
            return p + 0.1 * jax.random.normal(k, p.shape)
        return p if path[-1].key == "embed" else p * scale

    return jax.tree_util.tree_map_with_path(move, params), state


def batch(seed=1, b=2, t=T, vocab=256):
    ids = jax.random.randint(jax.random.key(seed), (b, t), 0, vocab)
    targets = jnp.concatenate(
        [ids[:, 1:], jnp.full((b, 1), IGNORE, ids.dtype)], axis=1)
    return ids, targets


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def system_loss_and_grads(config, params, state, ids, targets, cd=None):
    apply = sysm.build(config)[1]

    def f(p):
        logits, new = apply(p, state, ids, train=True, compute_dtype=cd)
        s, c = cross_entropy_sum_count(logits, targets)
        return s / c, (logits, new)

    (loss, (logits, new)), grads = jax.jit(
        jax.value_and_grad(f, has_aux=True))(params)
    return loss, grads, logits, new


def reference_loss_and_grads(config, params, state, ids, targets):
    with jax.default_matmul_precision("highest"):
        loss, grads, logits0 = ref.loss_and_grads(
            config, params, state, np.asarray(ids), np.asarray(targets))
        logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    assert logits0.shape == (1,) + logits.shape[1:]
    np.testing.assert_allclose(logits0[0], logits[0], rtol=1e-5, atol=1e-5)
    return loss, grads, logits


def leaf_errors(grads, r_grads):
    return {jax.tree_util.keystr(k): rel(a, b) for (k, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_flatten_with_path(r_grads)[0])}


@pytest.fixture
def kernel_path(monkeypatch):
    """The attention kernel in the Pallas interpreter, at blocks of 128:
    what the chip runs, on the CPU."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "FWD_BLOCKS", (128, 128))
    monkeypatch.setattr(attention, "BWD_BLOCKS", (128, 128))
    monkeypatch.setattr(attention, "causal_gqa", functools.partial(
        attention.causal_gqa, interpret=True))
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    return sysm.TRACED


# -- (a) the system against the reference -------------------------------------------

@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
def test_matches_reference(cd):
    """Logits, the loss and EVERY gradient leaf through the XLA loop: to
    rounding in float32; in the cell's precision (bf16 products) within
    what bf16 operands cost at this size."""
    config = tiny()
    params, state = seeded(config)
    ids, targets = batch()
    loss, grads, logits, new = system_loss_and_grads(
        config, params, state, ids, targets, cd)
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    assert logits.dtype == jnp.float32 and logits.shape == (2, T, 256)
    errs = leaf_errors(grads, r_grads)
    assert set(errs) == set(leaf_errors(r_grads, r_grads))
    if cd is None:
        assert abs(float(loss) - r_loss) < 2e-5
        assert rel(logits, r_logits) < 2e-5
        assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
    else:
        assert abs(float(loss) - r_loss) < 5e-3
        assert rel(logits, r_logits) < 0.03
        assert max(errs.values()) < 0.4, max(errs, key=errs.get)
        assert sorted(errs.values())[len(errs) // 2] < 0.05
    # Every routed layer counted its assignments, and none found no room.
    for name in ("layer_01", "layer_02", "layer_03", "layer_04"):
        assert int(new[name]["assignments"].sum()) > 0
        assert int(new[name]["dropped"]) == 0
    assert "layer_00" not in new  # the dense layer routes nothing


@pytest.mark.parametrize("window", [128, 100], ids=["whole", "ragged"])
def test_kernel_path_matches_reference(kernel_path, window):
    """The same through ``causal_gqa`` (the interpreter) at heads of 128
    lanes, both kinds of core on the kernel, a window that is whole key
    tiles and one that is not."""
    config = wide(sliding_window=window)
    params, state = seeded(config, scale=2.0)
    ids, targets = batch(t=256)
    loss, grads, logits, _ = system_loss_and_grads(
        config, params, state, ids, targets)
    assert kernel_path == {"dense": 1, "expert": 4, "full_kernel": 2,
                           "full_xla": 0, "swa_kernel": 3, "swa_xla": 0}
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    assert abs(float(loss) - r_loss) < 2e-5
    assert rel(logits, r_logits) < 2e-5
    errs = leaf_errors(grads, r_grads)
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)


def test_eval_mode_gives_the_same_logits_and_counts_nothing():
    config = tiny()
    params, state = seeded(config)
    ids, _ = batch()
    apply = sysm.build(config)[1]
    logits, new = jax.jit(functools.partial(apply, train=False))(
        params, state, ids)
    trained, _ = jax.jit(functools.partial(apply, train=True))(
        params, state, ids)
    np.testing.assert_allclose(logits, trained, rtol=1e-6, atol=1e-6)
    assert int(new["layer_01"]["assignments"].sum()) == 0


@pytest.mark.parametrize("over,why", [
    (dict(experts_held=[0, 3]), "experts_held says 3"),
    (dict(experts_held=[14, 4]), "outside the router's 16"),
    (dict(layer_types=["full_attention"] * 4), "do not hold 5 layers"),
    (dict(gating=False), "per-head output gate"),
    (dict(moe_apply_router_weight_on_input=True), "per-head output gate"),
    (dict(rope_parameters={"full_attention": {
        "rope_type": "longrope", "rope_theta": 1e4},
        "sliding_attention": {"rope_theta": 1e4}}), "not longrope")])
def test_a_share_that_cannot_run_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        init, apply, _ = sysm.build(tiny(**over))
        params, state = init(jax.random.key(0))
        apply(params, state, batch()[0])


def test_the_kernel_branch_lays_heads_out_in_groups(monkeypatch):
    """Where ``kernel_applies`` says so each core goes through
    ``causal_gqa`` as (sequence, key-value head) pairs of ``R`` query
    heads, the window only in a window layer; with the XLA loop standing
    in for the kernel the result is the other branch's."""
    config = tiny()
    dm = sysm.dims(config)
    params, _ = seeded(config)
    x = jax.random.normal(jax.random.key(2), (2, T, 64))
    for i, kind in ((0, "full"), (1, "swa")):
        p = params["layers"][sysm.layer_name(i)]
        tables = {kind: sysm.rope_tables(T, dm["rope"][kind], dm["hd"])}
        want = sysm.gated_attention(p, x, kind, dm, jnp.float32, tables)
        seen = {}

        def stand_in(q, k, v, scale, window=None):
            seen.update(q=q.shape, k=k.shape, window=window)
            return jax.lax.map(lambda a: sysm._attend_head(
                *a, scale=scale, cd=jnp.float32, window=window), (q, k, v))

        with monkeypatch.context() as m:
            m.setattr(attention, "kernel_applies", lambda *a: True)
            m.setattr(attention, "causal_gqa", stand_in)
            got = sysm.gated_attention(p, x, kind, dm, jnp.float32, tables)
        r = dm["heads"][i] // 2
        assert seen == {"q": (4, r, T, 16), "k": (4, T, 16),
                        "window": 32 if kind == "swa" else None}
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_published_shapes_ask_for_the_kernel(monkeypatch):
    """``kernel_applies(8192, 128, 2)`` holds by its own count on a TPU,
    and the self-check runs both of the cell's cores."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    dm = sysm.dims(published())
    assert dm["hd"] == 128 and dm["window"] == 512
    assert attention.kernel_applies(8192, dm["hd"], 2)
    assert [s[0] for s in attention.GQA_WINDOW_SELF_CHECK_SHAPES] == [
        (16, 8, 8192, 128, 512), (16, 6, 8192, 128, None)]
    assert {h // dm["kv_heads"] for h in dm["heads"]} == {6, 8}


# -- (b) rotary embedding and the window --------------------------------------------

def _yarn_by_hand():
    """ISSUE 39's reading of the full layers' rotary, written out: 64
    lanes, theta 5e5; the correction range over 32 pairs from the
    rotations beta_fast = 64 and beta_slow = 1 make over 4,096 positions."""
    dim, base = 64, 500000.0
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(base))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(base))
    assert (round(low, 2), round(high, 2)) == (5.66, 15.8)
    low, high = math.floor(low), math.ceil(high)
    out = []
    for j in range(dim // 2):
        f = base ** (-2 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / 64 * ramp)
    return np.asarray(out), 0.1 * math.log(64) + 1


@pytest.mark.parametrize("side", ["system", "reference"])
def test_yarn_frequencies_and_factor_at_the_published_values(side):
    rp = published()["rope_parameters"]
    want, factor = _yarn_by_hand()
    if side == "system":
        got, mult = sysm.rope_frequencies(rp["full_attention"], 128)
    else:
        dim, got, mult = ref.inv_freq_of(rp["full_attention"], 128)
        assert dim == 64
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert mult == pytest.approx(factor, rel=1e-12) == 1.4158883083359672
    # Pairs 0-5 keep their frequency, 16-31 divide it by 64.
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-12)
    assert np.all((got[6:16] < plain[6:16]) & (got[6:16] > plain[6:16] / 64))
    # The window layers': every lane, theta 1e4, no factor.
    if side == "system":
        got, mult = sysm.rope_frequencies(rp["sliding_attention"], 128)
    else:
        dim, got, mult = ref.inv_freq_of(rp["sliding_attention"], 128)
        assert dim == 128
    np.testing.assert_allclose(got, 1e4 ** (-np.arange(0, 128, 2) / 128))
    assert mult == 1.0


def _rotated(side, x, kind):
    """``x`` [T, heads, 128] rotated as a layer of that kind does it."""
    rp = published()["rope_parameters"][
        {"full": "full_attention", "swa": "sliding_attention"}[kind]]
    if side == "reference":
        return ref.rotate(x, rp)
    cos, sin = sysm.rope_tables(x.shape[0], rp, x.shape[-1])
    return sysm.rope(x[None], cos, sin, jnp.float32)[0]


@pytest.mark.parametrize("kind", ["full", "swa"])
@pytest.mark.parametrize("side", ["system", "reference"])
def test_rotary_scores_depend_on_the_distance_alone(side, kind):
    """A query at ``i`` and a key at ``j`` score the same as at ``i + s``
    and ``j + s``; the unrotated lanes of a full layer pass unchanged; and
    the two sides agree."""
    q = jax.random.normal(jax.random.key(5), (1, 1, 128))
    k = jax.random.normal(jax.random.key(6), (1, 1, 128))
    t = 700
    qs = _rotated(side, jnp.broadcast_to(q, (t, 1, 128)), kind)
    ks = _rotated(side, jnp.broadcast_to(k, (t, 1, 128)), kind)
    dots = np.einsum("thd,shd->ts", np.asarray(qs, np.float64),
                     np.asarray(ks, np.float64))
    for i, j, s in ((10, 3, 500), (200, 199, 371), (5, 0, 600)):
        assert dots[i, j] == pytest.approx(dots[i + s, j + s], rel=2e-3,
                                           abs=2e-3)
    if kind == "full":
        np.testing.assert_array_equal(qs[:, :, 64:], jnp.broadcast_to(
            q[..., 64:], (t, 1, 64)))
        # Only the rotated half's part of a score is scaled, by the
        # factor's square: at position 0 nothing turns.
        assert float(jnp.sum(qs[0, 0, :64] * ks[0, 0, :64])) == \
            pytest.approx(1.4158883083359672 ** 2 * float(
                jnp.sum(q[0, 0, :64] * k[0, 0, :64])), rel=1e-5)
    other = _rotated("reference" if side == "system" else "system",
                     jnp.broadcast_to(q, (t, 1, 128)), kind)
    assert rel(qs, other) < 1e-5


def test_the_rotation_is_half_split():
    """Lane ``j`` turns with lane ``j + rot/2`` (``rotate_half``), not
    with its neighbour: a vector in lanes 0 and 32 of a full layer keeps
    its norm and stays in those two lanes."""
    x = jnp.zeros((300, 1, 128)).at[:, :, 0].set(1.0).at[:, :, 32].set(2.0)
    y = np.asarray(_rotated("system", x, "full"))
    lanes = np.flatnonzero(np.abs(y).max(axis=(0, 1)) > 1e-6)
    assert list(lanes) == [0, 32]
    np.testing.assert_allclose(np.linalg.norm(y[:, 0], axis=-1),
                               1.4158883083359672 * math.sqrt(5), rtol=1e-5)


@pytest.mark.parametrize("kind", ["full", "swa"])
def test_the_window_hides_what_lies_before_it(kind):
    """In a window layer the result at ``t`` moves with the input at
    ``s`` for ``t - 32 < s <= t`` and never for ``s <= t - 32``; in a full
    layer it moves for every ``s <= t``."""
    config = tiny()
    dm = sysm.dims(config)
    params, _ = seeded(config)
    p = params["layers"][sysm.layer_name(0 if kind == "full" else 1)]
    tables = {kind: sysm.rope_tables(T, dm["rope"][kind], dm["hd"])}
    x = jax.random.normal(jax.random.key(7), (1, T, 64))
    base = sysm.gated_attention(p, x, kind, dm, jnp.float32, tables)
    s = 20
    moved = sysm.gated_attention(p, x.at[:, s].add(1.0), kind, dm,
                                 jnp.float32, tables)
    diff = np.abs(np.asarray(moved - base)).max(axis=-1)[0]
    assert np.all(diff[:s] == 0)
    reach = s + 32 if kind == "swa" else T
    assert np.all(diff[s:reach] > 1e-6)
    assert np.all(diff[reach:] == 0)


# -- (c) planted faults ---------------------------------------------------------------

def _fault_gate_left_off(mp):
    mp.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x)
               if x.shape[-1] in (6, 8) else 1 / (1 + jnp.exp(-x)))


def _fault_no_window(mp):
    real = sysm.dims
    mp.setattr(sysm, "dims", lambda c: dict(real(c), window=10**6))


def _fault_whole_head_rotated(mp):
    real = sysm.rope_frequencies
    mp.setattr(sysm, "rope_frequencies", lambda rp, hd: real(
        dict(rp, partial_rotary_factor=1), hd))


def _fault_yarn_off(mp):
    real = sysm.rope_frequencies
    mp.setattr(sysm, "rope_frequencies", lambda rp, hd: real(
        {k: v for k, v in rp.items() if k != "rope_type"}, hd))


def _fault_factor_off(mp):
    real = sysm.rope_frequencies
    mp.setattr(sysm, "rope_frequencies", lambda rp, hd: (real(rp, hd)[0],
                                                         1.0))


def _fault_weights_not_normalised(mp):
    real = moe.route_weights
    mp.setattr(moe, "route_weights", lambda s, b, dm: real(
        s, b, dict(dm, norm_topk=False)))


def _fault_shared_dropped(mp):
    mp.setattr(moe, "shared_expert",
               lambda p, x, cd, form: jnp.zeros_like(x))


def _fault_interleaved_pairs(mp):
    def interleaved(x, cos, sin, out_dtype):
        half = cos.shape[1]
        pairs = x[..., :2 * half].astype(jnp.float32).reshape(
            *x.shape[:-1], half, 2)
        a, b = pairs[..., 0], pairs[..., 1]
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([(a * c - b * s), (b * c + a * s),
                                x[..., 2 * half:]], -1).astype(out_dtype)
    mp.setattr(sysm, "rope", interleaved)


FAULTS = {
    "gate_left_off": _fault_gate_left_off,
    "no_window": _fault_no_window,
    "whole_head_rotated": _fault_whole_head_rotated,
    "yarn_off": _fault_yarn_off,
    "attention_factor_off": _fault_factor_off,
    "weights_not_normalised": _fault_weights_not_normalised,
    "shared_expert_dropped": _fault_shared_dropped,
    "interleaved_pairs": _fault_interleaved_pairs,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each part of the layer's equations moves the logits by far more
    than float32 rounding: a program that leaves one out is caught.  The
    YaRN faults show at positions past the ramp's turn, so the sequence
    is 512 long."""
    config = tiny()
    params, state = seeded(config)
    ids, targets = batch(t=512)
    with jax.default_matmul_precision("highest"):
        r_logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    with monkeypatch.context() as mp:
        FAULTS[fault](mp)
        logits, _ = sysm.build(config)[1](params, state, ids)
    assert rel(logits, r_logits) > 1e-3, fault


# -- (d) the shares add up ------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts in eight shares of 2: the eight shares' routed results,
    plus the shared expert counted once, are the uncut reference's layer."""
    whole = tiny(num_experts=16, experts_held=[0, 16])
    params, state = seeded(whole)
    p, st = params["layers"]["layer_01"], state["layer_01"]
    x = jax.random.normal(jax.random.key(3), (2, T, 64))
    with jax.default_matmul_precision("highest"):
        uncut = jax.vmap(lambda row: ref.experts(p, row, ref.dims(whole)))(x)
    shared = moe.shared_expert(p, x.reshape(-1, 64), jnp.float32,
                               moe.SWIGLU).reshape(x.shape)
    total = shared
    for first in range(0, 16, 2):
        share = tiny(num_experts=2, experts_held=[first, 2])
        p_s = dict(p, **{n: p[n][first:first + 2]
                         for n in moe.SWIGLU.routed})
        st_s = dict(st, assignments=jnp.zeros((2,), jnp.int32))
        y, new = moe.expert_layer(p_s, st_s, x, sysm.dims(share),
                                  jnp.float32, train=True, form=moe.SWIGLU)
        total = total + (y - shared)
        assert int(new["dropped"]) == 0
    assert rel(total, uncut) < 1e-5


def test_vocabulary_slices_are_the_uncut_heads_rows():
    whole = tiny()
    params, state = seeded(whole)
    apply = sysm.build(whole)[1]
    for first in range(0, 256, 64):
        ids, _ = batch(seed=first, vocab=64)
        out, _ = apply(params, state, ids + first)
        share = tiny(vocab_size=64, vocab_held=[first, first + 64])
        p_s = dict(params, embed=params["embed"][first:first + 64],
                   head=params["head"][:, first:first + 64])
        sliced, _ = sysm.build(share)[1](p_s, state, ids)
        assert sliced.shape == (2, T, 64)
        np.testing.assert_allclose(sliced, out[..., first:first + 64],
                                   rtol=1e-5, atol=1e-5)


# -- (e) through the step builder, the Trainer and the CLI ---------------------------

def test_the_step_over_two_replicas_is_the_reference():
    """The step's loss is the reference's over the GLOBAL batch, the
    counters grow by the replicas' sum, and the momentum is the
    reference's gradient."""
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import (init_train_state, make_train_step,
                                    shard_batch)
    config = tiny()
    model = get_model("laguna", config)
    mesh = make_mesh(2)
    step = make_train_step(model, SGDConfig(lr=0.1, momentum=0.9,
                                            weight_decay=0.0),
                           lambda s: 0.1, mesh)
    state = init_train_state(*seeded(config))
    params, mstate = jax.device_get((state.params, state.batch_stats))
    ids, targets = batch(b=4)
    r_loss, r_grads, _ = reference_loss_and_grads(config, params, mstate,
                                                  ids, targets)
    new, loss = step(state, shard_batch(
        {"image": np.asarray(ids), "label": np.asarray(targets)}, mesh),
        jax.random.key(0))
    assert abs(float(loss) - r_loss) < 2e-5
    total = sum(int(new.batch_stats[n]["assignments"].sum())
                for n in ("layer_01", "layer_02", "layer_03", "layer_04"))
    # 4 x T tokens, top-8 of 16 with 4 held: two of the eight a token.
    assert 0.5 * 4 * 2 * 4 * T < total < 2.0 * 4 * 2 * 4 * T
    errs = leaf_errors(new.opt_state.momentum_buf, r_grads)
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)


def test_three_epochs_through_the_trainer(tmp_path, capsys):
    """The Trainer's stream path: losses fall, the routing counters reach
    the host where losses are flushed, ``python -m ddp_tpu.obs --prom``
    prints them, and a model with one depth exports no depth losses."""
    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.obs.__main__ import main
    from ddp_tpu.obs.registry import MetricsRegistry
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    registry = MetricsRegistry()
    model = get_model("laguna", tiny(seq_len=64))
    params, state = model.init(jax.random.key(0))
    loader = TrainLoader(synthetic_tokens(16, 64, 256, seed=0), 2, 1,
                         augment=False, seed=0)
    sched = functools.partial(triangular_lr, base_lr=0.5, num_epochs=60,
                              steps_per_epoch=8, peak_frac=0.3)
    trainer = Trainer(model, loader, params, state, mesh=make_mesh(1),
                      lr_schedule=sched,
                      sgd_config=SGDConfig(lr=0.5, momentum=0.9,
                                           weight_decay=0.0),
                      save_every=10**9, snapshot_path=None,
                      compute_dtype=jnp.bfloat16, registry=registry)
    trainer.train(3)
    losses = np.asarray(trainer.loss_history)
    assert losses.shape == (24,) and np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean()
    assert set(trainer.routing.totals) == {"layer_01", "layer_02",
                                           "layer_03", "layer_04"}
    assert all(v["dropped"] == 0 for v in trainer.routing.totals.values())
    assert trainer.lm_loss == []
    prom = tmp_path / "run.prom"
    prom.write_text(registry.exposition())
    assert main(["--prom", str(prom)]) == 0
    out = capsys.readouterr().out
    assert "layer_04" in out and "ddp_lm_loss" not in out


def test_cli_lists_the_model_and_the_registry_builds_it():
    from ddp_tpu.cli import build_parser
    args = build_parser("test").parse_args(
        ["1", "1", "--model", "laguna", "--model_config", CONFIG_FILE])
    assert args.model == "laguna"
    with pytest.raises(ValueError, match="--model_config"):
        get_model("laguna")
    assert get_model("laguna", published()).tokens == (12544, 8192)


def test_the_cli_trains_the_model(tmp_path):
    """``multigpu.py --model laguna --model_config FILE --synthetic``: the
    parity entry point trains the tiny preset end to end and saves."""
    cfg = tmp_path / "laguna_tiny.json"
    cfg.write_text(json.dumps(tiny(seq_len=64)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "multigpu.py", "1", "1", "--model", "laguna",
         "--model_config", str(cfg), "--synthetic", "--synthetic_size", "8",
         "--batch_size", "2", "--bf16", "--lr", "0.05",
         "--snapshot_path", str(tmp_path / "ck.pt")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert (tmp_path / "ck.pt").exists()


# -- (f) the count, the operations, the file ---------------------------------------

def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(tree))


def test_parameter_count_at_the_published_widths():
    """691,623,936 by ISSUE 39's count, term by term, from
    ``jax.eval_shape`` of the initialiser at the published widths."""
    config = published()
    params, state = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    layers = params["layers"]
    attn = ("q", "k", "v", "o", "head_gate")
    full, window = 29_458_432, 37_879_808
    assert _count({k: layers["layer_00"][k] for k in attn}) == full
    assert _count({k: layers["layer_04"][k] for k in attn}) == full
    assert _count({k: layers["layer_02"][k] for k in attn}) == window
    assert _count(layers["layer_00"]["head_gate"]) == 2048 * 48
    assert _count(layers["layer_01"]["head_gate"]) == 2048 * 64
    assert _count(layers["layer_00"]) == full + 50_331_648 + 4096
    experts = 524_288 + 3_145_728 + 100_663_296
    for name in ("layer_01", "layer_02", "layer_03"):
        assert _count(layers[name]) == window + experts + 4096
    assert _count(layers["layer_04"]) == full + experts + 4096
    assert _count((params["embed"], params["head"])) == 51_380_224
    assert _count(params) == config["parameters"] == 691_623_936
    assert state["layer_04"]["e_bias"].shape == (256,)
    assert state["layer_04"]["assignments"].shape == (32,)


def test_parameter_count_of_the_uncut_model():
    """40 layers, 256 experts held, the whole vocabulary: the card's
    33.4B."""
    pub = published()
    config = dict(pub, **pub["published"], experts_held=[0, 256],
                  vocab_held=[0, 100352])
    params, _ = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    assert round(_count(params) / 1e9, 1) == 33.4


@pytest.mark.parametrize("part,share", [
    ("gated_proj", 43.0), ("full_core", 25.1), ("swa_core", 6.1),
    ("dense", 12.6), ("head", 6.4), ("routed", 3.1), ("shared", 3.1),
    ("router", 0.5)])
def test_flops_shares_of_a_sequence(part, share):
    dm = ref.layer_shapes(published())
    macs = flops_laguna.macs_per_token(dm, 8192)
    assert 100.0 * macs[part] / sum(macs.values()) == pytest.approx(
        share, abs=0.06)


def test_flops_against_hand_counts():
    dm = ref.layer_shapes(published())
    m = flops_laguna.macs_per_token(dm, 8192)
    assert m["gated_proj"] == 2 * 29_458_432 + 3 * 37_879_808
    assert m["full_core"] == 2 * 48 * 256 * 4096
    # The mean over 8,192 queries of min(i + 1, 512) keys.
    keys = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    assert flops_laguna.window_keys(8192, 512) == pytest.approx(keys)
    assert flops_laguna.window_keys(100, 512) == pytest.approx(50.5)
    assert m["swa_core"] == pytest.approx(3 * 64 * 256 * keys)
    assert m["routed"] == 4 * 3 * 2048 * 512 and m["shared"] == m["routed"]
    assert 2 * sum(m.values()) == pytest.approx(801.77e6, rel=1e-5)
    assert 2 * flops_laguna.train_flops_per_sequence(dm, 8192) \
        == pytest.approx(39.41e12, rel=1e-3)
    assert flops_laguna.core_train_flops(dm, 8192, 2, "full_core") \
        == pytest.approx(3 * 2 * m["full_core"] * 8192 * 2)


PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 10,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "num_attention_heads_per_layer": ([48] + [64] * 3) * 10,
}


def test_config_file_holds_the_published_widths():
    """Every key of the source's ``config.json`` stands in the file
    unchanged but those it lists as ``reduced``, whose published values
    stand beside them; the benchmark's entries agree."""
    config = published()
    reduced = {"num_hidden_layers": 5, "num_experts": 32,
               "vocab_size": 12544,
               "layer_types": PUBLISHED["layer_types"][:5],
               "mlp_layer_types": PUBLISHED["mlp_layer_types"][:5],
               "num_attention_heads_per_layer": [48, 64, 64, 64, 48]}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config["published"][key] == value
    assert config["router_experts"] == 256
    assert config["experts_held"] == [0, 32]
    assert config["vocab_held"] == [0, 12544] and 12544 * 8 == 100352
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = {c["name"]: c for c in spec["configs"]}["laguna_xs2_ep8"]
    assert entry["file"] == "benchmark/configs/laguna_xs2_ep8.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(reduced)
    cell = {w["name"]: w for w in spec["workloads"]}[
        "laguna_xs2_train_8k_1chip"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_xs2_ep8", "tok_stream_s8192_b2", 1)
    listed = {m["name"] for m in spec["per_layer"]
              if m.get("workloads") == ["laguna_xs2_train_8k_1chip"]}
    assert listed == {"swa_core_roofline_pct", "full_core_roofline_pct",
                      "gated_proj_device_pct"}
    assert set(config["scopes"]) == {
        "gated_proj", "full_core", "swa_core", "dense_mlp", "moe_route",
        "moe_experts", "moe_shared", "lm_head", "update"}


def test_the_step_program_names_every_scope():
    """Each scope the configuration lists shows in the lowered step."""
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import init_train_state, make_train_step
    config = tiny()
    model = get_model("laguna", config)
    step = make_train_step(model, SGDConfig(), lambda s: 0.1, make_mesh(1),
                           compute_dtype=jnp.bfloat16)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    b = {"image": jax.ShapeDtypeStruct((2, 64), jnp.int32),
         "label": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    text = step.lower(state, b, jax.random.key(0)).as_text(debug_info=True)
    for scope in config["scopes"]:
        assert re.search(rf"[/(]{scope}[/)]", text), scope


# -- (g) the benchmark's cell, rehearsed -------------------------------------------

def test_the_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "laguna_xs2_train_8k_1chip", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    detail = json.loads([ln for ln in proc.stderr.splitlines()
                         if ln.startswith("benchmark-detail: ")][-1]
                        .split(": ", 1)[1])
    assert all(detail["checks"].values()), (detail["checks"],
                                            detail["reference_check"])
    assert set(detail["checks"]) >= {"first_step_matches_reference",
                                     "none_dropped", "loss_fell"}
    assert set(detail["reference_check"]["errors"]) == set(ref.TOLERANCE)
    assert set(detail["routing"]) == {"layer_01", "layer_02", "layer_03",
                                      "layer_04"}


@pytest.mark.parametrize("name", ["swa_core_roofline_pct",
                                  "full_core_roofline_pct",
                                  "gated_proj_device_pct"])
def test_the_readers_read_the_scopes_and_nothing_without_them(name):
    """Each new reader: the scope's share of its roofline or of busy time
    from a trace's scope seconds, and None where the program named no
    scope (the parent's program, a trace without scopes)."""
    import importlib
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    dm = ref.layer_shapes(published())
    scope = name.rsplit("_", 2)[0] if "roofline" in name else "gated_proj"
    ctx = {"layers": dm, "seq_len": 8192, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12},
           "trace": {"samples": 16, "busy_s": 10.0,
                     "scope_s": {scope: 2.0, "-": 1.0}}}
    got = reader.read(ctx)
    if "roofline" in name:
        ops = flops_laguna.core_train_flops(dm, 8192, 16, scope)
        assert got == pytest.approx(100.0 * ops / 197e12 / 2.0)
        assert 0 < got < 100
    else:
        assert got == pytest.approx(20.0)
    assert reader.read(dict(ctx, trace={"samples": 16, "busy_s": 10.0,
                                        "scope_s": {"-": 3.0}})) is None
    assert reader.read(dict(ctx, trace=None)) is None
