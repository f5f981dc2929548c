"""``glm4_moe_lite`` (models/glm4_moe_lite.py) against its plain reference
(benchmark/reference/glm4_moe_lite.py), at a small size on the CPU: both
prediction depths' logits, the weighted loss and every gradient leaf; the
shares of experts and of the vocabulary add up to the uncut model; the
rotary embedding sees ``i - j`` alone and every head the one rotary key;
the prediction module's shift; planted faults; the loss core's two
depths through the step builder and the Trainer; the operation count, the
configuration file and the rehearsed benchmark cell.  Also: the other two
token models' step programs do not move with the expert layer's lift."""
import functools
import hashlib
import json
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

from benchmark import flops_glm4_moe_lite as flops_glm  # noqa: E402
from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import get_model  # noqa: E402
from ddp_tpu.models import glm4_moe_lite as sysm  # noqa: E402
from ddp_tpu.models import moe  # noqa: E402
from ddp_tpu.ops import attention, seq  # noqa: E402
from ddp_tpu.ops.losses import (IGNORE, LM_LOSS, DepthLogits,  # noqa: E402
                                depth_cross_entropy, shift_labels)

CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "glm47_flash_ep8.json")
T = 96


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny(**over):
    """The configuration file's own tiny preset (16 experts routed, 4
    held, 5 layers and the module) with ``over`` on top."""
    pub = published()
    return overlay(pub, {**{k: v for k, v in pub["tiny"].items()
                            if not k.startswith("_")}, **over})


def seeded(config, seed=0, scale=4.0):
    """Weights from the program's initialiser, the matrices scaled up so
    that every part moves the result, the norms' weights moved off 1."""
    params, state = sysm.build(config)[0](jax.random.key(seed))
    key = jax.random.key(seed + 99)

    def move(path, p):
        name = path[-1].key
        if p.ndim == 1:
            k = jax.random.fold_in(key, sum(map(ord, jax.tree_util.keystr(
                path))))
            return p + 0.1 * jax.random.normal(k, p.shape)
        return p if name == "embed" else p * scale

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
    """The loss as the step's core forms it on one shard, its gradient,
    both depths' logits and the new state."""
    apply = sysm.build(config)[1]

    def f(p):
        out, new = apply(p, state, ids, train=True, compute_dtype=cd)
        sums, counts = depth_cross_entropy(out, targets)
        loss = jnp.dot(jnp.asarray(out.weights), sums / counts)
        return loss, (jnp.stack([out.logits(k) for k in range(2)]), new)

    (loss, (logits, new)), grads = jax.jit(
        jax.value_and_grad(f, has_aux=True))(params)
    return loss, grads, logits, new


def reference_loss_and_grads(config, params, state, ids, targets):
    with jax.default_matmul_precision("highest"):
        loss, grads, logits0 = ref.loss_and_grads(
            config, params, state, np.asarray(ids), np.asarray(targets))
        logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    np.testing.assert_allclose(logits0, logits[:, 0], rtol=1e-5, atol=1e-5)
    return loss, grads, logits


def leaf_errors(grads, r_grads):
    return {jax.tree_util.keystr(k): rel(a, b) for (k, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_flatten_with_path(r_grads)[0])}


# -- (a) the system against the reference -------------------------------------------

@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
def test_matches_reference(cd):
    """Both depths' logits, the loss and EVERY gradient leaf: to rounding
    in float32; in the cell's precision (bf16 products) within what bf16
    operands cost at this size."""
    config = tiny()
    params, state = seeded(config)
    ids, targets = batch()
    loss, grads, logits, new = system_loss_and_grads(
        config, params, state, ids, targets, cd)
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    assert logits.dtype == jnp.float32 and logits.shape == (2, 2, T, 256)
    errs = leaf_errors(grads, r_grads)
    assert set(errs) == set(leaf_errors(r_grads, r_grads))
    if cd is None:
        assert abs(float(loss) - r_loss) < 2e-5
        assert rel(logits[0], r_logits[0]) < 2e-5
        assert rel(logits[1], r_logits[1]) < 2e-5
        assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
    else:
        assert abs(float(loss) - r_loss) < 5e-3
        assert rel(logits[0], r_logits[0]) < 0.03
        assert rel(logits[1], r_logits[1]) < 0.03
        assert max(errs.values()) < 0.4, max(errs, key=errs.get)
        assert sorted(errs.values())[len(errs) // 2] < 0.05
    # Every routed layer counted its assignments, and none found no room.
    for name in ("layer_01", "layer_04", "mtp"):
        assert int(new[name]["assignments"].sum()) > 0
        assert int(new[name]["dropped"]) == 0
    assert "layer_00" not in new  # the dense layer routes nothing


def test_eval_mode_gives_the_main_models_logits():
    config = tiny()
    params, state = seeded(config)
    ids, _ = batch()
    apply = sysm.build(config)[1]
    logits, new = jax.jit(functools.partial(apply, train=False))(
        params, state, ids)
    out, _ = jax.jit(functools.partial(apply, train=True))(
        params, state, ids)
    assert isinstance(out, DepthLogits) and len(out.hidden) == 2
    assert tuple(float(w) for w in out.weights) == pytest.approx((1.0, 0.3))
    assert logits.shape == (2, T, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, out.logits(0), rtol=1e-6, atol=1e-6)
    # Nothing counted outside training.
    assert int(new["layer_01"]["assignments"].sum()) == 0


def test_a_model_without_the_module_yields_plain_logits():
    config = tiny(num_nextn_predict_layers=0)
    params, state = sysm.build(config)[0](jax.random.key(0))
    assert "mtp" not in params and LM_LOSS not in state
    ids, _ = batch()
    logits, _ = sysm.build(config)[1](params, state, ids, train=True)
    assert logits.shape == (2, T, 256)
    with jax.default_matmul_precision("highest"):
        r_logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    assert r_logits.shape == (1, 2, T, 256)
    assert rel(logits, r_logits[0]) < 2e-5


@pytest.mark.parametrize("over,why", [
    (dict(experts_held=[0, 3]), "experts_held says 3"),
    (dict(experts_held=[14, 4]), "outside the router's 16"),
    (dict(partial_rotary_factor=0.5), "rotates all of qk_rope_head_dim"),
    (dict(num_nextn_predict_layers=2), "one prediction module")])
def test_a_share_that_cannot_run_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        sysm.build(tiny(**over))


def test_the_kernel_branch_lays_heads_out_as_pairs(monkeypatch):
    """Where ``kernel_applies`` says so the core goes through
    ``causal_gqa`` with every (sequence, head) a pair of its own (R = 1);
    with the XLA loop standing in for the kernel the result is the
    other branch's."""
    config = tiny(qk_nope_head_dim=24, v_head_dim=32)
    dm = sysm.dims(config)
    params, _ = seeded(config)
    p = params["layers"]["layer_00"]
    x = jax.random.normal(jax.random.key(2), (2, T, 64))
    cos, sin = sysm.rope_angles(T, dm["rope"], dm["theta"])
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    want = sysm.mla(p, x, dm, jnp.float32, cos, sin)
    seen = {}

    def stand_in(q, k, v, scale):
        seen.update(q=q.shape, k=k.shape, v=v.shape)
        return jax.lax.map(lambda a: seq.attend_head(
            *a, scale=scale, cd=jnp.float32, block=32), (q, k, v))

    monkeypatch.setattr(attention, "kernel_applies", lambda *a: True)
    monkeypatch.setattr(attention, "causal_gqa", stand_in)
    got = sysm.mla(p, x, dm, jnp.float32, cos, sin)
    assert seen == {"q": (8, 1, T, 32), "k": (8, T, 32), "v": (8, T, 32)}
    assert sysm.TRACED["core_xla"] == 1 and sysm.TRACED["core_kernel"] == 1
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_published_shape_asks_for_the_kernel(monkeypatch):
    """``kernel_applies(8192, 256, 2)`` holds by its own count on a TPU."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    dm = sysm.dims(published())
    assert dm["qk"] == dm["v"] == 256
    assert attention.kernel_applies(8192, dm["qk"], 2)
    assert attention._vmem_bytes(8192, 256, 2) < attention.VMEM_LIMIT_BYTES
    assert (40, 1, 8192, 256) in [s[0] for s in attention.SELF_CHECK_SHAPES]


# -- (b) the shares add up ------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts in eight shares of 2: the eight shares' routed results,
    plus the shared expert counted once, are the uncut reference's layer."""
    whole = tiny(n_routed_experts=16, experts_held=[0, 16])
    params, state = seeded(whole)
    p, st = params["layers"]["layer_01"], state["layer_01"]
    x = jax.random.normal(jax.random.key(3), (2, T, 64))
    with jax.default_matmul_precision("highest"):
        uncut = jax.vmap(lambda row: ref.experts(
            p, st["e_bias"], row, ref.dims(whole)))(x)
    shared = moe.shared_expert(p, x.reshape(-1, 64), jnp.float32,
                               moe.SWIGLU).reshape(x.shape)
    total = shared
    for first in range(0, 16, 2):
        share = tiny(n_routed_experts=2, experts_held=[first, 2])
        p_s = dict(p, **{n: p[n][first:first + 2]
                         for n in moe.SWIGLU.routed})
        st_s = dict(st, assignments=jnp.zeros((2,), jnp.int32))
        y, new = moe.expert_layer(p_s, st_s, x, sysm.dims(share),
                                  jnp.float32, train=True, form=moe.SWIGLU)
        total = total + (y - shared)
        assert int(new["dropped"]) == 0
    assert rel(total, uncut) < 1e-5


def test_vocabulary_slices_are_the_uncut_heads_rows():
    """Eight slices of 32 rows of embedding and head: the ids of a slice
    give, on that slice's model, the uncut model's logits in the slice's
    columns, at both depths."""
    whole = tiny()
    params, state = seeded(whole)
    apply = sysm.build(whole)[1]
    for first in range(0, 256, 32):
        ids, _ = batch(seed=first, vocab=32)
        out, _ = apply(params, state, ids + first, train=True)
        share = tiny(vocab_size=32, vocab_held=[first, first + 32])
        p_s = dict(params, embed=params["embed"][first:first + 32],
                   head=params["head"][:, first:first + 32])
        sliced, _ = sysm.build(share)[1](p_s, state, ids, train=True)
        for k in range(2):
            assert sliced.logits(k).shape == (2, T, 32)
            np.testing.assert_allclose(
                sliced.logits(k), out.logits(k)[..., first:first + 32],
                rtol=1e-5, atol=1e-5)


# -- (c) the rotary embedding -------------------------------------------------------

def _system_rotated(x, t):
    return sysm.rope(x[None], *sysm.rope_angles(t, x.shape[-1], 1e6),
                     jnp.float32)[0]


@pytest.mark.parametrize("side", ["system", "reference"])
def test_rotary_scores_depend_on_the_distance_alone(side):
    """The same query vector at position ``i`` against the same key
    vector at position ``j``: the score is a function of ``i - j``, and
    not a constant."""
    t, w = 64, 8
    q = jnp.tile(jax.random.normal(jax.random.key(0), (w,)), (t, 1))
    k = jnp.tile(jax.random.normal(jax.random.key(1), (w,)), (t, 1))
    if side == "system":
        rq, rk = _system_rotated(q, t), _system_rotated(k, t)
    else:
        rq, rk = ref.rotate(q, 1e6), ref.rotate(k, 1e6)
    scores = np.asarray(rq @ rk.T)
    for distance in (0, 1, 5, 17):
        band = np.diagonal(scores, -distance)
        np.testing.assert_allclose(band, band[0], rtol=1e-4, atol=1e-4)
    assert abs(scores[17, 0] - scores[0, 0]) > 1e-3
    np.testing.assert_allclose(rq[0], q[0] if side == "reference" else
                               jnp.concatenate([q[0, 0::2], q[0, 1::2]]),
                               rtol=1e-6)


def test_the_two_sides_rotate_alike():
    """The program lays a rotated vector out as ``[even | odd]``, the
    reference keeps the pairs interleaved: the same numbers."""
    x = jax.random.normal(jax.random.key(2), (32, 3, 8))
    a = sysm.rope(x[None], *sysm.rope_angles(32, 8, 1e6), jnp.float32)[0]
    b = ref.rotate(x, 1e6)
    np.testing.assert_allclose(
        a, jnp.concatenate([b[..., 0::2], b[..., 1::2]], axis=-1),
        rtol=1e-5, atol=1e-6)


def test_every_head_sees_the_one_rotary_key():
    config = tiny()
    dm = sysm.dims(config)
    params, _ = seeded(config)
    p = params["layers"]["layer_00"]
    x = jax.random.normal(jax.random.key(2), (2, T, 64))
    cos, sin = sysm.rope_angles(T, dm["rope"], dm["theta"])
    q, k, v = sysm.mla_heads(p, x, dm, jnp.float32, cos, sin)
    assert q.shape == k.shape == (2, 4, T, 32) and v.shape == (2, 4, T, 32)
    k_pe = k[..., dm["nope"]:]
    for head in range(1, dm["heads"]):
        np.testing.assert_array_equal(k_pe[:, head], k_pe[:, 0])
    want = sysm.rope((x @ p["kv_a"])[..., dm["kv_rank"]:], cos, sin,
                     jnp.float32)
    np.testing.assert_allclose(k_pe[:, 0], want, rtol=1e-6, atol=1e-6)
    # The parts without position differ a head.
    assert rel(k[:, 1, :, :dm["nope"]], k[:, 0, :, :dm["nope"]]) > 0.1


# -- (d) the prediction module's shift --------------------------------------------

def test_depth_one_reads_the_next_token_and_is_scored_on_the_one_after():
    """Change token ``j`` alone: depth 0 moves from position ``j`` on,
    depth 1 from ``j - 1`` on (it reads ``Emb(t_j)`` there).  Its labels
    are the targets moved one earlier, the last two positions ignored."""
    config = tiny()
    params, state = seeded(config)
    apply = jax.jit(functools.partial(sysm.build(config)[1], train=True))
    ids, targets = batch()
    j = 40
    moved = ids.at[:, j].set((ids[:, j] + 1) % 256)
    a, _ = apply(params, state, ids)
    b, _ = apply(params, state, moved)
    d0 = np.abs(np.asarray(a.hidden[0] - b.hidden[0])).max(axis=(0, 2))
    d1 = np.abs(np.asarray(a.hidden[1] - b.hidden[1])).max(axis=(0, 2))
    assert (d0[:j] == 0).all() and d0[j] > 0
    assert (d1[:j - 1] == 0).all() and d1[j - 1] > 0
    shifted = shift_labels(targets, 1)
    np.testing.assert_array_equal(shifted[:, :-2], ids[:, 2:])
    assert (np.asarray(shifted[:, -2:]) == IGNORE).all()
    sums, counts = depth_cross_entropy(a, targets)
    assert counts.tolist() == [2 * (T - 1), 2 * (T - 2)]
    logp = jax.nn.log_softmax(a.logits(1)[:, :-2])
    want = -jnp.take_along_axis(logp, ids[:, 2:, None], axis=-1).sum()
    np.testing.assert_allclose(sums[1], want, rtol=1e-5)


def test_the_last_token_moves_no_counted_loss():
    """The last position has no next token and no label: with the targets
    held, changing the last id (which the module reads at ``T - 2`` and,
    as the stand-in, at ``T - 1``) moves neither depth's loss."""
    config = tiny()
    params, state = seeded(config)
    apply = jax.jit(functools.partial(sysm.build(config)[1], train=True))
    ids, targets = batch()
    moved = ids.at[:, -1].set((ids[:, -1] + 7) % 256)
    a, _ = apply(params, state, ids)
    b, _ = apply(params, state, moved)
    assert rel(b.hidden[1][:, -2:], a.hidden[1][:, -2:]) > 1e-3
    np.testing.assert_allclose(depth_cross_entropy(b, targets)[0],
                               depth_cross_entropy(a, targets)[0],
                               rtol=1e-6)


# -- (e) planted faults ----------------------------------------------------------------

def _fault_rotary_left_off(mp, config, params, state):
    mp.setattr(sysm, "rope", lambda x, cos, sin, cd: jnp.concatenate(
        [x[..., 0::2], x[..., 1::2]], axis=-1).astype(cd))
    return config, params, state


def _fault_bias_in_weights(mp, config, params, state):
    route = moe.route_weights
    mp.setattr(moe, "route_weights", lambda s, e_bias, dm: route(
        s + e_bias, jnp.zeros_like(e_bias), dm))
    return config, params, state


def _fault_bias_not_in_choice(mp, config, params, state):
    return config, params, {
        k: dict(v, e_bias=v["e_bias"] * 0) if isinstance(v, dict) else v
        for k, v in state.items()}


def _fault_shared_dropped(mp, config, params, state):
    mp.setattr(moe, "shared_expert", lambda p, x, cd, form: jnp.zeros(
        (x.shape[0], p["shared_down"].shape[1]), cd))
    return config, params, state


def _fault_relu2_experts(mp, config, params, state):
    mp.setattr(moe, "SWIGLU", moe.ExpertForm(
        moe.SWIGLU.routed, moe.SWIGLU.shared,
        lambda mm, x, w: mm(moe.relu2(mm(x, w(0))) * mm(x, w(1)), w(2))))
    return config, params, state


def _fault_embedding_half_second(mp, config, params, state):
    m = params["mtp"]
    return config, dict(params, mtp=dict(m, eh_proj=jnp.roll(
        m["eh_proj"], m["eh_proj"].shape[0] // 2, axis=0))), state


def _fault_one_key_a_head(mp, config, params, state):
    """Each head its own rotary key: the heads' keys rolled a head."""
    heads = sysm.mla_heads

    def mla_heads(p, x, dm, cd, cos, sin):
        q, k, v = heads(p, x, dm, cd, cos, sin)
        k_pe = k[..., dm["nope"]:] * (
            1 + jnp.arange(dm["heads"])[None, :, None, None])
        return q, jnp.concatenate([k[..., :dm["nope"]], k_pe], axis=-1), v
    mp.setattr(sysm, "mla_heads", mla_heads)
    return config, params, state


FAULTS = {
    "rotary_embedding_left_off": _fault_rotary_left_off,
    "rotary_key_differs_a_head": _fault_one_key_a_head,
    "bias_put_into_the_weights": _fault_bias_in_weights,
    "bias_left_out_of_the_choice": _fault_bias_not_in_choice,
    "shared_expert_dropped": _fault_shared_dropped,
    "relu2_in_a_gated_expert": _fault_relu2_experts,
    "routed_scaling_factor_dropped":
        lambda mp, c, p, s: (dict(c, routed_scaling_factor=1.0), p, s),
    "weights_not_normalised":
        lambda mp, c, p, s: (dict(c, norm_topk_prob=False), p, s),
    "embedding_half_second_in_w_eh": _fault_embedding_half_second,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    config = tiny()
    params, state = seeded(config)
    ids, _ = batch()
    with jax.default_matmul_precision("highest"):
        r_logits, _ = jax.jit(ref.forward(config))(params, state, ids)

    def both_depths(cfg, p, st):
        out, _ = sysm.build(cfg)[1](p, st, ids, train=True)
        return jnp.stack([out.logits(0), out.logits(1)])

    assert rel(both_depths(config, params, state), r_logits) < 2e-5
    got = both_depths(*FAULTS[fault](monkeypatch, config, params, state))
    assert rel(got, r_logits) > 1e-3, rel(got, r_logits)


def test_a_dropped_module_weight_shows_in_the_loss_alone():
    """The loss is ``L_main + 0.3 L_mtp``: with the weight at 1 the
    logits stay and the loss does not."""
    config = tiny()
    params, state = seeded(config)
    ids, targets = batch()
    loss, _, logits, _ = system_loss_and_grads(config, params, state, ids,
                                               targets)
    loss1, _, logits1, _ = system_loss_and_grads(
        dict(config, mtp_loss_weight=1.0), params, state, ids, targets)
    r_loss, _, _ = reference_loss_and_grads(config, params, state, ids,
                                            targets)
    np.testing.assert_array_equal(logits, logits1)
    assert abs(float(loss) - r_loss) < 2e-5 < 0.5 < float(loss1) - r_loss


# -- (f) through the step builder and the Trainer -----------------------------------

def _step_and_state(config, mesh_size, cd=None):
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import init_train_state, make_train_step
    model = get_model("glm4_moe_lite", config)
    mesh = make_mesh(mesh_size)
    step = make_train_step(model, SGDConfig(lr=0.1, momentum=0.9,
                                            weight_decay=0.0),
                           lambda s: 0.1, mesh, compute_dtype=cd)
    return model, mesh, step, init_train_state(*seeded(config))


def test_the_loss_core_takes_both_depths_over_two_replicas():
    """The step's loss is the reference's weighted loss over the GLOBAL
    batch, each depth's own global mean rides the state, the counters
    grow by the replicas' sum, and the momentum is the reference's
    gradient."""
    from ddp_tpu.train.step import shard_batch
    config = tiny()
    _, mesh, step, state = _step_and_state(config, 2)
    params, mstate = jax.device_get((state.params, state.batch_stats))
    ids, targets = batch(b=4)
    r_loss, r_grads, _ = reference_loss_and_grads(config, params, mstate,
                                                  ids, targets)
    new, loss = step(state, shard_batch(
        {"image": np.asarray(ids), "label": np.asarray(targets)}, mesh),
        jax.random.key(0))
    assert abs(float(loss) - r_loss) < 2e-5
    by_depth = np.asarray(new.batch_stats[LM_LOSS])
    assert by_depth.shape == (2,)
    assert by_depth[0] + 0.3 * by_depth[1] == pytest.approx(float(loss),
                                                            rel=1e-6)
    assert int(new.batch_stats["mtp"]["assignments"].sum()) > 0
    total = sum(int(new.batch_stats[n]["assignments"].sum())
                for n in ("layer_01", "layer_02", "layer_03", "layer_04",
                          "mtp"))
    # 4 x T tokens, top-4 of 16 with 4 held: about a quarter of 4 a token.
    assert 0.5 * 5 * 4 * T < total < 2.0 * 5 * 4 * T
    errs = leaf_errors(new.opt_state.momentum_buf, r_grads)
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)


def _trainer(config, registry=None, **kw):
    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    model = get_model("glm4_moe_lite", config)
    params, state = model.init(jax.random.key(0))
    loader = TrainLoader(synthetic_tokens(16, 64, 256, seed=0), 2, 1,
                         augment=False, seed=0)
    sched = functools.partial(triangular_lr, base_lr=0.5, num_epochs=60,
                              steps_per_epoch=8, peak_frac=0.3)
    return Trainer(model, loader, params, state, mesh=make_mesh(1),
                   lr_schedule=sched,
                   sgd_config=SGDConfig(lr=0.5, momentum=0.9,
                                        weight_decay=0.0),
                   save_every=10**9, snapshot_path=None,
                   compute_dtype=jnp.bfloat16, registry=registry, **kw)


def test_three_epochs_through_the_trainer(tmp_path, capsys):
    """The Trainer's stream path: losses fall, the routing counters and
    both depths' losses reach the host where losses are flushed, and
    ``python -m ddp_tpu.obs --prom`` prints them."""
    from ddp_tpu.obs.__main__ import main
    from ddp_tpu.obs.registry import MetricsRegistry
    registry = MetricsRegistry()
    trainer = _trainer(tiny(seq_len=64), registry=registry)
    trainer.train(3)
    losses = np.asarray(trainer.loss_history)
    assert losses.shape == (24,) and np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean()
    assert set(trainer.routing.totals) == {"layer_01", "layer_02",
                                           "layer_03", "layer_04", "mtp"}
    assert all(v["dropped"] == 0 for v in trainer.routing.totals.values())
    assert len(trainer.lm_loss) == 2
    assert trainer.lm_loss[0] + 0.3 * trainer.lm_loss[1] == pytest.approx(
        losses[-1], rel=1e-5)
    prom = tmp_path / "run.prom"
    prom.write_text(registry.exposition())
    assert main(["--prom", str(prom)]) == 0
    out = capsys.readouterr().out
    assert "ddp_lm_loss depth 0:" in out and "ddp_lm_loss depth 1:" in out
    assert "mtp" in out and "layer_04" in out


def test_a_model_with_one_depth_exports_no_depth_losses():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b_ep16.json")) as f:
        pub = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                           "train_lm.json")) as f:
        config = overlay(pub, json.load(f)["config"])
    _, state = get_model("nemotron_h", config).init(jax.random.key(0))
    assert LM_LOSS not in state


def test_cli_lists_the_model_and_the_registry_builds_it():
    from ddp_tpu.cli import build_parser
    args = build_parser("test").parse_args(
        ["1", "1", "--model", "glm4_moe_lite", "--model_config",
         CONFIG_FILE])
    assert args.model == "glm4_moe_lite"
    with pytest.raises(ValueError, match="--model_config"):
        get_model("glm4_moe_lite")
    model = get_model("glm4_moe_lite", published())
    assert model.tokens == (19360, 8192)


# -- (g) the count, the operations, the file ---------------------------------------

def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(tree))


def test_parameter_count_at_the_published_widths():
    """706,518,528 by ISSUE 35's equations, term by term, from
    ``jax.eval_shape`` of the initialiser at the published widths."""
    config = published()
    params, state = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    mla = 21_759_232
    layers = params["layers"]
    assert _count({k: v for k, v in layers["layer_00"].items()
                   if k not in ("gate", "up", "down", "norm1", "norm2")}) \
        == mla
    assert _count(layers["layer_00"]) == 84_677_888
    for name in ("layer_01", "layer_02", "layer_03", "layer_04"):
        assert _count(layers[name]) == 106_829_056
    assert _count({k: layers["layer_01"][k]
                   for k in ("gate", "up", "down")}) == 8 * 9_437_184
    assert _count(layers["layer_01"]["router"]) == 131_072
    assert _count(params["mtp"]) == 115_223_808
    assert _count((params["embed"], params["head"])) == 79_298_560
    assert _count(params) == config["parameters"] == 706_518_528
    # State: a 64-wide bias and 8 counters a routed block, two losses.
    assert state["mtp"]["e_bias"].shape == (64,)
    assert state["layer_04"]["assignments"].shape == (8,)
    assert state[LM_LOSS].shape == (2,)


def test_parameter_count_of_the_uncut_model():
    """47 layers, 64 experts held, the whole vocabulary: 29.94 B and the
    module's 0.64 B more (the card says 30B-A3B)."""
    pub = published()
    config = dict(pub, **pub["published"], router_experts=64,
                  experts_held=[0, 64], vocab_held=[0, 154880])
    params, _ = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    module = _count(params["mtp"])
    assert round(module / 1e9, 2) == 0.64
    assert round((_count(params) - module) / 1e9, 2) == 29.94


@pytest.mark.parametrize("part,share", [
    ("mla_core", 41.6), ("mla_proj", 21.6), ("head", 13.1),
    ("experts", 11.8), ("dense", 10.4), ("w_eh", 1.4)])
def test_flops_shares_of_a_sequence(part, share):
    dm = ref.layer_shapes(published())
    macs = flops_glm.macs_per_token(dm, 8192)
    assert 100.0 * macs[part] / sum(macs.values()) == pytest.approx(
        share, abs=0.06)


def test_flops_against_hand_counts():
    dm = ref.layer_shapes(published())
    m = flops_glm.block_macs_per_token(dm, 8192)
    assert m["mla_proj"] == 21_759_232 - 768 - 512       # less the norms
    assert m["mla_core"] == 20 * 512 * 4096
    assert m["dense"] == 62_914_560
    assert m["routed"] == 0.5 * 9_437_184 and m["shared"] == 9_437_184
    assert flops_glm.train_flops_per_sequence(dm, 8192) == pytest.approx(
        29.70e12, rel=1e-3)
    # 1,024 rows an expert a step at uniform routing, 8 experts, 5 blocks.
    assert flops_glm.expert_train_flops(dm, 5 * 8 * 1024) == pytest.approx(
        3 * 2 * 9_437_184 * 40_960)
    assert flops_glm.mla_core_train_flops(dm, 8192, 2) == pytest.approx(
        3 * 2 * 6 * 20 * 512 * 4096 * 8192 * 2)


PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_config_file_holds_the_published_widths():
    """Every key of the source's ``config.json`` stands in the file
    unchanged but the three it lists as ``reduced``, whose published
    values stand beside them; the benchmark's entry agrees."""
    config = published()
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 8,
               "vocab_size": 19360}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config["published"][key] == value
    assert config["router_experts"] == 64
    assert config["experts_held"] == [0, 8]
    assert config["vocab_held"] == [0, 19360] and 19360 * 8 == 154880
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = {c["name"]: c for c in spec["configs"]}["glm47_flash_ep8"]
    assert entry["file"] == "benchmark/configs/glm47_flash_ep8.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(reduced)
    cell = {w["name"]: w for w in spec["workloads"]}[
        "glm47_flash_train_8k_1chip"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47_flash_ep8", "tok_stream_s8192_b2", 1)
    listed = {m["name"] for m in spec["per_layer"]
              if m.get("workloads") == ["glm47_flash_train_8k_1chip"]}
    assert listed == {"mla_core_roofline_pct", "mla_proj_device_pct",
                      "moe_gated_experts_roofline_pct", "mtp_device_pct"}
    assert set(config["scopes"]) == {
        "mla_proj", "mla_core", "dense_mlp", "moe_route", "moe_experts",
        "moe_shared", "mtp", "lm_head", "update"}


def test_the_step_program_names_every_scope():
    """Each scope the configuration lists shows in the lowered step."""
    config = tiny()
    _, mesh, step, state = _step_and_state(config, 1, jnp.bfloat16)
    b = {"image": jax.ShapeDtypeStruct((2, 64), jnp.int32),
         "label": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    text = step.lower(jax.eval_shape(lambda: state), b,
                      jax.random.key(0)).as_text(debug_info=True)
    for scope in config["scopes"]:
        assert re.search(rf"[/(]{scope}[/)]", text), scope


# -- (h) the benchmark's cell, rehearsed -------------------------------------------

def test_the_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "glm47_flash_train_8k_1chip", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is False and result["failed"] == 0
    assert {"first_step_s", "compiles_in_window", "epoch_setup_ms",
            "epoch_close_ms", "host_untraced_pct"} <= set(result["metrics"])
    detail = json.loads([ln for ln in proc.stderr.splitlines()
                         if ln.startswith("benchmark-detail: ")][-1]
                        .split(": ", 1)[1])
    assert all(detail["checks"].values()), (detail["checks"],
                                            detail["reference_check"])
    assert set(detail["checks"]) >= {"first_step_matches_reference",
                                     "none_dropped", "loss_fell"}
    assert set(detail["reference_check"]["errors"]) == set(ref.TOLERANCE)
    assert set(detail["routing"]) == {"layer_01", "layer_02", "layer_03",
                                      "layer_04", "mtp"}


def test_the_parent_fails_the_new_cell_at_once():
    """A tree without the model fails before the device is touched: the
    runner imports the configuration's model module first."""
    with open(os.path.join(ROOT, "benchmark", "runners",
                           "train_tok.py")) as f:
        text = f.read()
    first = text.index('importlib.import_module("ddp_tpu.models."')
    assert first < text.index("import jax\n")


# -- the other token models' programs do not move -------------------------------

# sha256 of the lowered step text (locations stripped) at the parent of
# the PR that lifted the expert layer into models/moe.py (c2e02eb), at the
# tiny presets on a 2-device mesh: a refactor of the shared layer, of the
# loss core or of ops/seq.py that changes either program shows here.
# ``nemotron_h``'s two are PR 38's program, which changed by design (the
# experts' products became a loop over the live row tiles with a backward
# rule of its own, and the layer's state two counters): re-pinned there;
# ``sambay``'s, which runs no expert layer, are still the parent's.
PARENTS_STEP = {
    ("nemotron_h", "f32"):
        "b5d4725bb693fdf8e51a160aa77a3cb58088578e27ed9a858a0fd18e8ed06fdb",
    ("nemotron_h", "bf16"):
        "485a423c33937d729b82d2b8cdc3ffb84347bcb7ce8381f06907511a7cbf81ee",
    ("sambay", "f32"):
        "893a90a8584e719f21a44c58861f0d26e9a74e3d12663fad7b77e3eee4507d33",
    ("sambay", "bf16"):
        "bd7f464ad2b855dc535ef6103d817bcfef32b9c3f7039a31906fde0d78082788",
}
_PRESETS = {"nemotron_h": ("nemotron3_nano_30b_a3b_ep16", "train_lm"),
            "sambay": ("phi4_mini_flash_stage14_19", "train_seq")}


@pytest.mark.parametrize("cd", ["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(_PRESETS))
def test_token_step_program_is_the_parents(name, cd):
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import init_train_state, make_train_step
    config_file, preset = _PRESETS[name]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_file + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                           preset + ".json")) as f:
        config = overlay(config, json.load(f)["config"])
    model = get_model(name, config)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    sched = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                              steps_per_epoch=8, peak_frac=0.3)
    step = make_train_step(
        model, SGDConfig(), sched, make_mesh(2),
        compute_dtype={"f32": None, "bf16": jnp.bfloat16}[cd])
    b = {"image": jax.ShapeDtypeStruct((4, 256), jnp.int32),
         "label": jax.ShapeDtypeStruct((4, 256), jnp.int32)}
    text = re.sub(r"loc\(.*?\)", "",
                  step.lower(state, b, jax.random.key(0)).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEP[
        (name, cd)]


def test_one_copy_of_the_routing_in_the_program():
    """``route_weights`` and ``row_plan`` are defined once under
    ``ddp_tpu/`` (the reference's own routing aside)."""
    found = []
    for base, _dirs, files in os.walk(os.path.join(ROOT, "ddp_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    text = f.read()
                found += [(name, fn) for fn in ("route_weights", "row_plan")
                          if re.search(rf"^def {fn}\(", text, re.M)]
    assert sorted(found) == [("moe.py", "route_weights"),
                             ("moe.py", "row_plan")]
