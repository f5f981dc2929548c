"""``sambay`` (models/sambay.py) against its plain reference
(benchmark/reference/sambay.py), at a small size on the CPU: the
six-layer pipeline stage and a 16-layer model, every gradient leaf; the
selective scan in chunks; the window's edge; differential attention at
``lam = 0``; the hand-over's gradients; the vocabulary slice; planted
faults; the Trainer's path; the parameter counts; the operation count;
the configuration file; the rehearsed benchmark cell.  Also: what the two
token models share lowers to the parent's text, the attention cores take
the kernel pair at the cell's shape and the parent's loop at the tiny
preset's, and the classifiers' processes never import any of it."""
import copy
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_sambay  # noqa: E402
from benchmark.reference import sambay as ref  # noqa: E402
from ddp_tpu.models import get_model  # noqa: E402
from ddp_tpu.models import nemotron_h, sambay as sysm  # noqa: E402
from ddp_tpu.ops import attention, selscan, seq  # noqa: E402

CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash_stage14_19.json")
# Hidden 64: a Mamba-1 mixer of 128 channels, state 16, dt_rank 4; 8 query
# heads of 8 (4 pairs) over 4 key-value heads (2 pairs); a window of 8.
TINY = dict(hidden_size=64, layer_norm_eps=1e-5, intermediate_size=96,
            num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
            vocab_size=256)
T = 80  # not a multiple of the scan's chunk or of the window


def stage(**over):
    """Layers 4-9 of 12: one of every kind, as the cell's 14-19 of 32."""
    return {**TINY, "num_hidden_layers": 6, "layers_held": [4, 10],
            "published": {"num_hidden_layers": 12}, **over}


def whole(depth=16, **over):
    return dict(TINY, num_hidden_layers=depth, **over)


def seeded(config, seed=0, scale=4.0):
    """Weights from the program's initialiser, the projections scaled up
    so that every mixer moves the result (std 0.02 at width 64 leaves the
    residual stream almost untouched), and every bias and norm weight
    moved off its trivial value."""
    params, state = sysm.build(config)[0](jax.random.key(seed))
    key = jax.random.key(seed + 99)

    def move(path, p):
        name = path[-1].key
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if name.endswith("_b") and name != "conv_b":
            return p + 0.1 * jax.random.normal(k, p.shape)
        if name in ("ln1_w", "ln2_w", "sub_norm", "norm_f_w", "D"):
            return p + 0.1 * jax.random.normal(k, p.shape)
        if p.ndim >= 2 and name not in ("A_log", "conv_w", "embed",
                                        "dt_proj"):
            return p * scale
        return p

    return jax.tree_util.tree_map_with_path(move, params), state


def batch(seed=1, b=2, t=T, vocab=256):
    ids = jax.random.randint(jax.random.key(seed), (b, t), 0, vocab)
    targets = jnp.concatenate(
        [ids[:, 1:], jnp.full((b, 1), ref.IGNORE, ids.dtype)], axis=1)
    return ids, targets


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def system_loss_and_grads(config, params, state, ids, targets, cd=None):
    from ddp_tpu.ops.losses import cross_entropy_sum_count
    apply = sysm.build(config)[1]

    def f(p):
        logits, _ = apply(p, state, ids, train=True, compute_dtype=cd)
        s, n = cross_entropy_sum_count(logits, targets)
        return s / n, logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
        params)
    return loss, grads, logits


def reference_logits(config, params, state, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(ref.forward(config))(params, state, ids)[0]


# -- (a) the system against the reference ------------------------------------------

@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("config", [stage(), whole(16)],
                         ids=["stage_4_9_of_12", "whole_16"])
def test_matches_reference(config, cd, monkeypatch):
    # Several query blocks in the global layers, the last one ragged;
    # several chunks of the scan, the last one ragged.
    monkeypatch.setattr(sysm, "ATTN_QUERY_BLOCK", 32)
    monkeypatch.setattr(sysm, "SCAN_CHUNK", 32)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 48)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 24)
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    params, state = seeded(config)
    ids, targets = batch()
    loss, grads, logits = system_loss_and_grads(config, params, state, ids,
                                                targets, cd)
    kinds = [sysm.kind_of(l, sysm.dims(config)["half"])
             for l in sysm.dims(config)["layers"]]
    # Which path compiled: the tally counts every layer traced.
    assert kinds[:6] == (["mamba", "window"] * 3 if len(kinds) == 16 else
                         ["mamba", "window", "mamba", "full", "gmu", "cross"])
    attention_layers = sum(kinds.count(k) for k in ("window", "full", "cross"))
    assert sysm.TRACED == {
        **{k: kinds.count(k) for k in sysm.TRACED},
        "core_kernel": 0, "core_xla": attention_layers,
        "conv_kernel": 0, "conv_xla": kinds.count("mamba")}
    with jax.default_matmul_precision("highest"):
        r_loss, r_grads, _ = ref.loss_and_grads(
            config, params, state, np.asarray(ids), np.asarray(targets))
    r_logits = reference_logits(config, params, state, ids)
    tol = 2e-4 if cd is None else 4e-2
    assert logits.dtype == jnp.float32 and logits.shape == (2, T, 256)
    assert rel(logits, r_logits) < tol
    assert abs(float(loss) - float(r_loss)) < tol
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    r_flat = jax.tree_util.tree_leaves(r_grads)
    assert len(flat) == len(r_flat)
    whole_rel = rel(np.concatenate([np.ravel(g) for _, g in flat]),
                    np.concatenate([np.ravel(g) for g in r_flat]))
    assert whole_rel < (tol if cd is None else 8e-2), whole_rel
    for (path, g), rg in zip(flat, r_flat):
        # bf16: a layer's four 8-number lam leaves are ONE scalar's
        # gradient (the sum of a whole layer's score differences, which
        # nearly cancel) times a vector: they move together, by up to 0.7
        # at this size, and are held in float32 alone; every other leaf
        # reads under 0.05.
        name = jax.tree_util.keystr(path)
        if cd is not None and name[-6:-3] in ("'lq", "'lk"):
            continue
        assert rel(g, rg) < (tol if cd is None else 0.35), (name,
                                                            rel(g, rg))


def test_kinds_by_the_published_index():
    kinds = [sysm.kind_of(l, 16) for l in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu",
                            "cross"]
    assert kinds == [ref.kind_of(l, 16) for l in range(32)]
    assert sysm.lam0_of(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))


@pytest.mark.parametrize("over,why", [
    (dict(layers_held=[7, 10], num_hidden_layers=3), "not held here"),
    (dict(num_hidden_layers=5), "layers_held says"),
    (dict(num_attention_heads=6), "do not pair up"),
])
def test_a_share_that_cannot_run_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        sysm.build(stage(**over))


# -- (b) the selective scan in chunks -------------------------------------------------

@pytest.mark.parametrize("t", [32, 64, 50, 7, 100])
def test_chunked_scan_matches_recurrence(t):
    ch, n = 24, 16
    ks = jax.random.split(jax.random.key(t), 5)
    x = jax.random.normal(ks[0], (2, t, ch))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, ch)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (ch, n), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (2, t, n))
    c = jax.random.normal(ks[4], (2, t, n))
    y = sysm.selective_scan(x, dt, a.T, b, c, 32)
    y_ref = jnp.stack([ref.selective_scan(
        x[i], dt[i], a, b[i], c[i], jnp.zeros((ch,))) for i in range(2)])
    assert y.shape == (2, t, ch) and y.dtype == jnp.float32
    assert rel(y, y_ref) < 1e-5


# -- (c) the window's edge, and lam = 0 -----------------------------------------------

def _core_operands(t, seed=2):
    dm = sysm.dims(stage())
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, t, dm["pairs"], 2, dm["hd"]))
    k = jax.random.normal(ks[1], (1, t, dm["kv_pairs"], 2, dm["hd"]))
    v = jax.random.normal(ks[2], (1, t, dm["kv_pairs"], 2 * dm["hd"]))
    params, _ = seeded(stage())
    return dm, params["layers"]["layer_05"], q, k, v


@pytest.mark.parametrize("side", ["system", "reference"])
@pytest.mark.parametrize("i", [8, 15, 16, 39])
def test_window_sees_key_i_minus_7_and_not_i_minus_8(i, side):
    """Window 8, the query counted: moving the value at key ``i - 7``
    moves query ``i``'s result, moving the one at ``i - 8`` does not (the
    queries sit before, on and after a query block's edge)."""
    dm, p, q, k, v = _core_operands(40)

    def core(v):
        if side == "system":
            return sysm.diff_core(p, q, k, v, 5, dm, jnp.float32,
                                  dm["window"])[0]
        return ref.diff_core(p, q[0], k[0], v[0], 5, ref.dims(stage()),
                             window=dm["window"])

    base = core(v)
    inside = core(v.at[0, i - 7].add(1.0))
    outside = core(v.at[0, i - 8].add(1.0))
    assert rel(inside[i], base[i]) > 1e-3
    np.testing.assert_array_equal(np.asarray(outside[i]),
                                  np.asarray(base[i]))
    # ... and no query sees a key past itself.
    ahead = core(v.at[0, i + 1:].add(1.0))
    np.testing.assert_array_equal(np.asarray(ahead[:i + 1]),
                                  np.asarray(base[:i + 1]))


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
def test_lam_zero_with_unit_norm_weight_is_plain_softmax_attention(window):
    dm, p, q, k, v = _core_operands(40)
    l = 7
    lam0 = sysm.lam0_of(l)
    # exp(0) - exp(lq2 . lk2) + lam0 = 0
    hd = dm["hd"]
    p = dict(p, lq1=jnp.zeros((hd,)), lk1=jnp.zeros((hd,)),
             lq2=jnp.full((hd,), math.log1p(lam0) / hd),
             lk2=jnp.ones((hd,)), sub_norm=jnp.ones((2 * hd,)))
    assert abs(float(sysm.lam_of(p, l))) < 1e-6
    out = sysm.diff_core(p, q, k, v, l, dm, jnp.float32, window)[0]
    rep = dm["pairs"] // dm["kv_pairs"]
    qi, si = np.arange(40)[:, None], np.arange(40)[None, :]
    seen = (si <= qi) & ((si > qi - window) if window else True)
    for pair in range(dm["pairs"]):
        scores = np.asarray(q[0, :, pair, 0] @ k[0, :, pair // rep, 0].T,
                            np.float64) / math.sqrt(hd)
        probs = np.where(seen, np.exp(scores), 0.0)
        o = (probs / probs.sum(-1, keepdims=True)) @ np.asarray(
            v[0, :, pair // rep], np.float64)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + dm["eps"])
        assert rel(out[:, pair * 2 * hd:(pair + 1) * 2 * hd],
                   o * (1.0 - lam0)) < 1e-5


# -- (d) what the cross-decoder hands back ----------------------------------------------

def test_handed_over_leaves_carry_the_cross_decoders_share():
    """Layer ``half``'s scan leaves and layer ``half + 1``'s key and value
    columns take gradient from the gated memory unit and the cross
    attention that read them: silencing those two changes them, and the
    silenced model still agrees with the reference leaf for leaf."""
    config = stage()
    dm = sysm.dims(config)
    params, state = seeded(config)
    ids, targets = batch()
    _, grads, _ = system_loss_and_grads(config, params, state, ids, targets)
    silent = copy.deepcopy(params)
    silent["layers"]["layer_08"]["gmu_out"] *= 0    # the GMU adds nothing
    silent["layers"]["layer_09"]["o"] *= 0          # nor the cross layer
    _, s_grads, _ = system_loss_and_grads(config, silent, state, ids,
                                          targets)
    nq = dm["pairs"] * 2 * dm["hd"]
    g17, s17 = (g["layers"]["layer_07"]["qkv"] for g in (grads, s_grads))
    g16, s16 = (g["layers"]["layer_06"] for g in (grads, s_grads))
    assert rel(g17[:, nq:], s17[:, nq:]) > 0.05       # keys and values
    for leaf in ("A_log", "dt_bias", "D", "x_proj"):  # the scan's own
        assert rel(g16[leaf], s16[leaf]) > 0.05, leaf
    # With the cross-decoder silent the hand-over carries nothing: the
    # reference agrees leaf for leaf on that model too.
    with jax.default_matmul_precision("highest"):
        _, r_grads, _ = ref.loss_and_grads(config, silent, state,
                                           np.asarray(ids),
                                           np.asarray(targets))
    assert rel(s17, r_grads["layers"]["layer_07"]["qkv"]) < 2e-4
    assert rel(s16["A_log"], r_grads["layers"]["layer_06"]["A_log"]) < 2e-4


def test_sliced_vocabulary_gives_the_whole_models_columns():
    config = stage()
    params, state = seeded(config)
    ids, _ = batch(vocab=128)
    logits, _ = sysm.build(config)[1](params, state, ids)
    share = stage(vocab_size=128, vocab_held=[0, 128])
    sliced, _ = sysm.build(share)[1](
        dict(params, embed=params["embed"][:128]), state, ids)
    assert sliced.shape == (2, T, 128)
    np.testing.assert_allclose(sliced, logits[..., :128], rtol=1e-5,
                               atol=1e-6)


# -- (e) planted faults ---------------------------------------------------------------------

def _fault_window_off_by_one(mp, config, params):
    return dict(config, sliding_window=config["sliding_window"] + 1), params


def _fault_lam0_by_the_local_index(mp, config, params):
    held = config["layers_held"][0]
    mp.setattr(sysm, "lam0_of", lambda l: _LAM0(l - held))
    return config, params


def _fault_gated_scan_handed_over(mp, config, params):
    def mixer(p, u, dm, cd):
        out, y = _MAMBA(p, u, dm, cd)
        z = jnp.split(sysm.linear(u, p["in_proj"]), 2, axis=-1)[1]
        return out, y * jax.nn.silu(z)
    mp.setattr(sysm, "mamba_mixer", mixer)
    return config, params


def _fault_d_skipped(mp, config, params):
    out = copy.deepcopy(params)
    out["layers"]["layer_06"]["D"] *= 0
    return config, out


def _fault_norm_before_the_difference(mp, config, params):
    def block(q, k, v, lam, sub_norm, *, start, lo, window, scale, gain,
              eps, cd):
        def one(j):
            a = seq.block_probs(q[j], k[j], start=start, scale=scale,
                                lo=lo, window=window)
            o = lax.dot_general(a, v, (((0,), (0,)), ((), ())))
            return o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = (one(0) - lam * one(1)) * sub_norm * gain
        return o.reshape(q.shape[1], q.shape[2], v.shape[-1])
    mp.setattr(sysm, "_diff_block", block)
    return config, params


def _fault_cross_pairs_shifted(mp, config, params):
    def cross(p, u, k, v, l, dm, cd):
        return _CROSS(p, u, jnp.roll(k, 1, axis=2), v, l, dm, cd)
    mp.setattr(sysm, "cross_attention", cross)
    return config, params


_LAM0, _MAMBA, _CROSS = (sysm.lam0_of, sysm.mamba_mixer,
                         sysm.cross_attention)
FAULTS = {
    "window_off_by_one": _fault_window_off_by_one,
    "lam0_by_the_local_index": _fault_lam0_by_the_local_index,
    "gated_scan_handed_over": _fault_gated_scan_handed_over,
    "D_skipped": _fault_d_skipped,
    "norm_before_the_difference": _fault_norm_before_the_difference,
    "cross_pairs_shifted": _fault_cross_pairs_shifted,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    config = stage()
    params, state = seeded(config)
    ids, _ = batch()
    r_logits = reference_logits(config, params, state, ids)
    # In float32 the sound system is within rounding of the reference ...
    logits, _ = jax.jit(sysm.build(config)[1])(params, state, ids)
    assert rel(logits, r_logits) < 2e-5
    # ... and the faulty one is not.
    f_config, f_params = FAULTS[fault](monkeypatch, config, params)
    logits, _ = jax.jit(sysm.build(f_config)[1])(f_params, state, ids)
    assert rel(logits, r_logits) > 2e-5, rel(logits, r_logits)


# -- (f) through the Trainer ---------------------------------------------------------------------

def test_three_epochs_through_the_trainer(monkeypatch):
    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.obs.tracer import SpanTracer
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    config = stage(seq_len=64)
    model = get_model("sambay", config)
    assert model.tokens == (256, 64)
    params, state = model.init(jax.random.key(0))
    assert state == {}
    tracer = SpanTracer(ring=1 << 16)
    loader = TrainLoader(synthetic_tokens(16, 64, 256, seed=0), 2, 1,
                         augment=False, seed=0)
    sched = functools.partial(triangular_lr, base_lr=0.5, num_epochs=60,
                              steps_per_epoch=8, peak_frac=0.3)
    trainer = Trainer(model, loader, params, state, mesh=make_mesh(1),
                      lr_schedule=sched,
                      sgd_config=SGDConfig(lr=0.5, momentum=0.9,
                                           weight_decay=0.0),
                      save_every=10**9, snapshot_path=None,
                      compute_dtype=jnp.bfloat16, tracer=tracer)
    trainer.train(3)
    print("layers traced:", sysm.TRACED)
    assert sysm.TRACED["mamba"] == 2 * sysm.TRACED["full"] > 0
    losses = np.asarray(trainer.loss_history)
    assert losses.shape == (24,) and np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean()
    assert not trainer.routing.totals  # no router, no counter
    spans = tracer.spans_since(0.0)
    assert {"epoch_setup", "data_wait", "h2d", "dispatch", "epoch_close",
            "loss_flush"} <= {s["phase"] for s in spans}
    assert {s["n"] for s in spans if s["phase"] == "dispatch"} == {2}


def test_get_model_asks_for_a_config():
    with pytest.raises(ValueError, match="--model_config"):
        get_model("sambay")


# -- (g) the counts ---------------------------------------------------------------------------

def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def _count(config):
    shapes = jax.eval_shape(lambda: get_model("sambay", config).init(
        jax.random.key(0)))[0]
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes))


MLP, LN = 2560 * 20480 + 10240 * 2560, 4 * 2560
MAMBA = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
         + 5120 * 16 + 5120 + 5120 * 2560) + MLP + LN
SELF = (2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128) + MLP + LN
GMU = 2 * 2560 * 5120 + MLP + LN
CROSS = (2 * (2560 * 2560 + 2560) + 4 * 64 + 128) + MLP + LN


def test_parameter_count_of_the_stage_held():
    c = published()
    assert (MAMBA, SELF, GMU, CROSS) == (119_895_040, 98_322_304,
                                         104_867_840, 91_766_144)
    assert _count(c) == c["parameters"] == 697_094_272 \
        == 2 * MAMBA + 2 * SELF + GMU + CROSS + 25_008 * 2560 + 2 * 2560


def test_parameter_count_of_the_uncut_model():
    c = published()
    uncut = {k: v for k, v in c.items()
             if k not in ("layers_held", "vocab_held", "published")}
    uncut.update(num_hidden_layers=32, vocab_size=200_064)
    n = _count(uncut)
    assert n == (9 * MAMBA + 9 * SELF + 7 * GMU + 7 * CROSS
                 + 200_064 * 2560 + 2 * 2560) == 3_852_562_944
    assert round(n / 1e9, 2) == 3.85  # the card says 3.8 B


@pytest.mark.parametrize("kind,mmac", [
    ("mamba", 41.39), ("window", 22.20), ("full", 40.63), ("cross", 34.08),
    ("gmu", 26.21), ("mlp", 78.64), ("head", 64.02)])
def test_flops_sambay_against_hand_counts(kind, mmac):
    """ISSUE 33's hand counts, forward multiply-accumulates (millions) a
    token at T = 8,192."""
    macs = flops_sambay.layer_macs_per_token(ref.layer_shapes(published()),
                                             8192)
    assert round(sum(macs[kind].values()) / 1e6, 2) == mmac


def test_flops_sambay_a_step():
    dm = ref.layer_shapes(published())
    assert dm["kinds"] == ["mamba", "window", "mamba", "full", "gmu",
                           "cross"]
    macs = flops_sambay.layer_macs_per_token(dm, 8192)
    assert macs["mamba"] == {
        "in_proj": 2560 * 10240, "conv": 4 * 5120, "x_proj": 5120 * 192,
        "dt_proj": 160 * 5120, "scan": 3 * 5120 * 16,
        "out_proj": 5120 * 2560}
    # A visible key costs a token 5,120 over all pairs; the window's
    # queries see 496 keys on average, the global layers' 4,096.5.
    assert flops_sambay.visible_keys("window", 8192, 512) == pytest.approx(
        (512 * 513 / 2 + 7680 * 512) / 8192)
    assert macs["full"]["core"] == macs["cross"]["core"] == 5120 * 4096.5
    assert round(flops_sambay.forward_flops_per_token(dm, 8192) / 2e6) \
        == 742
    assert round(2 * flops_sambay.train_flops_per_sequence(dm, 8192) / 1e12,
                 1) == 72.9
    # The scan is bound by bytes: 51,264 a token a layer a pass.
    assert flops_sambay.scan_train_bytes(dm, 1) == 2 * 3 * (
        (3 * 5120 + 2 * 16) * 2 + 5120 * 4)
    assert flops_sambay.scan_train_flops(dm, 1) == 2 * 3 * 2 * 3 * 5120 * 16
    assert flops_sambay.mlp_train_flops(dm, 1) == 6 * 3 * 2 * MLP
    assert flops_sambay.attn_core_train_flops(dm, 8192, 1) == sum(
        flops_sambay.attn_core_train_flops(dm, 8192, 1, kinds=(k,))
        for k in ("window", "full", "cross"))


def test_config_file_holds_the_published_widths():
    c = published()
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in catalog.items():
        if key in c["reduced"]:
            assert c["published"][key] == value
        else:
            assert c[key] == value, key
    assert (c["num_hidden_layers"], c["layers_held"], c["vocab_size"],
            c["vocab_held"]) == (6, [14, 20], 25008, [0, 25008])
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    dm = sysm.dims(c)
    assert (dm["d_inner"], dm["n"], dm["k"], dm["dt_rank"], dm["pairs"],
            dm["kv_pairs"], dm["hd"], dm["window"]) == (
                5120, 16, 4, 160, 20, 10, 64, 512)
    assert dm == {**ref.dims(c), "init_std": 0.02, "remat": "block"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = [e for e in spec["configs"] if e["name"] == c["name"]][0]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    listed = [m["name"] for m in spec["per_layer"]
              if m.get("workloads") == ["phi4_mini_flash_train_8k_1chip"]]
    assert listed == ["sel_scan_device_pct", "sel_scan_roofline_pct",
                      "attn_window_roofline_pct", "attn_global_roofline_pct",
                      "mlp_roofline_pct", "gmu_device_pct"]
    assert set(c["scopes"]) >= {"sel_scan", "attn_window", "attn_full",
                                "attn_cross", "gmu", "mlp"}


# -- (h) the benchmark's cell, rehearsed ----------------------------------------------------

def test_the_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "phi4_mini_flash_train_8k_1chip", "--seed", "2147483999",
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
    assert all(detail["checks"].values()), detail["checks"]
    # A dense model has no router: the check is not held, not failed.
    assert "none_dropped" not in detail["checks"]
    assert detail["reference_check"]["tolerance"]["logits_rel"] == 0.04
    assert detail["flops_per_sample"] == flops_sambay.train_flops_per_sequence(
        ref.layer_shapes({**published(), **json.load(open(os.path.join(
            ROOT, "benchmark", "tests", "tiny", "train_seq.json")))["config"]}),
        256)


# -- what the two token models share is the parent's ------------------------------------

def _parents_attend(q, k, v, *, start, scale, cd):
    """``nemotron_h._attend`` as it stood before ops/seq.py (343cea7)."""
    r, bq, hd = q.shape
    scores = jnp.dot(k, q.reshape(r * bq, hd).T,
                     preferred_element_type=jnp.float32) * scale
    qi = start + (jnp.arange(r * bq) % bq)[None, :]
    si = jnp.arange(k.shape[0])[:, None]
    probs = jax.nn.softmax(jnp.where(si <= qi, scores, -jnp.inf), axis=0)
    out = lax.dot_general(probs.astype(cd), v, (((0,), (0,)), ((), ())))
    return out.reshape(r, bq, hd)


def _parents_attend_head(q, k, v, *, scale, cd):
    blk = nemotron_h.ATTN_QUERY_BLOCK
    out = [jax.checkpoint(functools.partial(
        _parents_attend, start=s, scale=scale, cd=cd))(
            q[:, s:s + blk], k[:s + blk], v[:s + blk])
        for s in range(0, q.shape[1], blk)]
    return jnp.concatenate(out, axis=1)


def _parents_conv(xbc, w, b, cd):
    k, t = w.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + t] * w[i] for i in range(k)) + b
    return jax.nn.silu(xbc).astype(cd)


def _grad_text(f, *shapes):
    grad = jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(len(shapes))))
    return re.sub(r"loc\(.*?\)", "", jax.jit(grad).lower(*shapes).as_text())


@pytest.mark.parametrize("cd", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shared_attention_loop_lowers_to_the_parents_text(cd, monkeypatch):
    monkeypatch.setattr(nemotron_h, "ATTN_QUERY_BLOCK", 64)
    shapes = (jax.ShapeDtypeStruct((4, 160, 16), cd),   # a ragged block
              jax.ShapeDtypeStruct((160, 16), cd),
              jax.ShapeDtypeStruct((160, 16), cd))
    kw = dict(scale=0.25, cd=cd)
    assert _grad_text(functools.partial(nemotron_h._attend_head, **kw),
                      *shapes) \
        == _grad_text(functools.partial(_parents_attend_head, **kw), *shapes)


@pytest.mark.parametrize("cd", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shared_conv_lowers_to_the_parents_text(cd):
    shapes = (jax.ShapeDtypeStruct((2, 50, 24), cd),
              jax.ShapeDtypeStruct((4, 24), jnp.float32),
              jax.ShapeDtypeStruct((24,), jnp.float32))
    assert _grad_text(functools.partial(seq.causal_conv_silu, cd=cd),
                      *shapes) \
        == _grad_text(functools.partial(_parents_conv, cd=cd), *shapes)


# -- who takes the attention cores -------------------------------------------------------

def _parents_diff_block(q, k, v, lam, sub_norm, *, start, lo, window, scale,
                        gain, eps, cd):
    """``_diff_block`` as it stood before the kernel (66a9e2e)."""
    _, r, bq, _ = q.shape
    a1, a2 = (seq.block_probs(q[j], k[j], start=start, scale=scale, lo=lo,
                              window=window) for j in (0, 1))
    o = lax.dot_general((a1 - lam * a2).astype(cd), v,
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * (sub_norm * gain)).astype(cd).reshape(r, bq, v.shape[-1])


def _parents_diff_core(p, q, k, v, l, dm, cd, window):
    """``diff_core`` as it stood before the kernel (66a9e2e)."""
    bsz, t, pairs, _, hd = q.shape
    kvp = k.shape[2]
    rep = pairs // kvp
    block = min(window, sysm.ATTN_QUERY_BLOCK) if window \
        else sysm.ATTN_QUERY_BLOCK
    lam = sysm.lam_of(p, l).astype(jnp.float32)

    def unit(args):
        q_u, k_u, v_u = args
        out = []
        for s in range(0, t, block):
            lo, hi = (max(0, s - window) if window else 0), s + block
            f = jax.checkpoint(functools.partial(
                _parents_diff_block, start=s, lo=lo, window=window,
                scale=1.0 / math.sqrt(hd), gain=1.0 - sysm.lam0_of(l),
                eps=dm["eps"], cd=cd))
            out.append(f(q_u[:, :, s:s + block], k_u[:, lo:hi],
                         v_u[lo:hi], lam, p["sub_norm"]))
        return jnp.concatenate(out, axis=1)

    o = lax.map(unit, (
        q.reshape(bsz, t, kvp, rep, 2, hd).transpose(0, 2, 4, 3, 1, 5)
        .reshape(bsz * kvp, 2, rep, t, hd),
        k.transpose(0, 2, 3, 1, 4).reshape(bsz * kvp, 2, t, hd),
        v.transpose(0, 2, 1, 3).reshape(bsz * kvp, t, 2 * hd)))
    return o.reshape(bsz, kvp, rep, t, 2 * hd).transpose(
        0, 3, 1, 2, 4).reshape(bsz, t, pairs * 2 * hd)


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("window", [None, 32], ids=["full", "window"])
def test_tiny_preset_cores_lower_to_the_parents_loop(window, tpu,
                                                     monkeypatch):
    """At the benchmark's tiny preset (8-wide maps beside a 16-wide value,
    256 tokens) ``kernel_applies`` says no, on a TPU backend too, and the
    core, forward and backward, lowers to the parent's text."""
    tiny = json.load(open(os.path.join(
        ROOT, "benchmark", "tests", "tiny", "train_seq.json")))["config"]
    dm = sysm.dims({**published(), **tiny})
    t, cd = int(tiny["seq_len"]), jnp.bfloat16
    monkeypatch.setattr(attention, "_use_pallas", lambda: tpu)
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    assert (dm["hd"], t) == (8, 256)
    assert not attention.kernel_applies(t, dm["hd"], 2, 2 * dm["hd"])
    p = {name: jax.ShapeDtypeStruct((dm["hd"],), jnp.float32)
         for name in ("lq1", "lk1", "lq2", "lk2")}
    p["sub_norm"] = jax.ShapeDtypeStruct((2 * dm["hd"],), jnp.float32)
    shapes = (p,
              jax.ShapeDtypeStruct((2, t, dm["pairs"], 2, dm["hd"]), cd),
              jax.ShapeDtypeStruct((2, t, dm["kv_pairs"], 2, dm["hd"]), cd),
              jax.ShapeDtypeStruct((2, t, dm["kv_pairs"], 2 * dm["hd"]), cd))
    kw = dict(l=15, dm=dm, cd=cd, window=window)
    assert _grad_text(functools.partial(sysm.diff_core, **kw), *shapes) \
        == _grad_text(functools.partial(_parents_diff_core, **kw), *shapes)
    assert (sysm.TRACED["core_kernel"], sysm.TRACED["core_xla"]) == (0, 1)


def test_the_cells_program_takes_the_kernel_in_all_three_cores(monkeypatch):
    """The cell's configuration, traced (not lowered: off the chip only
    the interpreter lowers) with a TPU backend stood in: ``window``,
    ``full`` and ``cross`` each go through ``diff_attention``, forward
    and backward, the window layer with its 512, and no core through the
    loop; on this backend the same program traces the loop three times."""
    config = published()
    init, apply, (vocab, t) = sysm.build(config)
    params = jax.eval_shape(lambda: init(jax.random.key(0))[0])
    ids = jax.ShapeDtypeStruct((2, t), jnp.int32)
    for tpu, want in ((True, (3, 0)), (False, (0, 3))):
        # A function of its own a pass: the tracing cache knows nothing of
        # the stand-in.
        grad = jax.grad(lambda p, x: apply(
            p, {}, x, compute_dtype=jnp.bfloat16)[0].sum())
        monkeypatch.setattr(attention, "_use_pallas", lambda: tpu)
        monkeypatch.setattr(selscan, "_use_pallas", lambda: tpu)
        monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
        text = str(jax.make_jaxpr(grad)(params, ids))
        assert (sysm.TRACED["core_kernel"], sysm.TRACED["core_xla"]) == want
        assert sysm.TRACED["window"] == sysm.TRACED["full"] \
            == sysm.TRACED["cross"] == 1
        for name in ("diff_attention_fwd", "diff_attention_bwd"):
            assert (name in text) == tpu, name
        assert ("causal_gqa" in text) is False


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
    "ddp_tpu.models.sambay", "ddp_tpu.ops.seq", "ddp_tpu.models.nemotron_h",
    "jax.experimental.pallas"))
print("SEEN", seen)
"""


def test_classifier_processes_never_import_the_token_models():
    """PR 30's fence, with this model behind it: a fresh process that
    builds VGG's and ResNet's steps has imported neither ``models.sambay``
    nor what it shares with ``nemotron_h`` (``ops/seq.py``) nor Pallas."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run([sys.executable, "-c", _CLASSIFIER_PROCESS],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "SEEN []"


def test_sambay_alone_imports_its_own_kernels_only():
    """The model brings its selective scan's kernels (``ops/selscan.py``,
    and so Pallas) and, since its attention cores run the blocked kernel
    pair, ``ops/attention.py``; nothing else of ``nemotron_h``'s: neither
    that model nor its scan kernels; ``ops/seq.py`` is plain XLA."""
    code = ("import sys; import ddp_tpu.models.sambay; print('SEEN', sorted("
            "m for m in sys.modules if m in ('ddp_tpu.ops.selscan', "
            "'ddp_tpu.models.nemotron_h', 'ddp_tpu.ops.attention', "
            "'ddp_tpu.ops.ssd')))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] \
        == "SEEN ['ddp_tpu.ops.attention', 'ddp_tpu.ops.selscan']"
