"""``nemotron_h`` (models/nemotron_h.py) against its plain reference
(benchmark/reference/nemotron_h.py), at a small size on the CPU: the
mixers one by one and the 9-layer pattern, the chunked scan, the expert
share, planted faults, the dropless layer, the Trainer's path, the
operation count and the rehearsed benchmark cell.  Also: the classifiers'
step programs do not move with this model's arrival."""
import copy
import functools
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

from benchmark import flops_seq  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from ddp_tpu.models import MODEL_NAMES, get_model  # noqa: E402
from ddp_tpu.models import moe  # noqa: E402
from ddp_tpu.models import nemotron_h as sysm  # noqa: E402
from ddp_tpu.ops import attention, ssd  # noqa: E402

CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b_ep16.json")
TINY = dict(
    hidden_size=64, norm_eps=1e-5, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=4, router_experts=16, experts_held=[4, 4],
    num_experts_per_tok=6, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
    norm_topk_prob=True, vocab_size=256, num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EME", time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4)
T = 80  # not a multiple of the chunk


def tiny(pattern="MEMEM*EME", **over):
    return dict(TINY, hybrid_override_pattern=pattern,
                num_hidden_layers=len(pattern), **over)


def seeded(config, seed=0, scale=4.0):
    """Weights from the program's initialiser, the matrices scaled up so
    that every mixer moves the result (std 0.02 at width 64 leaves the
    residual stream almost untouched)."""
    params, state = sysm.build(config)[0](jax.random.key(seed))
    embed = params["embed"]  # already at the residual stream's scale
    params = jax.tree_util.tree_map(
        lambda p: p * scale if p.ndim >= 2 else p, params)
    return dict(params, embed=embed), state


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


def reference_loss_and_grads(config, params, state, ids, targets):
    with jax.default_matmul_precision("highest"):
        loss, grads, _ = ref.loss_and_grads(config, params, state,
                                            np.asarray(ids),
                                            np.asarray(targets))
        logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    return loss, grads, logits


# -- (a) the system against the reference ----------------------------------------

@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("pattern,path", [
    ("M", "xla"), ("*", "xla"), ("E", "xla"), ("MEMEM*EME", "xla"),
    ("*", "kernel"), ("MEMEM*EME", "kernel"), ("M", "scan_kernel"),
    ("MEMEM*EME", "scan_kernel")])
def test_matches_reference(pattern, path, cd, monkeypatch):
    # Several query blocks, the last one ragged.
    monkeypatch.setattr(sysm, "ATTN_QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 48)
    # Several row tiles an expert, the last one of each part padding.
    monkeypatch.setattr(moe, "MOE_ROW_TILE", 16)
    config, t = tiny(pattern), T
    monkeypatch.setattr(attention, "TRACED", {"kernel": 0, "xla": 0})
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    if path == "kernel":
        # The chip's path, through the interpreter: heads of whole lanes,
        # two query blocks of two key tiles.
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
        monkeypatch.setattr(attention, "FWD_BLOCKS", (128, 128))
        monkeypatch.setattr(attention, "BWD_BLOCKS", (128, 128))
        monkeypatch.setattr(attention, "causal_gqa", functools.partial(
            attention.causal_gqa, interpret=True))
        config, t = tiny(pattern, head_dim=128), 256
    if path == "scan_kernel":
        # The scan's chip path, through the interpreter: a state of whole
        # lanes, eight heads of 64 a group, three chunks of 128, a chunk
        # and two a grid step.
        monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
        monkeypatch.setattr(ssd, "FWD_CHUNKS", 1)
        monkeypatch.setattr(ssd, "BWD_CHUNKS", 2)
        monkeypatch.setattr(ssd, "ssd_scan", functools.partial(
            ssd.ssd_scan, interpret=True))
        config, t = tiny(pattern, mamba_num_heads=16, mamba_head_dim=64,
                         ssm_state_size=128, chunk_size=128), 384
    params, state = seeded(config)
    ids, targets = batch(t=t)
    loss, grads, logits = system_loss_and_grads(config, params, state, ids,
                                                targets, cd)
    taken = {k for k, n in attention.TRACED.items() if n}
    assert taken == ({"kernel" if path == "kernel" else "xla"}
                     if "*" in pattern else set())
    taken = {k for k, n in ssd.TRACED.items() if n}
    assert taken == ({"kernel" if path == "scan_kernel" else "xla"}
                     if "M" in pattern else set())
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    tol = 2e-4 if cd is None else 4e-2
    assert logits.dtype == jnp.float32 and logits.shape == (2, t, 256)
    assert rel(logits, r_logits) < tol
    assert abs(float(loss) - float(r_loss)) < tol
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    r_flat = jax.tree_util.tree_leaves(r_grads)
    assert len(flat) == len(r_flat)
    whole = rel(np.concatenate([np.ravel(g) for _, g in flat]),
                np.concatenate([np.ravel(g) for g in r_flat]))
    # Weights four times the initialiser's: bf16 rounding grows with them.
    assert whole < (tol if cd is None else 8e-2), whole
    for (path, g), rg in zip(flat, r_flat):
        # bf16 moves the choice of experts for a few tokens, which the
        # small leaves of an expert layer feel most.
        assert rel(g, rg) < (tol if cd is None else 0.35), \
            (jax.tree_util.keystr(path), rel(g, rg))


# -- (b) the chunked scan against the step-by-step recurrence -------------------------

@pytest.mark.parametrize("t", [32, 64, 50, 7, 100])
def test_chunked_scan_matches_recurrence(t):
    h, p, g, n = 8, 8, 2, 16
    ks = jax.random.split(jax.random.key(t), 6)
    x = jax.random.normal(ks[0], (1, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (1, t, g, n))
    c = jax.random.normal(ks[4], (1, t, g, n))
    d = jax.random.normal(ks[5], (h,))
    y = sysm.ssd_chunked(x, dt, a, b, c, d, 32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_ref = ref.recurrence(x[0], dt[0], a, b[0], c[0], d)
    assert y.shape == (1, t, h, p)
    assert rel(y[0], y_ref) < 1e-5


# -- (c) the share ties to the model ----------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts in shares of 4: the four shares' routed parts, plus the
    shared expert counted once, are the uncut reference's layer."""
    whole = tiny("E", n_routed_experts=16, experts_held=[0, 16])
    params, state = seeded(whole)
    p, st = params["layers"]["layer_00"], state["layer_00"]
    x = jax.random.normal(jax.random.key(3), (2, T, 64))
    with jax.default_matmul_precision("highest"):
        uncut = jax.vmap(lambda row: ref.experts(
            p, st["e_bias"], row, ref.dims(whole)))(x)
    shared = moe.shared_expert(p, x.reshape(-1, 64), jnp.float32,
                               moe.RELU2).reshape(x.shape)
    total = shared
    for first in (0, 4, 8, 12):
        share = tiny("E", experts_held=[first, 4])
        p_s = dict(p, up=p["up"][first:first + 4],
                   down=p["down"][first:first + 4])
        st_s = dict(st, assignments=jnp.zeros((4,), jnp.int32))
        y, new = sysm.expert_mixer(p_s, st_s, x, sysm.dims(share),
                                   jnp.float32, train=True)
        total = total + (y - shared)
        assert int(new["dropped"]) == 0
    assert rel(total, uncut) < 1e-5


def test_sliced_head_gives_the_whole_heads_rows():
    whole = tiny("M")
    params, state = seeded(whole)
    ids, _ = batch(vocab=128)
    logits, _ = sysm.build(whole)[1](params, state, ids)
    share = tiny("M", vocab_size=128, vocab_held=[0, 128])
    p_s = dict(params, embed=params["embed"][:128],
               head=params["head"][:, :128])
    sliced, _ = sysm.build(share)[1](p_s, state, ids)
    assert sliced.shape == (2, T, 128)
    np.testing.assert_allclose(sliced, logits[..., :128], rtol=1e-5,
                               atol=1e-6)


# -- (d) planted faults ---------------------------------------------------------------------

def _zero(params, layer, leaf):
    out = copy.copy(params)
    out["layers"] = dict(params["layers"])
    out["layers"][layer] = dict(params["layers"][layer],
                                **{leaf: params["layers"][layer][leaf] * 0})
    return out


def _fault_shared_dropped(mp, config, params, state):
    mp.setattr(moe, "shared_expert", lambda p, x, cd, form: jnp.zeros(
        (x.shape[0], p["shared_down"].shape[1]), cd))
    return config, params, state


def _fault_scale_dropped(mp, config, params, state):
    return dict(config, routed_scaling_factor=1.0), params, state


def _fault_not_normalised(mp, config, params, state):
    return dict(config, norm_topk_prob=False), params, state


def _fault_bias_not_in_choice(mp, config, params, state):
    return config, params, {k: dict(v, e_bias=v["e_bias"] * 0)
                            for k, v in state.items()}


def _fault_bias_in_weights(mp, config, params, state):
    def route_weights(s, e_bias, dm):
        return _ROUTE(s + e_bias, jnp.zeros_like(e_bias), dm)
    mp.setattr(moe, "route_weights", route_weights)
    return config, params, state


def _fault_d_skipped(mp, config, params, state):
    return config, _zero(params, "layer_00", "D"), state


def _fault_conv_bias_skipped(mp, config, params, state):
    return config, _zero(params, "layer_00", "conv_b"), state


def _fault_gate_after_norm(mp, config, params, state):
    def gated_norm(y, z, weight, groups, eps, out_dtype):
        normed = _GATED(y, jnp.full_like(z, 1.2785), weight, groups, eps,
                        jnp.float32)  # silu(1.2785) = 1: the norm alone
        return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(
            out_dtype)
    mp.setattr(sysm, "gated_norm", gated_norm)
    return config, params, state


def _fault_bf16_recurrence(mp, config, params, state):
    def scan(states, decay):
        def step(h_prev, inp):
            s_c, d_c = inp
            return (d_c[..., None, None] * h_prev + s_c), h_prev
        bf = jnp.bfloat16
        _, entering = jax.lax.scan(
            step, jnp.zeros(states.shape[1:], bf),
            (states.astype(bf), decay.astype(bf)))
        return entering.astype(jnp.float32)
    mp.setattr(sysm, "chunk_state_scan", scan)
    return config, params, state


_ROUTE, _GATED = moe.route_weights, sysm.gated_norm
FAULTS = {
    "shared_expert_dropped": _fault_shared_dropped,
    "routed_scaling_factor_dropped": _fault_scale_dropped,
    "weights_not_normalised": _fault_not_normalised,
    "bias_left_out_of_the_choice": _fault_bias_not_in_choice,
    "bias_put_into_the_weights": _fault_bias_in_weights,
    "D_skipped": _fault_d_skipped,
    "conv_bias_skipped": _fault_conv_bias_skipped,
    "gate_applied_after_the_norm": _fault_gate_after_norm,
    "bf16_recurrence": _fault_bf16_recurrence,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    config = tiny("MEMEM*EME")
    params, state = seeded(config)
    ids, _ = batch()
    with jax.default_matmul_precision("highest"):
        r_logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    # In float32 the sound system is within rounding of the reference ...
    logits, _ = jax.jit(sysm.build(config)[1])(params, state, ids)
    assert rel(logits, r_logits) < 2e-5
    # ... and the faulty one is not.
    f_config, f_params, f_state = FAULTS[fault](monkeypatch, config, params,
                                                state)
    logits, _ = jax.jit(sysm.build(f_config)[1])(f_params, f_state, ids)
    assert rel(logits, r_logits) > 2e-5, rel(logits, r_logits)


# -- (e) dropless ---------------------------------------------------------------------------------

def _one_expert_wins(config, monkeypatch):
    """Expert 5 (held: 4..7) wins every token by its bias; the router's
    weights are left as they are."""
    monkeypatch.setattr(moe, "MOE_ROW_TILE", 16)
    params, state = seeded(config)
    p, st = params["layers"]["layer_00"], state["layer_00"]
    st = dict(st, e_bias=jnp.zeros((16,)).at[5].set(10.0))
    x = jax.random.normal(jax.random.key(4), (2, T, 64))
    y, new = sysm.expert_mixer(p, st, x, sysm.dims(config), jnp.float32,
                               train=True)
    with jax.default_matmul_precision("highest"):
        y_ref = jax.vmap(lambda row: ref.experts(
            p, st["e_bias"], row, ref.dims(config)))(x)
    return y, y_ref, new


@pytest.mark.parametrize("headroom", [5, 2])
def test_dropless_when_every_token_goes_to_one_held_expert(
        headroom, monkeypatch):
    """At the program's headroom the tiny share's buffer holds every
    assignment that can fall on a held expert (4 a token); at twice the
    uniform load's 1.5 rows a token it still holds a load of two rows a
    token of which one a token falls on ONE expert."""
    monkeypatch.setattr(moe, "MOE_LOAD_HEADROOM", headroom)
    y, y_ref, new = _one_expert_wins(tiny("E"), monkeypatch)
    assert int(new["dropped"]) == 0
    assert int(new["assignments"][1]) == 2 * T
    assert rel(y, y_ref) < 1e-5


def test_dropped_counts_the_assignments_that_found_no_room(monkeypatch):
    """A buffer of half a row a token (a third of the uniform load's 1.5)
    cannot hold a row a token: what found no room is counted, and adds
    nothing."""
    monkeypatch.setattr(moe, "MOE_LOAD_HEADROOM", 1 / 3)
    y, y_ref, new = _one_expert_wins(tiny("E"), monkeypatch)
    routed_here = int(new["assignments"].sum())
    cap = (-(-int(2 * T * 0.5) // 16) + 4) * 16
    assert routed_here > cap
    # Expert 4's rows come first and all fit; whole tiles of padding
    # between the experts cost room too, so at least this many are lost.
    assert routed_here - cap <= int(new["dropped"]) < routed_here
    assert np.isfinite(np.asarray(y)).all() and rel(y, y_ref) > 1e-3


@pytest.mark.parametrize("load", ["even", "one_expert", "none_here"])
def test_row_plan_places_every_assignment_once(load):
    count, tile, tiles = 4, 8, 12
    key = {"even": np.arange(40) % 5,               # 4 = held elsewhere
           "one_expert": np.full(40, 2),
           "none_here": np.full(40, 4)}[load].astype(np.int32)
    sizes, src, tile_expert, dropped = map(np.asarray, moe.row_plan(
        jnp.asarray(key), count, tile, tiles)[:4])
    here = np.flatnonzero(key < count)
    assert sizes.tolist() == [int((key == e).sum()) for e in range(count)]
    assert int(dropped) == 0
    # Every assignment held here sits in exactly one row, in a tile of
    # its expert; every other row is padding.
    rows = np.flatnonzero(src < key.size)
    assert sorted(src[rows].tolist()) == here.tolist()
    assert (tile_expert[rows // tile] == key[src[rows]]).all()
    assert (np.delete(src, rows) == key.size).all()


# -- (f) through the Trainer ---------------------------------------------------------------------

def _trainer(config, tracer=None, registry=None, **kw):
    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    model = get_model("nemotron_h", config)
    params, state = model.init(jax.random.key(0))
    loader = TrainLoader(synthetic_tokens(16, 64, 256, seed=0), 2, 1,
                         augment=False, seed=0)
    sched = functools.partial(triangular_lr, base_lr=2.0, num_epochs=60,
                              steps_per_epoch=8, peak_frac=0.3)
    return Trainer(model, loader, params, state, mesh=make_mesh(1),
                   lr_schedule=sched,
                   sgd_config=SGDConfig(lr=2.0, momentum=0.9,
                                        weight_decay=0.0),
                   save_every=10**9, snapshot_path=None,
                   compute_dtype=jnp.bfloat16, tracer=tracer,
                   registry=registry, **kw)


def test_three_epochs_through_the_trainer(monkeypatch):
    from ddp_tpu.obs.registry import MetricsRegistry, parse_exposition
    from ddp_tpu.obs.tracer import SpanTracer
    tracer, registry = SpanTracer(ring=1 << 16), MetricsRegistry()
    monkeypatch.setattr(attention, "TRACED", {"kernel": 0, "xla": 0})
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    trainer = _trainer(tiny(), tracer=tracer, registry=registry)
    trainer.train(3)
    # Heads of 16 and a state of 16 on the CPU: the XLA loop and the
    # chunked form in XLA, chosen once when the step is traced (forward,
    # and again under each checkpoint).
    print("attention paths traced:", attention.TRACED)
    print("scan paths traced:", ssd.TRACED)
    assert attention.TRACED["kernel"] == 0 < attention.TRACED["xla"]
    assert ssd.TRACED["kernel"] == 0 < ssd.TRACED["xla"]
    losses = np.asarray(trainer.loss_history)
    assert losses.shape == (24,) and np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean()
    spans = tracer.spans_since(0.0)
    assert {"epoch_setup", "data_wait", "h2d", "dispatch", "epoch_close",
            "loss_flush"} <= {s["phase"] for s in spans}
    # dispatch carries sequences, not tokens.
    assert {s["n"] for s in spans if s["phase"] == "dispatch"} == {2}
    families = parse_exposition(registry.exposition())
    routed = sum(v for (name, _labels), v in _samples(families)
                 if name == "ddp_moe_assignments_total")
    assert routed == sum(int(v["assignments"].sum())
                         for v in trainer.routing.totals.values()) > 0
    assert all(v == 0 for (name, _l), v in _samples(families)
               if name == "ddp_moe_dropped_total")
    assert all(v >= 1 for (name, _l), v in _samples(families)
               if name == "ddp_moe_load_max_over_mean")


def _samples(families):
    for fam in families.values():
        yield from fam["samples"].items()


@pytest.mark.parametrize("flag,why", [
    ("resident", "image rows"), ("shard_update", "routing counters")])
def test_paths_not_wired_are_refused_with_their_reason(flag, why):
    with pytest.raises(ValueError, match=why):
        _trainer(tiny(), **{flag: True})


@pytest.mark.parametrize("name,tokens", [
    ("vgg", None), ("resnet18", None), ("tinylm", (256, 128)),
    ("nemotron_h", (256, 64))])
def test_token_input_is_a_property_of_the_model_not_a_name(name, tokens):
    """What the Trainer and the CLI branch on is ``ModelDef.tokens``:
    ``tinylm``, which needs no configuration, carries it too."""
    config = tiny(seq_len=64) if name == "nemotron_h" else None
    assert get_model(name, config).tokens == tokens


def test_the_trainer_refuses_by_the_property(monkeypatch):
    """A model this file has never heard of, with ``tokens`` set, is
    refused ``resident`` like any token model."""
    import ddp_tpu.models as models
    real = models.get_model
    monkeypatch.setattr(
        sys.modules[__name__], "get_model",
        lambda name, config: real(name, config)._replace(name="other"))
    with pytest.raises(ValueError, match="'other'.*\n.*image rows"):
        _trainer(tiny(), resident=True)


def test_counters_are_summed_over_replicas_not_averaged():
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import (init_train_state, make_train_step,
                                    shard_batch)
    config = tiny("ME")
    model = get_model("nemotron_h", config)
    params, state = seeded(config)
    ids, targets = batch(b=4)
    step = make_train_step(model, SGDConfig(lr=0.1), lambda s: 0.1,
                           make_mesh(2))
    one = {"image": np.asarray(ids), "label": np.asarray(targets)}
    _, whole = model.apply(params, state, ids, train=True)
    first = np.asarray(whole["layer_01"]["assignments"])
    st = init_train_state(params, state)  # donated to the step
    for _ in range(2):  # cumulative: the old count is not summed again
        st, _loss = step(st, shard_batch(one, make_mesh(2)),
                         jax.random.key(0))
    counted = np.asarray(st.batch_stats["layer_01"]["assignments"])
    assert 0 < counted.sum() <= 2 * 4 * T * 4
    assert first.sum() * 1.5 < counted.sum()  # two steps, both replicas


# -- (g) the operation count ------------------------------------------------------------------

def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


@pytest.mark.parametrize("kind,mflop", [
    ("M", 80.6), ("*", 113.9), ("E", 48.1), ("head", 88.1)])
def test_flops_seq_against_hand_counts(kind, mflop):
    """ISSUE 28's hand counts, forward MFLOP a token at T = 8,192."""
    macs = flops_seq.layer_macs_per_token(ref.layer_shapes(published()),
                                          8192)
    assert round(2 * sum(macs[kind].values()) / 1e6, 1) == mflop


def test_flops_seq_a_sequence():
    dm = ref.layer_shapes(published())
    # M: in_proj 2688 x 10304, conv 4 x 6144, 3 x 64 x 64 x 128, out_proj.
    assert flops_seq.layer_macs_per_token(dm, 8192)["M"] == {
        "in_proj": 2688 * 10304, "conv": 4 * 6144,
        "scan": 3 * 64 * 64 * 128, "out_proj": 4096 * 2688}
    assert round(flops_seq.forward_flops_per_token(dm, 8192) / 1e9, 3) \
        == 0.717
    assert round(flops_seq.train_flops_per_sequence(dm, 8192) / 1e12, 1) \
        == 17.6
    # The recurrence is bound by bytes: 86,784 a token a layer.
    assert flops_seq.scan_train_bytes(dm, 1) == 4 * 3 * (
        (3 * 4096 + 2 * 1024) * 2 + 64 * 4)
    assert flops_seq.expert_train_flops(dm, 1) == 3 * 2 * 2 * 2688 * 1856


# -- the configuration file ------------------------------------------------------------------

def test_config_file_holds_the_published_widths():
    c = published()
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
            c["chunk_size"]) == (2688, 64, 64, 8, 128, 4, 128)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (32, 2, 128)
    assert (c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"], c["router_experts"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) \
        == (1856, 3712, 128, 6, 2.5)
    assert sorted(c["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"])
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["vocab_size"]) \
        == (9, "MEMEM*EME", 8, 16384)
    assert c["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert c["published"]["hybrid_override_pattern"].startswith(
        c["hybrid_override_pattern"])


def test_parameter_count_from_the_initialised_tree():
    c = published()
    shapes = jax.eval_shape(lambda: get_model("nemotron_h", c).init(
        jax.random.key(0)))[0]
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == c["parameters"] == 666_962_944  # 667.0 M


def test_get_model_lists_its_names_and_asks_for_a_config():
    with pytest.raises(ValueError, match=", ".join(MODEL_NAMES)):
        get_model("no_such_model")
    with pytest.raises(ValueError, match="--model_config"):
        get_model("nemotron_h")
    assert get_model("vgg", {"ignored": True}).name == "vgg"


def test_cross_entropy_leaves_ignored_positions_out():
    from ddp_tpu.ops.losses import IGNORE, cross_entropy_sum_count
    logits = jax.random.normal(jax.random.key(0), (2, 5, 7))
    labels = jnp.array([[1, 2, 3, 4, IGNORE], [0, IGNORE, 6, 5, IGNORE]])
    s, n = cross_entropy_sum_count(logits, labels)
    r_s, r_n = ref.cross_entropy_sum(logits, labels)
    assert float(n) == 7 == int(r_n)
    np.testing.assert_allclose(s, r_s, rtol=1e-6)


def test_token_generator_is_seeded_and_shifted():
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.ops.losses import IGNORE
    a, b = synthetic_tokens(4, 64, 256, seed=3), synthetic_tokens(
        4, 64, 256, seed=3)
    assert np.array_equal(a.images, b.images)
    assert a.images.dtype == np.int32 and a.images.shape == (4, 64)
    assert np.array_equal(a.labels[:, :-1], a.images[:, 1:])
    assert (a.labels[:, -1] == IGNORE).all()
    follows = (a.images[:, 1:] == (31 * a.images[:, :-1] + 7) % 256).mean()
    assert 0.6 < follows < 0.9


def test_obs_prints_the_routing_counters(tmp_path, capsys):
    from ddp_tpu.obs.__main__ import main
    from ddp_tpu.obs.registry import MetricsRegistry
    from ddp_tpu.obs.routing import RoutingCounters
    registry = MetricsRegistry()
    RoutingCounters(registry).update({"layer_01": {
        "assignments": np.array([3, 9], np.int32),
        "dropped": np.array(0, np.int32)}})
    prom = tmp_path / "run.prom"
    prom.write_text(registry.exposition())
    assert main(["--prom", str(prom)]) == 0
    out = capsys.readouterr().out
    assert "layer_01" in out and "1.50" in out and "12" in out


# -- (h) the benchmark's cell, rehearsed ----------------------------------------------------

def test_the_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "nemotron3_nano_train_8k_1chip", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is False and result["failed"] == 0
    assert {"first_step_s", "compiles_in_window", "epoch_setup_ms",
            "epoch_close_ms", "host_untraced_pct", "moe_load_max_over_mean",
            "moe_dropped_pct"} <= set(result["metrics"])
    assert result["metrics"]["moe_dropped_pct"]["value"] == 0.0
    detail = json.loads([ln for ln in proc.stderr.splitlines()
                         if ln.startswith("benchmark-detail: ")][-1]
                        .split(": ", 1)[1])
    assert all(detail["checks"].values()), detail["checks"]


# -- the classifiers' programs do not move ------------------------------------------------

def _lowered_step(name, cd):
    """The lowered text of ``make_train_step(model)`` on a 2-device mesh
    at 8 samples, locations (the only place a scope's name shows)
    stripped."""
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import init_train_state, make_train_step
    model = get_model(name)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    sched = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                              steps_per_epoch=98, peak_frac=0.3)
    step = make_train_step(model, SGDConfig(), sched, make_mesh(2),
                           compute_dtype=cd)
    b = {"image": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8),
         "label": jax.ShapeDtypeStruct((8,), jnp.int32)}
    return re.sub(r"loc\(.*?\)", "",
                  step.lower(state, b, jax.random.key(0)).as_text())


def _loss_before_the_token_model(logits, labels):
    """``ops.losses.cross_entropy_sum_count`` as a classifier's step called
    it before per-position labels: one label a sample, no ignored id."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    ce = logz - picked
    return ce.sum(), jnp.asarray(ce.shape[0], jnp.float32)


@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["vgg", "resnet18"])
def test_classifier_step_program_is_the_parents(name, cd, monkeypatch):
    """What the step builders gained for the token model (a scope around
    the update and one around a per-position loss, the loss's branch on
    the labels' rank, a sum for integer state) reaches no classifier's
    program: built without them, the lowered text is the same."""
    import contextlib

    from jax import lax

    import ddp_tpu.train.step as step_lib
    with_them = _lowered_step(name, cd)
    monkeypatch.setattr(step_lib, "cross_entropy_sum_count",
                        _loss_before_the_token_model)
    monkeypatch.setattr(step_lib, "_reduce_state_leaf",
                        lambda new, _old: lax.pmean(new, step_lib.DATA_AXIS))
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    assert _lowered_step(name, cd) == with_them
