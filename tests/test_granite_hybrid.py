"""``granite_hybrid`` (models/granite_hybrid.py) against its plain
reference (benchmark/reference/granite_hybrid.py), at a small size on the
CPU: logits, loss and every gradient leaf at one group and at two, through
ssd_chunked and through the scan kernels in the Pallas interpreter (one
group wider than a grid step); each µP scalar moves both sides alike;
planted faults; the vocabulary slice; the step builder, the Trainer and
the CLI; the parameter counts, the operation count, the configuration
file, the readers and the rehearsed benchmark cell."""
import functools
import importlib
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

from benchmark import flops_granite  # noqa: E402
from benchmark.reference import granite_hybrid as ref  # noqa: E402
from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import get_model  # noqa: E402
from ddp_tpu.models import granite_hybrid as sysm  # noqa: E402
from ddp_tpu.models import nemotron_h  # noqa: E402
from ddp_tpu.ops import ssd  # noqa: E402
from ddp_tpu.ops.losses import IGNORE, cross_entropy_sum_count  # noqa: E402

CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "granite4_h_micro_stage0.json")
CELL = "granite4_h_micro_train_8k_1chip"
T = 96


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny(**over):
    """The configuration file's own tiny preset (the ten layers, one group
    of 16 Mamba-2 heads of 8) with ``over`` on top."""
    pub = published()
    return overlay(pub, {**{k: v for k, v in pub["tiny"].items()
                            if not k.startswith("_")}, **over})


def seeded(config, seed=0, scale=4.0):
    """Weights from the program's initialiser, the matrices scaled up so
    that every part moves the result, the norms' weights moved off 1."""
    params, state = sysm.build(config)[0](jax.random.key(seed))
    key = jax.random.key(seed + 99)

    def move(path, p):
        if p.ndim == 1:
            k = jax.random.fold_in(key, sum(map(ord, jax.tree_util.keystr(
                path))))
            name = path[-1].key
            if name in ("A_log", "dt_bias", "D"):
                return p
            return p + 0.1 * jax.random.normal(k, p.shape)
        return p * scale

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
        logits, _ = apply(p, state, ids, train=True, compute_dtype=cd)
        s, c = cross_entropy_sum_count(logits, targets)
        return s / c, logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(f, has_aux=True))(params)
    return loss, grads, logits


def reference_loss_and_grads(config, params, state, ids, targets):
    with jax.default_matmul_precision("highest"):
        loss, grads, logits0 = ref.loss_and_grads(
            config, params, state, np.asarray(ids), np.asarray(targets))
        logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    np.testing.assert_allclose(logits0[0], logits[0], rtol=1e-5, atol=1e-5)
    return loss, grads, logits


def leaf_errors(grads, r_grads):
    return {jax.tree_util.keystr(k): rel(a, b) for (k, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_flatten_with_path(r_grads)[0])}


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    return sysm.TRACED


# -- (a) the system against the reference -------------------------------------------

@pytest.mark.parametrize("cd", [None, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("groups", [1, 2])
def test_matches_reference(groups, cd, traced):
    """Logits, the loss and EVERY gradient leaf of the ten layers through
    ``ssd_chunked`` and the attention loop, at one group (the cell's: the
    norm over all of a token's channels) and at two: to rounding in
    float32; in the cell's precision within what bf16 operands cost."""
    config = tiny(mamba_n_groups=groups)
    params, state = seeded(config)
    ids, targets = batch()
    loss, grads, logits = system_loss_and_grads(config, params, state, ids,
                                                targets, cd)
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    assert logits.dtype == jnp.float32 and logits.shape == (2, T, 256)
    errs = leaf_errors(grads, r_grads)
    assert len(errs) == 10 * 4 + 9 * 8 + 4 + 2 and state == {}
    if cd is None:
        assert abs(float(loss) - r_loss) < 2e-5
        assert rel(logits, r_logits) < 2e-5
        assert max(errs.values()) < 1e-4, max(errs, key=errs.get)
    else:
        assert abs(float(loss) - r_loss) < 5e-3
        assert rel(logits, r_logits) < 0.03
        assert max(errs.values()) < 0.2, max(errs, key=errs.get)
        assert sorted(errs.values())[len(errs) // 2] < 0.05
    assert traced == {"mamba": 9, "attention": 1, "scan_kernel": 0,
                      "scan_xla": 9, "conv_kernel": 0, "conv_xla": 9,
                      "core_kernel": 0, "core_xla": 1}


@pytest.mark.parametrize("heads", [16, 24])
def test_kernel_path_matches_reference(heads, traced, monkeypatch):
    """The same through the scan kernels (the interpreter) at ONE group of
    ``heads`` Mamba-2 heads of 64, state 128, chunks of 128, the group
    scanned in tiles of ``HEAD_TILE`` heads (two and three grid steps) and
    gated and normed after: a Mamba-2 layer and the attention layer."""
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "ssd_scan", functools.partial(
        ssd.ssd_scan, interpret=True))
    config = tiny(num_hidden_layers=2, layer_types=["mamba", "attention"],
                  mamba_n_heads=heads, mamba_d_head=64, mamba_d_state=128,
                  mamba_chunk_size=128)
    params, state = seeded(config, scale=2.0)
    ids, targets = batch(t=256)
    loss, grads, logits = system_loss_and_grads(config, params, state, ids,
                                                targets)
    assert traced == {"mamba": 1, "attention": 1, "scan_kernel": 1,
                      "scan_xla": 0, "conv_kernel": 0, "conv_xla": 1,
                      "core_kernel": 0, "core_xla": 1}
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        config, params, state, ids, targets)
    assert abs(float(loss) - r_loss) < 2e-5
    assert rel(logits, r_logits) < 2e-5
    errs = leaf_errors(grads, r_grads)
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_bf16_step_stands_apart_from_the_bf16_reference(seed):
    """The cell's first-step limits lie between the system in its
    precision and the reference in bfloat16 throughout, so the system has
    to stand well apart from the latter: at the program's own initial
    weights its logits lie less than half as far from float32 as the
    bfloat16 reference's.  A residual stream rounded to bf16 after every
    branch reads 0.84 of it here."""
    from benchmark import reference_check_tok
    config = tiny()
    params, state = jax.device_get(sysm.build(config)[0](jax.random.key(seed)))
    ids, targets = map(np.asarray, batch(seed=seed + 1, t=256))
    full = reference_check_tok.reference_side(config, params, state, ids,
                                              targets)
    low = reference_check_tok.reference_side(config, params, state, ids,
                                             targets, cast=jnp.bfloat16)
    _loss, _grads, logits = system_loss_and_grads(
        config, params, state, ids, targets, jnp.bfloat16)
    control = rel(low["logits0"], full["logits0"])
    assert rel(logits[:1], full["logits0"]) < 0.5 * control, control


MULTIPLIERS = {"embedding_multiplier": 3.0, "residual_multiplier": 0.7,
               "attention_multiplier": 1.0, "logits_scaling": 2.0}


@pytest.mark.parametrize("name", sorted(MULTIPLIERS))
def test_each_mup_scalar_moves_both_sides_alike(name):
    """Set to another value, each µP scalar moves the logits (by far more
    than rounding) and the system and the reference stay together."""
    config = tiny()
    params, state = seeded(config)
    ids, targets = batch()
    before = sysm.build(config)[1](params, state, ids)[0]
    moved = tiny(**{name: MULTIPLIERS[name]})
    loss, grads, logits = system_loss_and_grads(moved, params, state, ids,
                                                targets)
    r_loss, r_grads, r_logits = reference_loss_and_grads(
        moved, params, state, ids, targets)
    assert rel(logits, before) > 1e-3
    assert abs(float(loss) - r_loss) < 2e-5
    assert rel(logits, r_logits) < 2e-5
    errs = leaf_errors(grads, r_grads)
    assert max(errs.values()) < 1e-4, max(errs, key=errs.get)


@pytest.mark.parametrize("over,why", [
    (dict(num_local_experts=4), "dense silu MLPs"),
    (dict(position_embedding_type="rope"), "no positional encoding"),
    (dict(tie_word_embeddings=False), "a tied head"),
    (dict(layer_types=["mamba"] * 9), "must hold 10 entries"),
    (dict(layer_types=["mamba"] * 9 + ["window"]), "mamba and attention")])
def test_a_file_that_cannot_run_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        sysm.build(tiny(**over))


# -- (b) planted faults ---------------------------------------------------------------

def _dims_with(**over):
    def fault(mp):
        mp.setattr(sysm, "dims",
                   lambda c, f=sysm.dims: dict(f(c), **over))
    return fault


def _fault_halves_swapped(mp):
    def swapped(p, u, dm, cd, f=sysm.mlp):
        w = p["input_linear"]
        return f(dict(p, input_linear=jnp.concatenate(
            [w[:, dm["ff"]:], w[:, :dm["ff"]]], axis=1)), u, dm, cd)
    mp.setattr(sysm, "mlp", swapped)


def _fault_norm_per_head(mp):
    def norm(y, z, weight, groups, eps, out_dtype, f=nemotron_h.gated_norm):
        return f(y, z, weight, groups * 16, eps, out_dtype)
    mp.setattr(nemotron_h, "gated_norm", norm)


FAULTS = {"residual_multiplier_off": _dims_with(residual=1.0),
          "embedding_multiplier_off": _dims_with(embedding_multiplier=1.0),
          "logits_scaling_off": _dims_with(logits_scaling=1.0),
          "scores_at_one_over_sqrt_hd": _dims_with(attn_scale=0.25),
          "mlp_halves_swapped": _fault_halves_swapped,
          "norm_per_head_not_whole": _fault_norm_per_head}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each part of the equations moves the logits by far more than
    float32 rounding: a program that gets one wrong is caught."""
    config = tiny()
    params, state = seeded(config)
    ids, _ = batch()
    with jax.default_matmul_precision("highest"):
        r_logits, _ = jax.jit(ref.forward(config))(params, state, ids)
    with monkeypatch.context() as mp:
        FAULTS[fault](mp)
        logits, _ = sysm.build(config)[1](params, state, ids)
    assert rel(logits, r_logits) > 1e-3, fault


# -- (c) the vocabulary slice ------------------------------------------------------------

def test_vocabulary_slices_are_the_uncut_models_rows():
    """The tied embedding held as four slices of rows: ids drawn from a
    slice give, over that slice, the uncut model's logits and the loss of
    the uncut model's columns of that slice."""
    whole = tiny()
    params, state = seeded(whole)
    apply = sysm.build(whole)[1]
    for first in range(0, 256, 64):
        ids, targets = batch(seed=first, vocab=64)
        out, _ = apply(params, state, ids + first)
        share = tiny(vocab_size=64, vocab_held=[first, first + 64])
        p_s = dict(params, embed=params["embed"][first:first + 64])
        sliced, _ = sysm.build(share)[1](p_s, state, ids)
        assert sliced.shape == (2, T, 64)
        np.testing.assert_allclose(sliced, out[..., first:first + 64],
                                   rtol=1e-5, atol=1e-5)
        s, c = cross_entropy_sum_count(sliced, targets)
        s_u, c_u = cross_entropy_sum_count(out[..., first:first + 64],
                                           targets)
        assert abs(float(s / c) - float(s_u / c_u)) < 1e-5


# -- (d) through the step builder, the Trainer and the CLI ---------------------------

def test_the_step_over_two_replicas_is_the_reference():
    """The step's loss is the reference's over the GLOBAL batch and the
    momentum is the reference's gradient."""
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import (init_train_state, make_train_step,
                                    shard_batch)
    config = tiny()
    model = get_model("granite_hybrid", config)
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
    errs = leaf_errors(new.opt_state.momentum_buf, r_grads)
    assert max(errs.values()) < 1e-4, max(errs, key=errs.get)


def test_three_epochs_through_the_trainer():
    """The Trainer's stream path at the tiny preset: losses finite and
    falling, no routing counters."""
    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.tokens import synthetic_tokens
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    model = get_model("granite_hybrid", tiny(seq_len=64))
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
                      compute_dtype=jnp.bfloat16)
    trainer.train(3)
    losses = np.asarray(trainer.loss_history)
    assert losses.shape == (24,) and np.isfinite(losses).all()
    assert losses[-8:].mean() < losses[:8].mean()
    assert not trainer.routing.totals


def test_cli_lists_the_model_and_the_registry_builds_it():
    from ddp_tpu.cli import build_parser
    from ddp_tpu.models import MODEL_NAMES
    args = build_parser("test").parse_args(
        ["1", "1", "--model", "granite_hybrid", "--model_config",
         CONFIG_FILE])
    assert args.model == "granite_hybrid" and "granite_hybrid" in MODEL_NAMES
    with pytest.raises(ValueError, match="--model_config"):
        get_model("granite_hybrid")
    assert get_model("granite_hybrid", published()).tokens == (25088, 8192)


def test_the_cli_trains_the_model(tmp_path):
    """``multigpu.py --model granite_hybrid --model_config FILE
    --synthetic``: the parity entry point trains the tiny preset end to end
    and saves."""
    cfg = tmp_path / "granite_tiny.json"
    cfg.write_text(json.dumps(tiny(seq_len=64)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "multigpu.py", "1", "1", "--model",
         "granite_hybrid", "--model_config", str(cfg), "--synthetic",
         "--synthetic_size", "8", "--batch_size", "2", "--bf16", "--lr",
         "0.05", "--snapshot_path", str(tmp_path / "ck.pt")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert (tmp_path / "ck.pt").exists()


def test_every_scan_takes_the_kernel_at_the_cells_widths(monkeypatch):
    """``python -m ddp_tpu.models.granite_hybrid FILE`` at the file's
    widths and 8,192 tokens, as a TPU would see it (traced, nothing run):
    nine scans and nine convolutions through the kernels, none through
    ``ssd_chunked`` or the XLA form, the one attention core on the XLA
    loop (heads 64 wide)."""
    from ddp_tpu.ops import attention, causal_conv, gather
    for module in (ssd, attention, gather, causal_conv):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "TRACED", {"kernel": 0, "xla": 0})
    monkeypatch.setattr(causal_conv, "TRACED", {"kernel": 0, "xla": 0})
    monkeypatch.setattr(sysm, "TRACED", dict.fromkeys(sysm.TRACED, 0))
    assert sysm._main([CONFIG_FILE]) == 0
    assert sysm.TRACED == {"mamba": 9, "attention": 1, "scan_kernel": 9,
                           "scan_xla": 0, "conv_kernel": 9, "conv_xla": 0,
                           "core_kernel": 0, "core_xla": 1}


# -- (e) the counts, the operations, the file --------------------------------------

def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(tree))


def test_parameter_count_of_the_cut():
    """797,850,560 by the issue's count, block by block, from
    ``jax.eval_shape`` of the initialiser at the published widths: a
    Mamba-2 layer 76,182,976, the attention layer 60,821,504, the tied
    slice 51,380,224."""
    config = published()
    params, state = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    layers = params["layers"]
    assert state == {}
    for i, kind in enumerate(config["layer_types"]):
        want = 76_182_976 if kind == "mamba" else 60_821_504
        assert _count(layers[sysm.layer_name(i)]) == want, i
    assert layers["layer_05"]["q"].shape == (2048, 32 * 64)
    assert layers["layer_05"]["k"].shape == (2048, 8 * 64)
    assert layers["layer_00"]["in_proj"].shape == (2048, 8512)
    assert layers["layer_00"]["conv_w"].shape == (4, 4352)
    assert layers["layer_00"]["input_linear"].shape == (2048, 16384)
    assert _count(params["embed"]) == 51_380_224
    assert _count(params) == config["parameters"] == 797_850_560


def test_parameter_count_of_the_uncut_model():
    """40 layers, the whole vocabulary: 3,191,396,096 by the equations."""
    pub = published()
    config = dict(pub, **pub["published"], vocab_held=[0, 100352])
    params, _ = jax.eval_shape(
        lambda: sysm.build(config)[0](jax.random.key(0)))
    assert _count(params) == 3_191_396_096


@pytest.mark.parametrize("part,tflop", [
    ("mlp", 24.7), ("ssm_proj", 11.4), ("head", 2.5), ("attn_core", 0.8),
    ("attn_proj", 0.5), ("scan", 0.7)])
def test_flops_of_a_sequence(part, tflop):
    """The issue's table, TFLOP a sequence of 8,192 tokens, three passes."""
    dm = ref.layer_shapes(published())
    macs = flops_granite.macs_per_token(dm, 8192)
    assert 6 * macs[part] * 8192 / 1e12 == pytest.approx(tflop, abs=0.05)


def test_flops_against_hand_counts():
    dm = ref.layer_shapes(published())
    m = flops_granite.macs_per_token(dm, 8192)
    assert m["mlp"] == 10 * 3 * 2048 * 8192
    assert m["ssm_proj"] == 9 * (2048 * 8512 + 4096 * 2048)
    assert m["ssm_conv"] == 9 * 4 * 4352
    assert m["scan"] == 9 * 3 * 64 * 64 * 128
    assert m["attn_core"] == 2 * 32 * 64 * 4096
    assert m["head"] == 2048 * 25088
    assert flops_granite.train_flops_per_sequence(dm, 8192) \
        == pytest.approx(40.7e12, rel=2e-3)
    assert flops_granite.scan_train_flops(dm, 8192) \
        == pytest.approx(6 * m["scan"] * 8192)
    # x, z, y (3 x 4,096) and B, C (2 x 128) in bf16, dt (64) in float32.
    assert flops_granite.scan_train_bytes(dm, 1) \
        == 3 * 9 * ((3 * 4096 + 2 * 128) * 2 + 64 * 4)


def _catalog_config():
    """The source's numbers as the catalog beside the model-configs guide
    holds them (config.json of granite-4.0-h-micro)."""
    mamba = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    return {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": mamba * 4, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}


def test_config_file_holds_the_published_widths():
    """Every key of the source's ``config.json`` stands in the file
    unchanged but those it lists as ``reduced``, whose published values
    stand beside them; the benchmark's entries agree."""
    config, source = published(), _catalog_config()
    reduced = {"num_hidden_layers": 10, "vocab_size": 25088,
               "layer_types": source["layer_types"][:10]}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in source.items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config["published"][key] == value
    assert config["vocab_held"] == [0, 25088] and 25088 * 4 == 100352
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = {c["name"]: c for c in spec["configs"]}["granite4_h_micro_stage0"]
    assert entry["file"] == "benchmark/configs/granite4_h_micro_stage0.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(reduced)
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite4_h_micro_stage0", "tok_stream_s8192_b2", 1)
    listed = {m["name"] for m in spec["per_layer"]
              if m.get("workloads") == [CELL]}
    assert listed == {"scan_one_group_roofline_pct", "ssm_conv_device_pct",
                      "attn64_core_device_pct"}
    assert set(config["scopes"]) == {
        "ssm_proj", "ssm_conv", "ssm_scan", "attn_proj", "attn_core", "mlp",
        "lm_head", "update"}


def test_the_step_program_names_every_scope():
    """Each scope the configuration lists shows in the lowered step."""
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train.step import init_train_state, make_train_step
    config = tiny()
    model = get_model("granite_hybrid", config)
    step = make_train_step(model, SGDConfig(), lambda s: 0.1, make_mesh(1),
                           compute_dtype=jnp.bfloat16)
    state = jax.eval_shape(
        lambda: init_train_state(*model.init(jax.random.key(0))))
    b = {"image": jax.ShapeDtypeStruct((2, 64), jnp.int32),
         "label": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    text = step.lower(state, b, jax.random.key(0)).as_text(debug_info=True)
    for scope in config["scopes"]:
        assert re.search(rf"[/(]{scope}[/)]", text), scope


def test_every_norm_reaches_its_products_written_out_in_bf16():
    """Each layer's two norms and the head's reach their products as a
    bf16 array behind an optimisation barrier, so XLA cannot fuse the
    norm of the float32 stream into a product's operand: that cost the
    chip 15% of the step (PERF.md, section 6)."""
    config = tiny()
    init, apply, _ = sysm.build(config)
    params, state = jax.eval_shape(lambda: init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, x: apply(
        p, state, x, train=True, compute_dtype=jnp.bfloat16)[0])(params, ids))
    written = re.findall(r":(\w+\[[\d,]+\]) = optimization_barrier", text)
    d = config["hidden_size"]
    assert written == [f"bf16[2,64,{d}]"] * (
        2 * config["num_hidden_layers"] + 1), written


# -- (f) the benchmark's cell, rehearsed -------------------------------------------

def test_the_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         CELL, "--seed", "2147483999", "--seconds", "1", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    detail = json.loads([ln for ln in proc.stderr.splitlines()
                         if ln.startswith("benchmark-detail: ")][-1]
                        .split(": ", 1)[1])
    assert all(detail["checks"].values()), (detail["checks"],
                                            detail["reference_check"])
    assert "none_dropped" not in detail["checks"]
    assert set(detail["reference_check"]["errors"]) == set(ref.TOLERANCE)
    assert detail["routing"] == {}


@pytest.mark.parametrize("name,scope", [
    ("scan_one_group_roofline_pct", "ssm_scan"),
    ("ssm_conv_device_pct", "ssm_conv"),
    ("attn64_core_device_pct", "attn_core")])
def test_the_readers_read_the_scopes_and_nothing_without_them(name, scope):
    """Each new reader: the scope's share of its roofline or of busy time
    from a trace's scope seconds, and None where the program named no
    scope (the parent's program, a trace without scopes)."""
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    dm = ref.layer_shapes(published())
    ctx = {"layers": dm, "seq_len": 8192, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"samples": 16, "busy_s": 10.0,
                     "scope_s": {scope: 2.0, "-": 1.0}}}
    got = reader.read(ctx)
    if "roofline" in name:
        tokens = 16 * 8192
        least = max(flops_granite.scan_train_flops(dm, tokens) / 197e12,
                    flops_granite.scan_train_bytes(dm, tokens) / 819e9)
        assert got == pytest.approx(100.0 * least / 2.0)
        assert 0 < got < 100
    else:
        assert got == pytest.approx(20.0)
    assert reader.read(dict(ctx, trace={"samples": 16, "busy_s": 10.0,
                                        "scope_s": {"-": 3.0}})) is None
    assert reader.read(dict(ctx, trace=None)) is None
