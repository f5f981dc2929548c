"""The generative-LM training path — next-token CE over ``[B, T]`` token
batches, same SPMD skeleton as the classifier step (train/step.py).

The tinylm model (models/transformer.py:lm_apply) is the decoder twin of
the CIFAR transformer encoder: identical block stack, identical TP_RECIPE,
so the attention collective arithmetic the auditor prices for the encoder
(qkv column / out row, fc1 column / fc2 row) holds verbatim here.  The
step builders mirror :func:`~ddp_tpu.train.step.make_train_step`'s two
gradient cores exactly:

- 1-D / trivial plan: differentiate the GLOBAL-mean loss
  ``psum(ce_sum)/psum(count)`` — shard_map's autodiff inserts the
  ``data`` gradient psum itself (vma semantics, as step.py documents);
- 2-D tp plan: differentiate the collective-free LOCAL objective
  ``ce_sum/(count*d)`` (the zero-style core — the tp forward's row psums
  carry identity transposes, parallel/tp/layers.py), then explicitly
  ``psum`` grads over ``data`` only.

Next-token shift: ``tokens[:, :-1]`` predicts ``tokens[:, 1:]``; every
position is a valid target (fixed-length synthetic sequences), so the
count is just ``B*(T-1)`` per shard — kept as a traced count anyway so a
masked/ragged corpus later changes nothing structurally.

The synthetic corpus is DETERMINISTIC and learnable: an affine next-token
map ``t+1 = (a*t + c) mod V`` from a seeded start token, so the
next-token distribution is a delta the model can drive CE toward zero on
— loss descent is a real training signal, not noise, and every run/test
reproduces bit-identically from the seed.

CLI:  python -m ddp_tpu.train.lm --steps 30 --mesh_shape 2,4 \
          --snapshot_path runs/lm/ckpt.npz
writes the checkpoint through the SAME save_checkpoint + lineage.commit
path the classifier trainer uses, so the serve engine's
``latest_verifiable`` walk restores it unchanged (a (d,m)-trained LM
checkpoint serves on a 1-D mesh via ckpt_shard.load_for_mesh).
"""
from __future__ import annotations

import argparse
import functools
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..optim import sgd as sgd_lib
from ..ops.losses import cross_entropy_sum_count
from ..parallel.mesh import (DATA_AXIS, MODEL_AXIS, data_axis_size,
                             make_mesh, replicated_sharding)
from ..utils.platform import device_line, enable_compile_cache
from .step import TrainState, init_train_state


def make_lm_loss_and_grads(model, compute_dtype=None):
    """Replicated-params gradient core for token batches:
    ``fn(params, batch_stats, tokens, rng) -> (loss, stats, grads)`` —
    the LM twin of :func:`~ddp_tpu.train.step.make_loss_and_grads` (same
    gradient-collective contract)."""

    def loss_and_grads(params, batch_stats, tokens, rng):
        def loss_fn(params):
            logits, new_stats = model.apply(
                params, batch_stats, tokens[:, :-1], train=True, rng=rng,
                compute_dtype=compute_dtype)
            ce_sum, count = cross_entropy_sum_count(
                logits.reshape(-1, logits.shape[-1]),
                tokens[:, 1:].reshape(-1))
            loss = (lax.psum(ce_sum, DATA_AXIS)
                    / lax.psum(count, DATA_AXIS))
            return loss, new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, new_stats, grads

    return loss_and_grads


def make_lm_loss_and_grads_tp(model, data_size: int, compute_dtype=None,
                              tp_recipe=None):
    """Tensor-parallel gradient core: differentiate the collective-free
    LOCAL objective ``ce_sum/(count*d)`` with the ``tp_axis`` forward
    (row psums carry identity transposes), then explicitly psum grads
    over ``data`` only — byte-for-byte the contract of
    :func:`~ddp_tpu.train.step.make_loss_and_grads_tp`."""

    def loss_and_grads(params, batch_stats, tokens, rng):
        def local_loss_fn(params):
            logits, new_stats = model.apply(
                params, batch_stats, tokens[:, :-1], train=True, rng=rng,
                compute_dtype=compute_dtype, tp_axis=MODEL_AXIS,
                **({} if tp_recipe is None else {"tp_recipe": tp_recipe}))
            ce_sum, count = cross_entropy_sum_count(
                logits.reshape(-1, logits.shape[-1]),
                tokens[:, 1:].reshape(-1))
            return ce_sum / (count * data_size), (new_stats, ce_sum, count)

        grads, (new_stats, ce_sum, count) = jax.grad(
            local_loss_fn, has_aux=True)(params)
        loss = lax.psum(ce_sum, DATA_AXIS) / lax.psum(count, DATA_AXIS)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, DATA_AXIS), grads)
        return loss, new_stats, grads

    return loss_and_grads


def make_lm_train_step(model, sgd_config: sgd_lib.SGDConfig,
                       lr_schedule: Callable[[jax.Array], jax.Array],
                       mesh: Mesh, compute_dtype=None, plan=None):
    """The jitted SPMD LM train step: ``step_fn(state, tokens, rng) ->
    (state, loss)`` with ``tokens`` ``i32[B, T]`` sharded on ``data``
    (replicated over ``model``), B divisible by the data-axis size.

    ``plan`` (a 2-D :class:`~ddp_tpu.parallel.tp.plan.TPPlan`) runs the
    tensor-parallel variant with the state sharded per the plan's specs;
    the state must be ``device_put`` onto ``state_shardings(plan, mesh)``.
    Same donation/out-sharding wiring as the classifier step so the
    auditor's donation and collective invariants apply unchanged.
    """
    from ..parallel.tp.plan import (is_trivial, recipe_override,
                                    state_shardings, state_specs)
    if plan is None or is_trivial(plan):
        core = make_lm_loss_and_grads(model, compute_dtype=compute_dtype)
        st_specs, st_sh, extra = P(), replicated_sharding(mesh), {}
    else:
        core = make_lm_loss_and_grads_tp(
            model, data_axis_size(mesh), compute_dtype=compute_dtype,
            tp_recipe=recipe_override(plan))
        st_specs, st_sh, extra = (state_specs(plan),
                                  state_shardings(plan, mesh),
                                  {"check_vma": False})

    def _shard_body(state: TrainState, tokens, rng):
        rng = jax.random.fold_in(rng, state.step)
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        loss, new_stats, grads = core(state.params, state.batch_stats,
                                      tokens, rng)
        lr_t = lr_schedule(state.step)
        params, opt_state = sgd_lib.apply_updates(
            state.params, grads, state.opt_state, lr_t, sgd_config)
        return (TrainState(params, new_stats, opt_state, state.step + 1),
                loss)

    mapped = jax.shard_map(
        _shard_body, mesh=mesh,
        in_specs=(st_specs, P(DATA_AXIS), P()),
        out_specs=(st_specs, P()),
        **extra,
    )
    return jax.jit(mapped, donate_argnums=(0,),
                   out_shardings=(st_sh, replicated_sharding(mesh)))


# -- deterministic synthetic corpus ---------------------------------------

CORPUS_A = 31          # multiplier of the affine next-token map
CORPUS_C = 7           # increment; gcd checks below keep the map a bijection


def synthetic_tokens(n_seqs: int, seq_len: int, *, vocab: int,
                     seed: int = 0) -> np.ndarray:
    """``i32[n_seqs, seq_len]`` of affine sequences ``t_{k+1} = (31*t_k +
    7) mod vocab`` from seeded uniform start tokens — deterministic in
    ``seed``, and exactly learnable (next token is a function of the
    current token alone), so CE descent measures real optimisation."""
    rng = np.random.RandomState(seed)
    toks = np.zeros((n_seqs, seq_len), np.int32)
    toks[:, 0] = rng.randint(0, vocab, size=n_seqs)
    for k in range(1, seq_len):
        toks[:, k] = (CORPUS_A * toks[:, k - 1] + CORPUS_C) % vocab
    return toks


def train_lm(*, steps: int, batch: int, seq_len: int, mesh: Mesh,
             lr: float = 0.1, seed: int = 0, compute_dtype=None,
             plan=None, snapshot_path: Optional[str] = None,
             log_every: int = 10, quiet: bool = False):
    """Run the whole tiny-LM training loop; returns ``(state, losses)``
    with ``state`` fetched back to host layout and ``losses`` the per-step
    float list.  ``snapshot_path`` writes the final state through
    save_checkpoint + CheckpointLineage.commit (the serve-loadable
    format)."""
    from ..models import get_model
    from ..models import transformer as tfm

    model = get_model("tinylm")
    if seq_len > tfm.T_MAX:
        raise ValueError(f"seq_len {seq_len} exceeds T_MAX {tfm.T_MAX}")
    d = data_axis_size(mesh)
    if batch % d:
        raise ValueError(f"batch {batch} not divisible by data axis {d}")

    params, batch_stats = model.init(jax.random.PRNGKey(seed))
    state = init_train_state(params, batch_stats)
    if plan is not None:
        from ..parallel.tp.plan import state_shardings
        state = jax.device_put(state, state_shardings(plan, mesh))
    else:
        state = jax.device_put(state, replicated_sharding(mesh))

    step_fn = make_lm_train_step(
        model, sgd_lib.SGDConfig(lr=lr, momentum=0.9, weight_decay=0.0),
        lambda s: jnp.asarray(lr, jnp.float32), mesh,
        compute_dtype=compute_dtype, plan=plan)

    corpus = synthetic_tokens(max(batch * 8, batch), seq_len,
                              vocab=tfm.VOCAB, seed=seed)
    rng = jax.random.PRNGKey(seed + 1)
    losses = []
    for i in range(steps):
        lo = (i * batch) % corpus.shape[0]
        tokens = jnp.asarray(corpus[lo:lo + batch])
        state, loss = step_fn(state, tokens, rng)
        losses.append(float(loss))
        if not quiet and (i % log_every == 0 or i == steps - 1):
            print(f"[lm] step {i:4d}  loss {losses[-1]:.4f}", flush=True)

    state = jax.device_get(state)
    if snapshot_path:
        from ..resilience.lineage import CheckpointLineage
        from .checkpoint import save_checkpoint
        os.makedirs(os.path.dirname(snapshot_path) or ".", exist_ok=True)
        sha = save_checkpoint(snapshot_path, state.params,
                              state.batch_stats, state.opt_state,
                              int(state.step), 0)
        CheckpointLineage(snapshot_path).commit(
            epoch=0, step=int(state.step), sha256=sha)
        if not quiet:
            print(f"[lm] wrote {snapshot_path} (sha256 {sha[:12]}...)",
                  flush=True)
    return state, losses


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m ddp_tpu.train.lm",
        description="Train the tiny decoder-only LM (models/transformer.py"
                    ":lm_apply) on the deterministic synthetic corpus.")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq_len", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh_shape", type=str, default=None,
                   help="D or D,M — 2-D runs tensor-parallel attention "
                        "per the transformer TP_RECIPE")
    p.add_argument("--num_devices", type=int, default=None)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--snapshot_path", type=str, default=None)
    args = p.parse_args(argv)

    enable_compile_cache()
    if args.mesh_shape:
        shape = tuple(int(v) for v in args.mesh_shape.split(","))
        mesh = make_mesh(shape=shape)
    else:
        mesh = make_mesh(args.num_devices)
    print(device_line(mesh), flush=True)

    plan = None
    if len(mesh.axis_names) >= 2 and mesh.shape[MODEL_AXIS] > 1:
        from ..models import get_model
        from ..parallel.tp.plan import format_plan_table, plan_for_model
        model = get_model("tinylm")
        params, _ = model.init(jax.random.PRNGKey(args.seed))
        plan = plan_for_model("tinylm", params,
                              model_size=mesh.shape[MODEL_AXIS])
        print(format_plan_table(plan), flush=True)

    t0 = time.perf_counter()
    _, losses = train_lm(
        steps=args.steps, batch=args.batch, seq_len=args.seq_len,
        mesh=mesh, lr=args.lr, seed=args.seed,
        compute_dtype=jnp.bfloat16 if args.bf16 else None, plan=plan,
        snapshot_path=args.snapshot_path)
    dt = time.perf_counter() - t0
    print(f"[lm] {args.steps} steps in {dt:.1f}s  "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
