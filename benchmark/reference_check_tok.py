"""``correct`` (a) for a token-trained cell whose model may predict at
more than one depth: ``reference_check_lm.py``'s method (the reference
first, a sequence at a time, before the Trainer's state is on the chip;
then one step of the Trainer's own program on the cell's first batch at
the schedule's peak, the state put back as it was), with two things read
from the configuration's reference module instead of written here:

- the limits are the module's ``TOLERANCE``, every one of them;
- the leaves whose momentum is held one by one are the module's
  ``THIN_LEAVES`` (``momentum_rel_thin``: in a model without a scan the
  leaves that separate one precision from the next are the thin ones).
  The measure is the root mean square of those leaves' own relative
  errors, a leaf a vote whatever its size: the worst single leaf of two
  dozen swings with the seed by more than the precisions lie apart, and
  the mean does not (PERF.md, findings of PR 35).

Every prediction depth's first-sequence logits are compared: the
reference returns them stacked ``[D,T,V]``; the system's come from the
forward pass that the step differentiates (``model.apply(...,
train=True)``), which yields plain logits or a
``ddp_tpu.ops.losses.DepthLogits``.  ``logits_rel`` is depth 0's,
``logits_rel_d<k>`` depth ``k``'s: all positions as one vector.
``logits_rel_median`` (``_d<k>``) is the median over the sequence's
positions of a position's own relative error: a token whose expert
changed because bf16 operands moved a score across the top-k boundary is
far off in both precisions and decides the vector's error in some seeds;
it does not move the median, which reads what the arithmetic of the
unmoved tokens costs.
"""
from __future__ import annotations

import numpy as np

from .reference_check_lm import (_leaves, _rel_l2, _whole_rel,
                                 reference_side)

__all__ = ["compare", "reference_side", "system_side"]


def _by_position(a, b) -> np.ndarray:
    """The relative L2 error of each position's logits, ``[T]``."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    num = np.sqrt(np.einsum("tv,tv->t", a - b, a - b, dtype=np.float64))
    return num / np.maximum(np.sqrt(np.einsum("tv,tv->t", b, b,
                                              dtype=np.float64)), 1e-30)


def compare(ref: dict, *, loss: float, logits0, momentum, update, lr: float,
            tolerance: dict, thin: tuple, leaves: bool = False) -> dict:
    """The errors of a system-side reading against the reference's, each
    beside its limit.  The first step starts from zero momentum and no
    decay: the buffer is the gradient and the update ``-lr`` times it."""
    by_leaf = {k: _rel_l2(a, b) for (k, a), (_, b) in zip(
        _leaves(momentum), _leaves(ref["grads"]))}
    ends = tuple(f"['{n}']" for n in thin)
    thin_errs = np.asarray([v for k, v in by_leaf.items()
                            if k.endswith(ends)])
    logits0, ref_logits = np.asarray(logits0), np.asarray(ref["logits0"])
    if ref_logits.ndim == 2:  # a reference with one depth and no axis for it
        ref_logits = ref_logits[None]
    errs = {"loss_abs": abs(loss - ref["loss"])}
    quantiles = {}
    for k in range(ref_logits.shape[0]):
        tag = f"_d{k}" if k else ""
        by_position = _by_position(logits0[k], ref_logits[k])
        errs["logits_rel" + tag] = _rel_l2(logits0[k], ref_logits[k])
        errs["logits_rel_median" + tag] = float(np.median(by_position))
        quantiles["logits_rel_by_position" + tag] = [
            float(np.quantile(by_position, q)) for q in (0.1, 0.5, 0.9,
                                                         0.99, 1.0)]
    errs.update({
        "momentum_rel": _whole_rel(momentum, ref["grads"]),
        "momentum_rel_thin": float(np.sqrt(np.mean(np.square(thin_errs)))),
        "momentum_rel_worst": max(by_leaf.values()),
        "update_rel": _whole_rel(update, ref["grads"], scale_b=-lr),
    })
    worst = max(by_leaf, key=by_leaf.get)
    thin_worst = max((k for k in by_leaf if k.endswith(ends)),
                     key=by_leaf.get)
    ok = all(np.isfinite(v) and v <= tolerance[k] for k, v in errs.items())
    info = {**quantiles,
            "momentum_rel_median_leaf": float(np.median(list(
                by_leaf.values()))),
            "momentum_rel_worst_leaf": [worst, by_leaf[worst]],
            "momentum_rel_thin_leaf": [thin_worst, by_leaf[thin_worst]],
            "logits_max_abs": float(np.max(np.abs(logits0 - ref_logits)))}
    if leaves:
        info["momentum_rel_by_leaf"] = by_leaf
    return {"ok": bool(ok), "errors": errs,
            "tolerance": {k: tolerance[k] for k in errs}, "info": info}


def system_side(*, trainer, model, batch: dict, check_step: int, lr: float,
                compute_dtype, ref: dict, tolerance: dict, thin: tuple,
                leaves: bool = False) -> dict:
    """One step of ``trainer.train_step`` on ``batch`` from the Trainer's
    own (fresh) state at ``check_step``, compared with ``ref``; the
    Trainer's state is put back as it was (fresh buffers: the step
    donates the old ones)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ddp_tpu.ops.losses import DepthLogits
    from ddp_tpu.train.step import TrainState, shard_batch

    mesh = trainer.mesh
    params0, stats0 = jax.device_get((trainer.state.params,
                                      trainer.state.batch_stats))
    opt0 = trainer.state.opt_state._replace(
        momentum_buf=jax.tree_util.tree_map(np.zeros_like, params0))
    # Every leaf goes on (and back) with the sharding that the step's
    # results carry, so this call, the loop's first and every later one
    # are one program prepared once (reference_check_lm.py says why).
    placed = jax.tree_util.tree_map(
        lambda _x: NamedSharding(mesh, PartitionSpec()), trainer.state)

    def fresh(step: int):
        return jax.device_put(TrainState(
            params0, stats0, opt0, np.asarray(step, np.int32)), placed)

    def first_sequence_logits(p, s, x):
        out = model.apply(p, s, x, train=True,
                          compute_dtype=compute_dtype)[0]
        if isinstance(out, DepthLogits):
            return jnp.stack([out.logits(k)[0]
                              for k in range(len(out.hidden))])
        return out[0][None]

    trainer.state = None  # its gigabytes go before their copy comes
    state = fresh(check_step)
    logits0 = np.asarray(jax.jit(first_sequence_logits)(
        state.params, state.batch_stats, jnp.asarray(batch["image"])),
        np.float32)
    new, loss = trainer.train_step(state, shard_batch(batch, mesh),
                                   trainer.rng)
    loss = float(loss)
    new_params, momentum = jax.device_get(
        (new.params, new.opt_state.momentum_buf))
    del new, state
    trainer.state = fresh(0)
    update = jax.tree_util.tree_map(lambda a, b: a - b, new_params, params0)
    out = compare(ref, loss=loss, logits0=logits0, momentum=momentum,
                  update=update, lr=lr, tolerance=tolerance, thin=thin,
                  leaves=leaves)
    out.update(step=check_step, lr=lr, loss=loss,
               reference_loss=ref["loss"],
               batch=list(np.asarray(batch["image"]).shape))
    return out
