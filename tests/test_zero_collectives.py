"""Structural guard for the ZeRO step's collective pattern (VERDICT r2
watchlist: ``check_vma=False`` blankets train/zero.py, so the type system
can no longer catch a refactor that reintroduces shard_map's automatic
gradient psum — which would silently all-reduce AND reduce-scatter, i.e.
double-count by R.  These tests pin the compiled HLO instead: the exact
collective inventory the design promises (zero.py module docstring)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel import make_mesh
from ddp_tpu.train import shard_batch
from ddp_tpu.train.step import TrainState, init_train_state, make_train_step
from ddp_tpu.train.zero import init_opt_shard

# Matches an HLO op DEFINITION of the given kind, tuple-shaped (variadic)
# or not: "%name = f32[123]{0} all-gather(..." / "= (f32[], f32[]) all-reduce(".
# Includes the async "-start" spelling so the guard cannot go blind if a
# future XLA lowers these as all-reduce-start/done pairs (the suite runs on
# the CPU backend — conftest — where today they are synchronous; the "done"
# halves carry no shape of their own, so counts stay 1:1 either way).
def _op_shapes(txt: str, kind: str):
    return re.findall(
        rf"= (\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*) {kind}(?:-start)?\(", txt)


def _compiled_text(step, st, batch):
    return step.lower(st, batch, jax.random.key(0)).compile().as_text()


def _setup(n=2):
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    mesh = make_mesh(n)
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=1,
                              steps_per_epoch=4)
    x = np.zeros((4 * n, 32, 32, 3), np.float32)
    y = np.zeros((4 * n,), np.int32)
    batch = shard_batch({"image": x, "label": y}, mesh)
    return model, params, stats, mesh, sched, batch


def _numel(shape: str) -> int:
    dims = re.findall(r"\[([0-9,]*)\]", shape)
    total = 0
    for d in dims:
        n = 1
        for part in d.split(","):
            if part:
                n *= int(part)
        total += n
    return total


def test_zero_step_collective_inventory():
    """Exactly ONE reduce-scatter (the gradient flat buffer, 1/R-sized
    output) + ONE all-gather (the updated params) + scalar-only
    all-reduces (the loss/count psum).  A param-scale all-reduce here
    means the auto-psum came back and gradients are double-counted."""
    model, params, stats, mesh, sched, batch = _setup(2)
    step = make_train_step(model, SGDConfig(lr=0.1), sched, mesh,
                           shard_update=True)
    st = TrainState(params, stats, init_opt_shard(params, mesh),
                    jnp.zeros((), jnp.int32))
    txt = _compiled_text(step, st, batch)

    rs = _op_shapes(txt, "reduce-scatter")
    ag = _op_shapes(txt, "all-gather")
    ar = _op_shapes(txt, "all-reduce")
    assert len(rs) == 1, rs
    assert len(ag) == 1, ag
    # reduce-scatter output is the 1/R grad shard; all-gather output the
    # full padded param vector = R x the shard.
    assert _numel(ag[0]) == 2 * _numel(rs[0]), (rs, ag)
    # Any all-reduce must be scalar-ish (loss & count psums) — never a
    # parameter/gradient-sized buffer.
    for shape in ar:
        assert _numel(shape) <= 16, (shape, ar)


def test_replicated_step_has_no_scatter_gather():
    """The replicated path's only collectives are all-reduces (DDP
    semantics); its parameter traffic must NOT contain the zero path's
    reduce-scatter/all-gather pair."""
    model, params, stats, mesh, sched, batch = _setup(2)
    step = make_train_step(model, SGDConfig(lr=0.1), sched, mesh)
    txt = _compiled_text(step, init_train_state(params, stats), batch)
    assert not _op_shapes(txt, "reduce-scatter")
    assert not _op_shapes(txt, "all-gather")
