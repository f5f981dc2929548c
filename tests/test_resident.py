"""Device-resident scan-per-epoch path vs the streaming per-step path.

The two execution strategies share the per-batch math
(train/step.py: make_loss_and_grads under make_group_step), so on identical weights and data order
they must agree — the same golden-reference discipline the reference's two
scripts embody (singlegpu.py as the numerics fixture for multigpu.py,
SURVEY.md §4).

Tolerances: the first few steps agree bitwise; beyond that the two XLA
programs' fusion-order ULP differences amplify through the chaotic training
dynamics (measured: bit-equal for 3 steps at lr 0.1, then divergence), so
parity is asserted over a SHORT horizon at low lr.  Meshes are kept at 2
devices: compiling the scanned VGG epoch for an 8-device CPU mesh takes
tens of minutes (CPU-backend artifact; the real-TPU compile is ~15 s).
"""
import functools
import math
import re

import jax
import numpy as np
import pytest

from ddp_tpu.data import EvalLoader, ResidentData, TrainLoader, synthetic
from ddp_tpu.data.cifar10 import Dataset
from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel import make_mesh
from ddp_tpu.train import Trainer, evaluate
from ddp_tpu.train.evaluate import evaluate_resident


def _train(resident, *, n_train, batch, replicas, epochs=1,
           device_augment=False, model_name="vgg", seed=3, lr=0.02,
           grad_accum=1, shard_update=False):
    train_ds, _ = synthetic(n_train=n_train, n_test=16)
    mesh = make_mesh(replicas)
    model = get_model(model_name)
    params, stats = model.init(jax.random.key(seed))
    loader = TrainLoader(train_ds, batch, replicas, seed=seed,
                         augment=False)
    sched = functools.partial(triangular_lr, base_lr=lr, num_epochs=epochs,
                              steps_per_epoch=len(loader))
    tr = Trainer(model, loader, params, stats, mesh=mesh, lr_schedule=sched,
                 sgd_config=SGDConfig(lr=lr), save_every=10**9,
                 snapshot_path=None, seed=seed,
                 device_augment=device_augment, resident=resident,
                 grad_accum=grad_accum, shard_update=shard_update)
    tr.train(epochs)
    return tr


def _assert_same_training(a, b):
    # The first steps must agree to float noise — any semantic difference
    # (wrong indices, different augmentation RNG, BN over the wrong axis)
    # shows up here as a wholesale change, not a 1e-7.
    np.testing.assert_allclose(a.loss_history[:2], b.loss_history[:2],
                               rtol=0, atol=1e-6)
    # Later steps: fusion-order ULP drift between the two XLA programs
    # amplifies through the training dynamics (measured ~1e-5 by step 4
    # at lr 0.02); the loose bound still rules out any real divergence.
    np.testing.assert_allclose(a.loss_history, b.loss_history,
                               rtol=2e-3, atol=2e-3)
    fa = jax.tree_util.tree_leaves(a.state.params)
    fb = jax.tree_util.tree_leaves(b.state.params)
    for la, lb in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-3, atol=2e-3)
    assert int(a.state.step) == int(b.state.step)


# 88 samples / 2 replicas = 44/shard, batch 8 -> 5 full batches + tail of
# 4: 6 optimizer steps; under A=2 the groups are [2],[2],[1 remainder],
# [tail] = 4.  DeepNN: the grouping and the sharded update are
# model-independent, and the VGG representative (BN-stat threading through
# the scan) is the first case.
_RAGGED = dict(n_train=88, batch=8, replicas=2, model_name="deepnn")


@pytest.mark.parametrize("accum,shard_update,kw,steps", [
    (False, False, dict(n_train=64, batch=8, replicas=2), 4),
    (True, False, _RAGGED, 4),
    (False, True, _RAGGED, 6),
    (True, True, _RAGGED, 4),
], ids=["plain", "accum", "shard_update", "accum+shard_update"])
def test_resident_matches_streaming(accum, shard_update, kw, steps):
    """``make_train_epoch`` == ``make_train_step`` under the same
    ``(accum, shard_update)``, through the Trainer on a 2-way mesh
    (augment off): the scan-epoch reproduces the per-step loop — under
    ``--grad_accum`` full groups of A, the remainder group, and the ragged
    tail as its own optimizer step; under ``--shard_update`` with the
    momentum sharded on both sides."""
    kw = dict(kw, grad_accum=2 if accum else 1, shard_update=shard_update)
    a, b = _train(False, **kw), _train(True, **kw)
    assert len(a.loss_history) == steps
    _assert_same_training(a, b)
    if shard_update:
        # No BN and a short horizon: every loss agrees bit for bit.  The
        # parameters after the LAST update do not quite (measured: 2 of
        # 3456 elements of one kernel, 1 ULP — the scan's and the step's
        # fusion order), so they keep _assert_same_training's bound.
        np.testing.assert_array_equal(a.loss_history, b.loss_history)
        for tr in (a, b):  # momentum stays flat and sharded on both sides
            buf = tr.state.opt_state.momentum_buf
            assert buf.ndim == 1
            assert {s.data.shape[0] for s in buf.addressable_shards} == {
                buf.shape[0] // 2}
        np.testing.assert_allclose(
            np.asarray(a.state.opt_state.momentum_buf),
            np.asarray(b.state.opt_state.momentum_buf), rtol=2e-3, atol=2e-3)


def test_resident_matches_streaming_device_augment():
    """Both paths fold the same augmentation RNG per step: the per-step
    random_crop_flip and the resident fused gather_crop_flip must agree.
    DeepNN: the augmentation plumbing is model-independent; the VGG
    resident-vs-streaming representative (with BN-stat threading) is
    test_resident_matches_streaming above."""
    kw = dict(n_train=64, batch=8, replicas=2, device_augment=True,
              model_name="deepnn")
    _assert_same_training(_train(False, **kw), _train(True, **kw))


def test_resident_ragged_tail():
    """Shard size not divisible by batch: the tail batch runs at its true
    shape in both paths (singlegpu.py:179 drop_last=False semantics).
    DeepNN: ragged-shape mechanics are model-independent and its CPU-mesh
    compile is ~10x cheaper than VGG's (which the two tests above cover)."""
    # 2 replicas x 36/2=18 per shard, batch 8 -> 2 full steps + tail of 2.
    kw = dict(n_train=36, batch=8, replicas=2, model_name="deepnn")
    a, b = _train(False, **kw), _train(True, **kw)
    assert len(a.loss_history) == 3  # 2 full + 1 tail
    _assert_same_training(a, b)


def test_resident_single_replica_ragged():
    """Mesh of 1 with the plain shuffle sampler (singlegpu.py path)."""
    kw = dict(n_train=40, batch=16, replicas=1, model_name="deepnn")
    a, b = _train(False, **kw), _train(True, **kw)
    assert len(a.loss_history) == 3  # 2 full + tail of 8
    _assert_same_training(a, b)


@pytest.mark.extended  # resident x accum x augment; default reprs: test_resident_matches_streaming_device_augment + test_zero_resident_accum_all_composed
def test_resident_grad_accum_device_augment():
    """The composed path folds the same per-micro augmentation RNG as the
    streaming accumulation step."""
    kw = dict(n_train=64, batch=8, replicas=2, model_name="deepnn",
              grad_accum=2, device_augment=True)
    a, b = _train(False, **kw), _train(True, **kw)
    assert len(a.loss_history) == 2
    _assert_same_training(a, b)


def test_epoch_index_matrix_matches_materialize():
    """Row k of the index matrix gathers exactly materialize(k)'s rows —
    host-level check, full 8-way sharding, both sampler kinds."""
    # 468: ragged under both samplers (8-way: 59/shard -> 7x8 + tail 3;
    # 1-way: 58x8 + tail 4).
    train_ds, _ = synthetic(n_train=468, n_test=16)
    for replicas in (8, 1):
        loader = TrainLoader(train_ds, 8, replicas, seed=5, augment=False)
        loader.set_epoch(1)
        full, tail = loader.epoch_index_matrix()
        for k in range(full.shape[0]):
            np.testing.assert_array_equal(train_ds.images[full[k]],
                                          loader.materialize(k)["image"])
        last = loader.materialize(full.shape[0])
        assert tail is not None
        np.testing.assert_array_equal(train_ds.images[tail], last["image"])
        np.testing.assert_array_equal(train_ds.labels[tail], last["label"])


def test_evaluate_resident_matches_streaming():
    """One-scan resident eval == batched streaming eval, ragged test set."""
    _, test_ds = synthetic(n_train=16, n_test=84)
    mesh = make_mesh(2)
    model = get_model("vgg")
    params, stats = model.init(jax.random.key(0))
    loader = EvalLoader(test_ds, 16, 2)  # 84 = 2 full global batches + 20
    acc_stream = evaluate(model, params, stats, loader, mesh,
                          progress=False)
    acc_res = evaluate_resident(model, params, stats,
                                ResidentData(test_ds, mesh), loader, mesh)
    assert abs(acc_stream - acc_res) < 1e-4, (acc_stream, acc_res)


@pytest.mark.parametrize("row_shape,stored", [
    ((32, 32, 3), (24, 128)),  # 3,072 elements: the gather's lane layout
    ((5, 5, 3), (5, 5, 3)),    # 75: not a multiple of 128, uploaded as is
])
def test_resident_data_is_stored_in_the_gathers_layout(row_shape, stored):
    """``ResidentData`` uploads ``[N, D/128, 128]`` where D % 128 == 0 and
    the rows as they are where it is not, replicated, with the row shape
    beside the array; ``gather_rows`` gives ``dataset.images[idx]`` bit for
    bit from either."""
    from ddp_tpu.ops.gather import RowTable, gather_rows

    rng = np.random.default_rng(7)
    ds = Dataset(rng.integers(0, 256, (44,) + row_shape, dtype=np.uint8),
                 rng.integers(0, 10, 44).astype(np.int64))
    mesh = make_mesh(2)
    res = ResidentData(ds, mesh)
    assert isinstance(res.images, RowTable)
    assert res.images.row_shape == row_shape
    assert res.images.data.shape == (44,) + stored
    assert res.images.data.dtype == np.uint8
    assert res.images.data.sharding.is_fully_replicated
    assert res.labels.shape == (44,) and res.labels.dtype == np.int32
    idx = rng.integers(0, 44, 16).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(gather_rows)(res.images, idx)), ds.images[idx])


_TENSOR = re.compile(r"tensor<(\d+(?:x\d+)+)x\w+>")


def _table_results(mlir_text, rows, row_elems):
    """The operations of a lowered module whose result is a whole copy of
    a ``rows``-row table (``ops.gather.whole_table_writes``' rule, on
    StableHLO text: result types follow the last ``->``, or the last
    `` : `` of an operation that prints one type; an operation that opens
    a region prints them on the line that closes it)."""
    found = []
    for line in mlir_text.splitlines():
        opens, closes = line.endswith("{"), line.lstrip().startswith("}")
        if opens or not (" = " in line or closes):
            continue
        types = (line.rsplit("->", 1) if "->" in line
                 else line.rsplit(" : ", 1))[-1]
        for dims in _TENSOR.findall(types):
            dims = [int(d) for d in dims.split("x")]
            if dims[0] == rows and math.prod(dims[1:]) >= row_elems:
                found.append(line.strip()[:160])
    return found


def test_table_results_rule():
    text = ("%1 = stablehlo.reshape %0 : (tensor<52x32x32x3xui8>) -> "
            "tensor<52x3072xui8>\n"
            "%2 = stablehlo.custom_call @tpu_custom_call(%i, %arg1) : "
            "(tensor<8xi32>, tensor<52x24x128xui8>) -> tensor<8x24x128xui8>\n"
            "%3 = stablehlo.add %a, %b : tensor<52x24x128xui8>\n"
            "%4 = stablehlo.gather(%l, %i) : (tensor<52xi32>) -> "
            "tensor<8xi32>\n"
            "%5:2 = sdy.manual_computation(%arg0) manual_axes={\"data\"} "
            "(%arg9: tensor<52x24x128xui8>) {\n"
            "} : (tensor<52x24x128xui8>) -> (tensor<4xf32>, "
            "tensor<52x3072xui8>)")
    found = _table_results(text, 52, 3072)
    assert [f[:2] for f in found] == ["%1", "%3", "} "]


@pytest.mark.parametrize("program", ["train", "train_augment", "eval"])
def test_resident_programs_never_write_the_table(program, monkeypatch):
    """Lowered for the TPU (the Pallas branch, no chip needed: nothing is
    compiled), a resident train epoch and the evaluation scan hold the
    Mosaic kernel and NO operation whose result is the whole table: the
    relayout that a table stored as ``[N,32,32,3]`` paid on every step
    (PERF.md section 6, PR 27) cannot come back unseen."""
    from ddp_tpu.ops import gather as gather_mod
    from ddp_tpu.train.epoch import make_eval_epoch, put_index_matrix

    monkeypatch.setattr(gather_mod, "_use_pallas", lambda: True)
    rows = 52  # no batch, width or step count of these programs is 52
    ds, _ = synthetic(n_train=rows, n_test=8, seed=4)
    mesh = make_mesh(2)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(2))
    if program == "eval":
        loader = EvalLoader(ds, 8, 2)
        res = ResidentData(ds, mesh)
        idx, mask = loader.epoch_index_matrix()
        fn = make_eval_epoch(model, mesh)
        args = (params, stats, res.images, res.labels,
                put_index_matrix(idx, mesh), put_index_matrix(mask, mesh))
    else:
        loader = TrainLoader(ds, 8, 2, seed=2, augment=False)
        tr = Trainer(model, loader, params, stats, mesh=mesh,
                     lr_schedule=lambda step: 0.02,
                     sgd_config=SGDConfig(lr=0.02), save_every=10**9,
                     snapshot_path=None, seed=2, resident=True,
                     device_augment=program == "train_augment")
        full, _tail = loader.epoch_index_matrix()
        fn = tr.train_epoch
        args = (tr.state, tr.resident.images, tr.resident.labels,
                put_index_matrix(full, mesh), tr.rng)
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f"tensor<{rows}x24x128xui8>" in text  # the table is an operand
    assert _table_results(text, rows, 32 * 32 * 3) == []


def test_resident_cli_end_to_end(tmp_path, capsys, monkeypatch):
    """The --resident flag through the real CLI: same report surface."""
    from ddp_tpu import cli
    monkeypatch.chdir(tmp_path)
    parser = cli.build_parser("test")
    # deepnn: the CLI mechanics under test are model-independent, and its
    # CPU-mesh compile is ~10x cheaper than VGG's.
    args = parser.parse_args(
        ["1", "1", "--batch_size", "8", "--synthetic", "--resident",
         "--model", "deepnn",
         "--lr", "0.05", "--num_devices", "2", "--synthetic_size", "64"])
    acc = cli.run(args, num_devices=None)
    out = capsys.readouterr().out
    assert "[GPU0] Epoch 0 | Batchsize: 8 | Steps:" in out
    assert "fp32 model has accuracy=" in out
    assert (tmp_path / "checkpoint.pt").exists()
    assert 0.0 <= acc <= 100.0
