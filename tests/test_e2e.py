"""End-to-end integration: the real CLI path for 2 epochs on a tiny
synthetic dataset — loss decreases, checkpoint lands, accuracy is sane
(the integration tier SURVEY.md §4 prescribes)."""
import functools

import jax
import numpy as np

from ddp_tpu import cli
from ddp_tpu.data import EvalLoader, TrainLoader, synthetic
from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel import make_mesh
from ddp_tpu.train import Trainer, evaluate


def test_cli_end_to_end(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    parser = cli.build_parser("test")
    args = parser.parse_args(
        ["2", "1", "--batch_size", "8", "--synthetic", "--lr", "0.05",
         "--num_devices", "8", "--synthetic_size", "256"])
    acc = cli.run(args, num_devices=None)
    out = capsys.readouterr().out
    # Where it ran, from the shared start-up helper (utils/platform.py).
    assert ('device: platform=cpu device_kind="cpu" visible=8 mesh=data=8 '
            'ids=0,1,2,3,4,5,6,7 native_augment=on\n') in out
    # Reference report lines (multigpu.py:102, 235, 238, 248).
    assert "[GPU0] Epoch 0 | Batchsize: 8 | Steps:" in out
    assert "Total training time:" in out
    assert "fp32 model has size=35.20 MiB" in out
    assert "fp32 model has accuracy=" in out
    assert (tmp_path / "checkpoint.pt").exists()
    assert 0.0 <= acc <= 100.0


def test_training_learns_synthetic_signal():
    """Loss must clearly decrease on the learnable synthetic data.

    DeepNN: the learning-dynamics mechanics under test are
    model-independent and its CPU-mesh compile is ~10x cheaper; the
    flagship VGG's learning is separately evidenced end-to-end by
    test_cli_end_to_end."""
    train_ds, test_ds = synthetic(n_train=512, n_test=256)
    mesh = make_mesh(8)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=8)
    # Triangular schedule as in the reference (singlegpu.py:135-149) at a
    # BN-free-stable peak (DeepNN has no BatchNorm: the reference's 0.4
    # needs BN's scale control and diverges here — the 0.4 recipe itself
    # is exercised on VGG by the golden-trace tests).
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=6,
                              steps_per_epoch=len(loader))
    tr = Trainer(model, loader, params, stats, mesh=mesh, lr_schedule=sched,
                 sgd_config=SGDConfig(lr=0.05), save_every=100,
                 snapshot_path="/tmp/unused_e2e.pt")
    tr.train(6)
    first = np.mean(tr.loss_history[:4])
    last = np.mean(tr.loss_history[-4:])
    assert last < first - 0.2, (first, last)
    acc = evaluate(model, tr.state.params, tr.state.batch_stats,
                   EvalLoader(test_ds, 32, 8), mesh, progress=False)
    assert acc > 15.0  # better than the 10% random baseline
