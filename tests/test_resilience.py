"""Resilience subsystem (ddp_tpu/resilience/): checkpoint lineage +
fall-back restore, the --on_nan loss-health policies, coordinated
preemption checkpoints, the watchdog, and the dist.abort fast-path canary
(VERDICT r5 #3) — all driven by the fault injectors in
ddp_tpu/resilience/faults.py.

The failure modes injected here are the ones real TPU pods throw
(preemption SIGTERM, torn files, diverging numerics, hung peers); the
reference has no story for any of them (a SIGTERM loses everything since
the last save_every boundary, multigpu.py:117-119).
"""
import functools
import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu.data import TrainLoader, synthetic
from ddp_tpu.models import get_model
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.optim.sgd import SGDState
from ddp_tpu.parallel import dist, make_mesh
from ddp_tpu.resilience import faults
from ddp_tpu.resilience.drift import DriftDetectedError, leaf_paths
from ddp_tpu.resilience.guard import (LossSpikeError, NonFiniteLossError,
                                      RestoreFromLastGood, StepHealthGuard)
from ddp_tpu.resilience.lineage import (CheckpointLineage,
                                        load_latest_verifiable)
from ddp_tpu.resilience.preemption import (PreemptionGuard,
                                           PreemptionInterrupt)
from ddp_tpu.resilience.watchdog import WATCHDOG_EXIT_STATUS, Watchdog
from ddp_tpu.train import Trainer, load_checkpoint, save_checkpoint
from ddp_tpu.train.checkpoint import CheckpointError, sha256_of_file

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- checkpoint lineage ----------------------------------------------------


def _write_ck(path, *, step, epoch):
    """A tiny but structurally valid checkpoint; returns its sha."""
    return save_checkpoint(
        path, {"w": np.full(4, float(step), np.float32)}, {},
        SGDState({"w": np.zeros(4, np.float32)}), step=step, epoch=epoch)


def _commit(lin, epoch):
    lin.preserve_head()
    sha = _write_ck(lin.path, step=epoch, epoch=epoch)
    lin.commit(epoch=epoch, step=epoch, sha256=sha)


def test_save_checkpoint_returns_file_sha(tmp_path):
    path = str(tmp_path / "ck.pt")
    sha = _write_ck(path, step=3, epoch=1)
    assert sha == sha256_of_file(path)


def test_lineage_rotation_manifest_and_fallback_order(tmp_path):
    """5 commits at keep=3: the head plus the 2 newest rotated snapshots
    survive (older ones rotated away), the manifest's shas match the bytes
    on disk, and tearing candidates newest-first walks the fall-back chain
    until a CheckpointError that names every candidate tried."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=3)
    for e in range(5):
        _commit(lin, e)
    files = sorted(os.listdir(tmp_path))
    assert files == ["ck.pt", "ck.pt.ep00000002", "ck.pt.ep00000003",
                     "ck.pt.manifest.json"]
    m = json.load(open(path + ".manifest.json"))
    assert m["head"]["epoch"] == 4
    assert m["head"]["sha256"] == sha256_of_file(path)
    assert [e["epoch"] for e in m["retained"]] == [3, 2]
    for e in m["retained"]:
        assert e["sha256"] == sha256_of_file(str(tmp_path / e["file"]))

    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 4 and used == path
    faults.tear_file(path)
    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 3 and used.endswith(".ep00000003")
    faults.tear_file(used)
    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 2 and used.endswith(".ep00000002")
    faults.tear_file(used)
    with pytest.raises(CheckpointError) as ei:
        load_latest_verifiable(path)
    for name in ("ck.pt", "ep00000003", "ep00000002"):
        assert name in str(ei.value)


def test_manifest_commit_fsync_order_pins_crash_atomicity(tmp_path,
                                                          monkeypatch):
    """Satellite: the manifest commit must fsync the temp FILE before the
    ``os.replace`` publish and fsync the DIRECTORY after it — rename
    ordering alone is a filesystem implementation detail.  Pinned by (a)
    recording the exact syscall order and (b) failing the pre-rename
    fsync: the crash window must leave the previous manifest untouched."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=2)
    _commit(lin, 0)  # a known-good manifest on disk
    lin.preserve_head()
    sha = _write_ck(path, step=1, epoch=1)
    calls = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        os, "fsync",
        lambda fd: (calls.append("fsync"), real_fsync(fd))[1])
    monkeypatch.setattr(
        os, "replace",
        lambda a, b: (calls.append("replace"), real_replace(a, b))[1])
    lin.commit(epoch=1, step=1, sha256=sha)
    assert calls == ["fsync", "replace", "fsync"]  # file, publish, dir
    # ENOSPC at the pre-rename fsync: commit raises, the temp file is
    # cleaned up, and the epoch-1 manifest survives byte-for-byte.
    lin.preserve_head()
    sha2 = _write_ck(path, step=2, epoch=2)

    def _boom(fd):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(os, "fsync", _boom)
    with pytest.raises(OSError, match="No space left"):
        lin.commit(epoch=2, step=2, sha256=sha2)
    monkeypatch.setattr(os, "fsync", real_fsync)
    m = json.load(open(path + ".manifest.json"))
    assert m["head"]["epoch"] == 1  # the torn commit published NOTHING
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_lineage_keep1_is_head_only(tmp_path):
    """Default --keep_checkpoints 1 preserves today's artifact layout: one
    head file (plus the manifest), no rotated snapshots."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=1)
    for e in range(3):
        _commit(lin, e)
    assert sorted(os.listdir(tmp_path)) == ["ck.pt", "ck.pt.manifest.json"]
    ck, _ = load_latest_verifiable(path)
    assert ck.epoch == 2


def test_lineage_manifest_missing_falls_back_via_scan(tmp_path):
    """No manifest (satellite edge case): the directory scan of the
    P.ep* naming still finds the newest rotated snapshot."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=2)
    for e in range(2):
        _commit(lin, e)
    os.unlink(path + ".manifest.json")
    faults.tear_file(path)
    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 0 and used.endswith(".ep00000000")


def test_lineage_manifest_referencing_deleted_file(tmp_path, capfd):
    """A manifest entry whose file is gone is skipped with a warning, not
    a crash; remaining candidates still restore."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=2)
    for e in range(2):
        _commit(lin, e)
    os.unlink(str(tmp_path / "ck.pt.ep00000000"))
    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 1 and used == path
    assert "the file is gone" in capfd.readouterr().err
    # ... and with the head ALSO torn, the only remaining candidate is a
    # missing file -> every candidate is named in the error.
    faults.tear_file(path)
    with pytest.raises(CheckpointError, match="ck.pt"):
        load_latest_verifiable(path)


def test_lineage_stale_manifest_sha_still_restores(tmp_path, capfd):
    """A preemption between the head write and the manifest write leaves a
    stale sha; the head must still restore (with a logged mismatch), not
    be discarded."""
    path = str(tmp_path / "ck.pt")
    lin = CheckpointLineage(path, keep=2)
    _commit(lin, 0)
    _write_ck(path, step=9, epoch=1)  # head overwritten, manifest not
    ck, used = load_latest_verifiable(path)
    assert ck.epoch == 1 and used == path
    assert "sha256 mismatch" in capfd.readouterr().err


def test_rotation_never_touches_unlisted_or_inflight_files(tmp_path):
    """Rotation deletes only manifest-listed P.ep* siblings beyond the
    retention budget — an in-flight writer's *.tmp and any unlisted file
    survive every commit (satellite edge case: the async saver's
    in-progress file can never be rotated away)."""
    path = str(tmp_path / "ck.pt")
    inflight = str(tmp_path / "ck.pt.ep_writer.tmp")
    stranger = str(tmp_path / "other.npz")
    open(inflight, "wb").write(b"half-written")
    open(stranger, "wb").write(b"unrelated")
    lin = CheckpointLineage(path, keep=2)
    for e in range(4):
        _commit(lin, e)
    assert os.path.exists(inflight) and os.path.exists(stranger)
    # Retention still enforced around them.
    eps = sorted(f for f in os.listdir(tmp_path)
                 if f.startswith("ck.pt.ep0"))
    assert eps == ["ck.pt.ep00000002"]


# -- trainer wiring: resume fall-back, --on_nan, preemption ----------------


def _make_trainer(path, epochs, seed=0, resume=False, keep=1,
                  on_nan="abort", preemption=None, save_every=1,
                  ckpt_format="gathered", **extra):
    """test_checkpoint.py's DeepNN trainer, resilience knobs exposed
    (``extra`` reaches the Trainer ctor: metrics, drift/guard knobs)."""
    train_ds, _ = synthetic(n_train=256, seed=1)
    mesh = make_mesh(8)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(seed))
    loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=8,
                         seed=seed)
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=epochs,
                              steps_per_epoch=len(loader))
    return Trainer(model, loader, params, stats, mesh=mesh, lr_schedule=sched,
                   sgd_config=SGDConfig(lr=0.05), save_every=save_every,
                   snapshot_path=path, resume=resume,
                   keep_checkpoints=keep, on_nan=on_nan,
                   preemption=preemption, ckpt_format=ckpt_format, **extra)


def _params_equal(a, b):
    wa = jax.tree_util.tree_leaves_with_path(jax.device_get(a))
    wb = jax.tree_util.tree_leaves_with_path(jax.device_get(b))
    assert len(wa) == len(wb)
    for (pa, x), (pb, y) in zip(wa, wb):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(pa))


def test_fail_ckpt_write_surfaces_at_next_boundary_lineage_untorn(
        tmp_path, monkeypatch):
    """Checkpoint-write-failure drill (installed through the same
    ``DDP_TPU_FAULT`` env path the subprocess drills use): the epoch-1
    async write dies on the WRITER THREAD.  The deferred
    ``trainer._save_error`` must surface at the next
    ``_join_pending_save`` boundary — a silently-lost checkpoint must
    not look saved — and the lineage must be left un-torn: the fault
    fires before the head file is opened, so the newest verifiable
    snapshot (the one ``--resume`` would restore) is still the clean
    epoch-0 save, byte-intact."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=2, keep=2)
    monkeypatch.setenv(faults.FAULT_ENV, "fail_ckpt_write@epoch=1")
    faults.install_env_faults(tr)
    with pytest.raises(OSError,
                       match="injected checkpoint write failure"):
        tr.train(2)
    loaded = load_latest_verifiable(path)
    assert loaded is not None
    ckpt, used = loaded
    assert int(ckpt.epoch) == 0  # the pre-fault save, byte-intact
    assert int(ckpt.step) == len(tr.train_loader)


def test_resume_falls_back_on_torn_head(tmp_path, capfd):
    """The acceptance drill: tear the head, resume must restore the
    previous retained snapshot with a logged warning and train on."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, keep=2)
    tr.train(2)
    faults.tear_file(path)
    res = _make_trainer(path, epochs=3, keep=2, resume=True)
    err = capfd.readouterr().err
    assert "FALLBACK" in err and "ep00000000" in err
    assert res.start_epoch == 1  # fell back to the epoch-0 snapshot
    res.train(3)  # ...and the run continues to completion
    assert int(res.state.step) == 3 * len(res.train_loader)
    # With EVERY candidate torn, resume fails naming each one.
    faults.tear_file(path)
    faults.tear_file(str(tmp_path / "ck.pt.ep00000001"))
    with pytest.raises(CheckpointError) as ei:
        _make_trainer(path, epochs=3, keep=2, resume=True)
    assert "ck.pt" in str(ei.value) and "ep00000001" in str(ei.value)


def test_sharded_resume_falls_back_on_torn_shard(tmp_path, capfd):
    """ISSUE 6: the sharded (v2) format keeps the lineage fallback
    semantics — a TORN SHARD FILE (head index intact, shard sha256
    mismatch) fails that candidate with the shard named and resume falls
    back to the previous retained snapshot, exactly like a torn v1 head."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, keep=2, ckpt_format="sharded")
    tr.train(2)
    shards1 = [n for n in os.listdir(tmp_path) if ".ep00000001.shard" in n
               and n.endswith(".npz")]
    assert shards1, "sharded save wrote no epoch-1 shard files"
    faults.tear_file(str(tmp_path / shards1[0]))
    res = _make_trainer(path, epochs=3, keep=2, resume=True,
                        ckpt_format="sharded")
    err = capfd.readouterr().err
    assert "FALLBACK" in err
    assert res.start_epoch == 1  # fell back to the epoch-0 snapshot
    res.train(3)  # ...and the run continues to completion
    assert int(res.state.step) == 3 * len(res.train_loader)


def test_sharded_resume_falls_back_on_missing_shard(tmp_path, capfd):
    """A MISSING shard file (deleted/never-landed) is the other v2 damage
    mode: the candidate fails naming the absent shard, the walk falls
    back; with EVERY epoch's shard set damaged, resume raises naming each
    candidate tried."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, keep=2, ckpt_format="sharded")
    tr.train(2)
    shards1 = [n for n in os.listdir(tmp_path) if ".ep00000001.shard" in n
               and n.endswith(".npz")]
    assert shards1
    os.unlink(str(tmp_path / shards1[0]))
    res = _make_trainer(path, epochs=3, keep=2, resume=True,
                        ckpt_format="sharded")
    err = capfd.readouterr().err
    assert "FALLBACK" in err and "MISSING" in err
    assert res.start_epoch == 1
    # Now damage the fallback too: every candidate fails, loudly.
    for n in os.listdir(tmp_path):
        if ".ep00000000.shard" in n and n.endswith(".npz"):
            os.unlink(str(tmp_path / n))
    with pytest.raises(CheckpointError) as ei:
        _make_trainer(path, epochs=3, keep=2, resume=True,
                      ckpt_format="sharded")
    assert "ck.pt" in str(ei.value) and "ep00000000" in str(ei.value)


def test_sharded_lineage_trims_dropped_epochs_shards(tmp_path):
    """Retention composes with the shard set: when an epoch drops out of
    the manifest its shard files are unlinked with it — and never one a
    surviving entry still references (the rotated head's epoch-qualified
    shards stay restorable)."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, keep=2, ckpt_format="sharded")
    tr.train(3)
    names = os.listdir(tmp_path)
    assert not [n for n in names if ".ep00000000.shard" in n], \
        "dropped epoch 0's shard files were not trimmed"
    assert [n for n in names if ".ep00000001.shard" in n], \
        "retained epoch 1's shard files were trimmed"
    assert [n for n in names if ".ep00000002.shard" in n]
    # The head and the retained rotated snapshot both still restore.
    assert load_checkpoint(path).epoch == 2
    assert load_checkpoint(str(tmp_path / "ck.pt.ep00000001")).epoch == 1


def test_on_nan_abort_raises_and_head_stays_good(tmp_path):
    """--on_nan abort: fail fast — and because losses are flushed/checked
    before the epoch's save, the poisoned epoch never becomes a
    checkpoint: the head on disk is the last verified-finite epoch."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3)
    steps = len(tr.train_loader)
    faults.poison_loss(tr, steps + 1)  # second step of epoch 1
    with pytest.raises(NonFiniteLossError, match="step"):
        tr.train(3)
    assert load_checkpoint(path).epoch == 0


def test_on_nan_skip_logs_and_continues(tmp_path, capfd):
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, on_nan="skip")
    steps = len(tr.train_loader)
    faults.poison_loss(tr, steps + 1)
    tr.train(3)
    assert "--on_nan skip" in capfd.readouterr().err
    assert int(tr.state.step) == 3 * steps
    assert np.isnan(tr.loss_history).any()


def test_on_nan_restore_recovers_and_completes(tmp_path, capfd):
    """Acceptance: --on_nan restore reloads the last-good checkpoint after
    a poisoned step, re-seeds the step RNG, and completes the run."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, on_nan="restore")
    steps = len(tr.train_loader)
    faults.poison_loss(tr, steps + 1)
    tr.train(3)
    err = capfd.readouterr().err
    assert "restored last-good checkpoint" in err
    assert tr._health.restores == 1
    assert int(tr.state.step) == 3 * steps
    # The discarded trajectory's records were truncated at the rewind:
    # one entry per global step, none of them the poisoned NaN.
    assert len(tr.loss_history) == 3 * steps
    assert all(np.isfinite(l) for l in tr.loss_history)
    assert load_checkpoint(path).epoch == 2


def test_on_nan_restore_budget_exhausts(tmp_path):
    """A divergence that recurs on every restore must eventually abort,
    not spin forever."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=3, on_nan="restore")
    tr._health.max_restores = 2
    steps = len(tr.train_loader)
    # Re-arm the poison after every flush: a persistent divergence.
    orig = tr._flush_losses

    def always_poison(epoch, start_step, stacked):
        if stacked is not None and start_step + stacked.shape[0] > steps:
            arr = np.array(jax.device_get(stacked), dtype=np.float64)
            arr[-1] = float("nan")
            stacked = arr
        return orig(epoch, start_step, stacked)

    tr._flush_losses = always_poison
    with pytest.raises(NonFiniteLossError, match="budget exhausted"):
        tr.train(3)
    assert tr._health.restores == 2


def test_health_guard_rejects_unknown_policy():
    with pytest.raises(ValueError, match="on_nan"):
        StepHealthGuard("explode")


def test_preemption_drill_resume_matches_uninterrupted(tmp_path, capfd):
    """Acceptance: SIGTERM mid-run -> emergency checkpoint at the next
    epoch boundary -> PreemptionInterrupt; --resume from it reproduces the
    uninterrupted run of the same seed bit-for-bit (epoch-granular resume
    semantics: the restart replays nothing and skips nothing)."""
    p_full = str(tmp_path / "full.pt")
    p_half = str(tmp_path / "half.pt")
    t_full = _make_trainer(p_full, epochs=3, save_every=100)
    t_full.train(3)

    guard = PreemptionGuard().install()
    try:
        t_half = _make_trainer(p_half, epochs=3, save_every=100,
                               preemption=guard)
        faults.sigterm_at_epoch(t_half, 1)
        with pytest.raises(PreemptionInterrupt):
            t_half.train(3)
    finally:
        guard.uninstall()
    err = capfd.readouterr().err
    assert "preemption notice" in err and "emergency checkpoint" in err
    ck = load_checkpoint(p_half)
    assert ck.epoch == 1  # the boundary right after the signal

    t_res = _make_trainer(p_half, epochs=3, save_every=100, resume=True)
    assert t_res.start_epoch == 2
    t_res.train(3)
    _params_equal(t_full.state.params, t_res.state.params)
    assert int(t_full.state.step) == int(t_res.state.step)


# -- round 12: mid-epoch checkpoint/resume, drift audit, spike guard ------


@pytest.fixture(scope="module")
def full_run_ref(tmp_path_factory):
    """The uninterrupted 3-epoch run every mid-epoch drill compares
    against (one compile+train for the whole module)."""
    path = str(tmp_path_factory.mktemp("ref") / "full.pt")
    tr = _make_trainer(path, epochs=3, save_every=100)
    tr.train(3)
    return jax.device_get(tr.state.params), int(tr.state.step)


@pytest.mark.parametrize("fmt", ["gathered", "sharded"])
@pytest.mark.parametrize("kill_step", [5, 9])
def test_midepoch_preemption_resume_bit_identical(tmp_path, capfd,
                                                  full_run_ref, fmt,
                                                  kill_step):
    """Acceptance (round 12): SIGTERM mid-epoch -> emergency checkpoint
    at the NEXT STEP boundary carrying a data_state (epoch, offset, seed,
    rng_folds); --resume fast-forwards the epoch to that exact batch and
    lands bit-for-bit on the uninterrupted run's final state — at two
    kill points, in both checkpoint formats."""
    want_params, want_step = full_run_ref
    path = str(tmp_path / "half.pt")
    guard = PreemptionGuard().install()
    try:
        half = _make_trainer(path, epochs=3, save_every=100,
                             preemption=guard, ckpt_format=fmt)
        steps = len(half.train_loader)
        faults.sigterm_at_step(half, kill_step)
        with pytest.raises(PreemptionInterrupt):
            half.train(3)
    finally:
        guard.uninstall()
    err = capfd.readouterr().err
    assert "preemption notice" in err and "emergency checkpoint" in err
    ck = load_checkpoint(path)
    ds = ck.data_state
    assert ds is not None and ds["version"] == 1
    # The stop lands on the signal's step boundary (the OS may deliver
    # one dispatch late) and MID-epoch: a nonzero batch offset.
    stopped_at = ds["epoch"] * steps + ds["offset"]
    assert kill_step <= stopped_at <= kill_step + 2
    assert 0 < ds["offset"] < steps
    assert ds["rng_folds"] == 0 and ds["seed"] == 0
    # Satellite: the lineage manifest's head entry mirrors the record.
    man = json.load(open(path + ".manifest.json"))
    assert man["head"]["data_state"] == ds

    res = _make_trainer(path, epochs=3, save_every=100, resume=True,
                        ckpt_format=fmt)
    assert res.start_epoch == ds["epoch"]
    assert res._resume_offset == ds["offset"]
    res.train(3)
    assert "fast-forwarding" in capfd.readouterr().out
    _params_equal(want_params, res.state.params)
    assert int(res.state.step) == want_step


def test_torn_data_state_degrades_to_epoch_boundary(tmp_path, capfd):
    """A torn/unparseable data_state record is treated as ABSENT: resume
    falls back to the epoch-boundary semantics with a warning — never an
    error (MIGRATING.md contract)."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=2)
    tr.train(2)
    faults.torn_data_state(path)
    res = _make_trainer(path, epochs=2, resume=True)
    err = capfd.readouterr().err
    assert "no data_state record" in err
    assert res.start_epoch == 2 and res._resume_offset == 0


def test_legacy_checkpoint_missing_data_state_warns(tmp_path, capfd):
    """A pre-round-12 checkpoint (key absent, not torn) resumes at the
    next epoch boundary with the one-line warning."""
    from ddp_tpu.train.checkpoint import write_npz_hashed
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=2)
    tr.train(2)
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files if k != "meta/data_state_json"}
    write_npz_hashed(path, flat)
    res = _make_trainer(path, epochs=2, resume=True)
    err = capfd.readouterr().err
    assert "no data_state record" in err and "epoch boundary" in err
    assert res.start_epoch == 2 and res._resume_offset == 0


def _events(path):
    return [json.loads(line) for line in open(path)]


def test_drift_audit_detects_flip_within_k_and_aborts(tmp_path, capfd):
    """Acceptance (round 12 SDC drill): one flipped parameter bit on one
    virtual replica is detected within K steps of the next audit, the
    drift_detected event names the offending leaf path and replica, and
    --drift_action abort fails fast with the event already on disk."""
    from ddp_tpu.utils.metrics import MetricsLogger
    path = str(tmp_path / "ck.pt")
    mpath = str(tmp_path / "m.jsonl")
    metrics = MetricsLogger(mpath)
    tr = _make_trainer(path, epochs=3, metrics=metrics,
                       drift_audit_every=2)
    bad_leaf = leaf_paths(tr.state.params)[0]
    faults.flip_param_bit(tr, 5, replica=1)
    with pytest.raises(DriftDetectedError, match="drift"):
        tr.train(3)
    metrics.close()
    assert "silent data corruption" in capfd.readouterr().err
    ev = [e for e in _events(mpath) if e.get("event") == "drift_detected"]
    assert len(ev) == 1
    assert ev[0]["step"] <= 5 + 2  # within K=2 steps of the flip
    assert bad_leaf in ev[0]["leaves"]
    assert ev[0]["replicas"] == [1]


def test_drift_audit_restore_recovers_and_completes(tmp_path):
    """--drift_action restore: roll back to the last verified snapshot
    (sharing the guard's restore budget) and complete the run with zero
    non-finite losses in the flushed metrics."""
    from ddp_tpu.utils.metrics import MetricsLogger
    path = str(tmp_path / "ck.pt")
    mpath = str(tmp_path / "m.jsonl")
    metrics = MetricsLogger(mpath)
    tr = _make_trainer(path, epochs=3, metrics=metrics,
                       drift_audit_every=2, drift_action="restore")
    faults.flip_param_bit(tr, 5, replica=2)
    tr.train(3)
    metrics.close()
    assert tr._drift.detections == 1
    assert tr._health.restores == 1  # shared budget consumed
    assert int(tr.state.step) == 3 * len(tr.train_loader)
    losses = [e["loss"] for e in _events(mpath) if "loss" in e]
    assert losses and all(np.isfinite(l) for l in losses)


def test_drift_audit_rejects_resident_mode(tmp_path):
    """The audit needs step boundaries; the resident whole-epoch scan has
    none — refused at construction, not silently skipped."""
    train_ds, _ = synthetic(n_train=256, seed=1)
    mesh = make_mesh(8)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    loader = TrainLoader(train_ds, per_replica_batch=8, num_replicas=8,
                         augment=False, seed=0)
    sched = functools.partial(triangular_lr, base_lr=0.05, num_epochs=1,
                              steps_per_epoch=len(loader))
    with pytest.raises(ValueError, match="drift_audit_every"):
        Trainer(model, loader, params, stats, mesh=mesh,
                lr_schedule=sched, sgd_config=SGDConfig(lr=0.05),
                snapshot_path=str(tmp_path / "ck.pt"),
                resident=True, device_augment=True,
                drift_audit_every=10)


def test_guard_spike_rollback_skips_poisoned_window(tmp_path, capfd):
    """A poisoned batch spikes the loss; --guard_action rollback restores
    the last verified snapshot and SKIPS the condemned batch window on
    replay (re-ingesting it would just spike again)."""
    path = str(tmp_path / "ck.pt")
    tr = _make_trainer(path, epochs=4, guard_spike_factor=2.0,
                       guard_action="rollback", guard_window=8)
    steps = len(tr.train_loader)
    faults.poison_batch(tr, 2 * steps + 1, scale=40)
    tr.train(4)
    err = capfd.readouterr().err
    assert "poisoned batch window" in err
    assert tr._health.decisions["spike_rollback"] == 1
    assert tr._health.last_decision.startswith("spike_rollback@")
    # The condemned batches never re-dispatched: fewer optimizer steps
    # than the uninterrupted run, and every surviving loss is finite.
    assert int(tr.state.step) < 4 * steps
    assert all(np.isfinite(l) for l in tr.loss_history)


def test_guard_spike_abort_and_skip():
    """Series-level unit: the rolling median/MAD detector flags a spike
    after _MIN_WINDOW history; abort raises, skip keeps the outlier OUT
    of the window so the baseline doesn't inflate."""
    g = StepHealthGuard(window=8, spike_factor=2.0, spike_action="abort")
    g.check_series("loss", [1.0] * 8, list(range(8)), epoch=0)
    with pytest.raises(LossSpikeError, match="guard_action abort"):
        g.check_series("loss", [50.0], [8], epoch=0)

    g2 = StepHealthGuard(window=8, spike_factor=2.0, spike_action="skip")
    g2.check_series("loss", [1.0] * 8, list(range(8)), epoch=0)
    g2.check_series("loss", [50.0], [8], epoch=0)  # logged, not raised
    assert g2.decisions["spike_skip"] == 1
    # The spike stayed out of the window: a normal value is still normal.
    g2.check_series("loss", [1.1], [9], epoch=0)
    assert g2.decisions["spike_skip"] == 1


def test_guard_lr_backoff_halves_schedule_scale():
    calls = []
    g = StepHealthGuard(window=8, spike_factor=2.0,
                        spike_action="lr_backoff")
    g.check_series("loss", [1.0] * 8, list(range(8)), epoch=0)
    # No trainer hook installed: degrades to a logged skip.
    g.check_series("loss", [50.0], [8], epoch=0)
    assert g.lr_scale == 1.0 and g.decisions["spike_skip"] == 1
    g.on_lr_backoff = calls.append
    g.check_series("loss", [50.0], [9], epoch=0)
    assert g.lr_scale == 0.5 and calls == [0.5]
    assert g.decisions["spike_lr_backoff"] == 1


def test_guard_rollback_names_the_poisoned_steps():
    g = StepHealthGuard(window=8, spike_factor=2.0,
                        spike_action="rollback")
    g.check_series("loss", [1.0] * 8, list(range(80, 88)), epoch=3)
    with pytest.raises(RestoreFromLastGood) as ei:
        g.check_series("loss", [50.0, 60.0], [88, 89], epoch=3)
    assert ei.value.skip_steps == [88, 89]
    assert ei.value.skip_epoch == 3
    assert g.restores == 1  # shares the --on_nan restore budget


def test_guard_rejects_bad_spike_knobs():
    with pytest.raises(ValueError, match="guard_action"):
        StepHealthGuard(window=8, spike_action="explode")
    with pytest.raises(ValueError, match="guard_spike_factor"):
        StepHealthGuard(window=8, spike_factor=-1.0)


def test_preemption_guard_second_signal_restores_previous_handler():
    prev = signal.getsignal(signal.SIGTERM)
    guard = PreemptionGuard(signals=(signal.SIGTERM,)).install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if guard.noticed():
                break
            time.sleep(0.01)
        assert guard.noticed()
        # First delivery re-armed the pre-existing behavior.
        assert signal.getsignal(signal.SIGTERM) in (prev, signal.SIG_DFL)
    finally:
        guard.uninstall()
    assert signal.getsignal(signal.SIGTERM) in (prev, signal.SIG_DFL)


# -- watchdog --------------------------------------------------------------


def test_watchdog_fires_on_stall_and_is_fast(capfd):
    fired = []
    wd = Watchdog(0.3, tag="unit")
    wd._exit = fired.append  # seam: don't kill pytest
    t0 = time.monotonic()
    wd.start()
    try:
        for _ in range(200):
            if fired:
                break
            time.sleep(0.05)
    finally:
        wd.stop()
    assert fired == [WATCHDOG_EXIT_STATUS]
    assert time.monotonic() - t0 < 5.0  # orders of magnitude under 300 s
    assert "WATCHDOG" in capfd.readouterr().err


def test_watchdog_heartbeats_prevent_firing():
    fired = []
    wd = Watchdog(0.5, tag="unit")
    wd._exit = fired.append
    wd.start()
    try:
        for _ in range(15):
            time.sleep(0.1)
            wd.beat()
    finally:
        wd.stop()
    assert not fired


# -- dist.abort fast-path canary (VERDICT r5 #3) ---------------------------


def test_abort_fast_path_canary():
    """The non-blocking abort() rides private jax._src.distributed
    internals; if a JAX upgrade moves them, every multi-host abort
    silently becomes a 300 s graceful-shutdown hang.  Pin (a) the internal
    attributes exist on the pinned JAX and (b) abort() returns within a
    tight bound."""
    assert dist.abort_fast_path_ready(), (
        "jax._src.distributed.global_state no longer exposes "
        f"{dist._ABORT_FAST_PATH_ATTRS}; dist.abort() would fall back to "
        "the blocking graceful shutdown (300 s per abort) — update "
        "dist.abort() for the new internal layout")
    t0 = time.monotonic()
    dist.abort()  # uninitialized here: must be an instant no-op
    assert time.monotonic() - t0 < 5.0
    # The sync-manager accessor must never raise either (preemption.py
    # polls it every epoch boundary).
    dist.preemption_sync_manager()


# -- scan-unroll product gating (ADVICE r5) --------------------------------


def _trace_accum_epoch(monkeypatch, shard_update):
    """Trace an accumulation epoch program with scan_unroll recorded:
    G*A > 32 but A <= 32 — the shape where an A-gated inner scan would
    inline conv bodies inside a rolled outer loop."""
    from ddp_tpu.parallel.mesh import scan_unroll as real_scan_unroll
    from ddp_tpu.train import epoch as mod
    from ddp_tpu.train.epoch import put_index_matrix
    from ddp_tpu.train.step import TrainState, init_train_state
    from ddp_tpu.train.zero import init_opt_shard

    calls = []

    def recording(mesh, length=None):
        calls.append(length)
        return real_scan_unroll(mesh, length)

    monkeypatch.setattr(mod, "scan_unroll", recording)
    mesh = make_mesh(8)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=1,
                              steps_per_epoch=34)
    fn = mod.make_train_epoch(model, SGDConfig(), sched, mesh, accum=True,
                              shard_update=shard_update)
    G, A, B = 17, 2, 8  # G*A = 34 > 32, A = 2 <= 32
    from ddp_tpu.ops.gather import RowTable
    images = RowTable.from_rows(jnp.zeros((16, 32, 32, 3), jnp.float32))
    labels = jnp.zeros((16,), jnp.int32)
    idx = put_index_matrix(np.zeros((G, A, B), np.int32), mesh)
    if shard_update:
        state = TrainState(params, stats, init_opt_shard(params, mesh),
                           jnp.zeros((), jnp.int32))
    else:
        state = init_train_state(params, stats)
    fn.lower(state, images, labels, idx, jax.random.key(0))
    return G, A, calls


@pytest.mark.parametrize("shard_update", [False, True])
def test_accum_inner_unroll_gated_on_product(monkeypatch, shard_update):
    """ADVICE r5: BOTH the outer epoch scan and the inner accum scan must
    gate their unroll on the G*A product — an inner scan gated on A alone
    would fully unroll A conv fwd+bwd bodies inside a rolled while loop
    whenever A <= 32 < G*A (the pathological XLA:CPU conv-in-rolled-loop
    shape)."""
    G, A, calls = _trace_accum_epoch(monkeypatch, shard_update)
    assert len(calls) == 2  # outer epoch scan + inner accum scan
    assert calls == [G * A, G * A]


def test_bench_scan_record_carries_unroll_marker():
    """ADVICE r5: the bench JSON's scan-dispatch record must say which
    program shape (rolled vs unrolled) was timed."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--model", "deepnn", "--steps", "4",
         "--warmup", "1", "--repeats", "1", "--batch_size", "8",
         "--num_devices", "2", "--dispatch", "scan", "--primary_only",
         "--no_bf16"],
        cwd=_REPO, env={**os.environ}, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["scan_unroll"] == 4  # 4-step CPU window: fully unrolled
    assert rec["scan_rolled"] is False


# -- subprocess drills (slow: real processes, real signals) ----------------


def _clean_env(ndev: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    return env


@pytest.mark.slow
def test_cli_preemption_exit_status_and_resume(tmp_path):
    """End-to-end preemption drill through the real CLI: fault-injected
    SIGTERM mid-run -> emergency checkpoint + exit status 75; --resume
    finishes the run and lands on the SAME final state as an uninterrupted
    run of the same seed."""
    common = ["3", "1", "--batch_size", "4", "--synthetic", "--model",
              "deepnn", "--lr", "0.05", "--synthetic_size", "64",
              "--seed", "3"]
    env = _clean_env(8)

    def run_cli(snapshot, extra=(), fault=None):
        e = dict(env)
        if fault:
            e[faults.FAULT_ENV] = fault
        return subprocess.run(
            [sys.executable, "multigpu.py", *common, *extra,
             "--snapshot_path", str(tmp_path / snapshot)],
            cwd=_REPO, env=e, capture_output=True, text=True, timeout=600)

    full = run_cli("full.pt")
    assert full.returncode == 0, (full.stdout[-2000:], full.stderr[-2000:])

    interrupted = run_cli("int.pt", fault="sigterm@epoch=1")
    assert interrupted.returncode == 75, (interrupted.stdout[-2000:],
                                          interrupted.stderr[-2000:])
    assert "emergency checkpoint" in interrupted.stderr
    assert load_checkpoint(str(tmp_path / "int.pt")).epoch == 1

    resumed = run_cli("int.pt", extra=["--resume"])
    assert resumed.returncode == 0, (resumed.stdout[-2000:],
                                     resumed.stderr[-2000:])
    assert "Resuming training from snapshot at Epoch 1" in resumed.stdout

    want = load_checkpoint(str(tmp_path / "full.pt"))
    got = load_checkpoint(str(tmp_path / "int.pt"))
    _params_equal(want.params, got.params)
    assert want.step == got.step


@pytest.mark.slow
def test_cli_midepoch_preemption_resume_bit_identical(tmp_path):
    """Round-12 CI drill through the real CLI: SIGTERM at a STEP inside
    epoch 1 -> emergency checkpoint with a mid-epoch data_state + exit
    75; --resume fast-forwards to the unconsumed batch and lands on the
    SAME final state as the uninterrupted run."""
    common = ["3", "1", "--batch_size", "4", "--synthetic", "--model",
              "deepnn", "--lr", "0.05", "--synthetic_size", "64",
              "--seed", "3"]
    env = _clean_env(8)

    def run_cli(snapshot, extra=(), fault=None):
        e = dict(env)
        if fault:
            e[faults.FAULT_ENV] = fault
        return subprocess.run(
            [sys.executable, "multigpu.py", *common, *extra,
             "--snapshot_path", str(tmp_path / snapshot)],
            cwd=_REPO, env=e, capture_output=True, text=True, timeout=600)

    full = run_cli("full.pt")
    assert full.returncode == 0, (full.stdout[-2000:], full.stderr[-2000:])

    # 2 steps/epoch (64 / (4*8)): step 2 is the first batch of epoch 1,
    # so the stop boundary lands mid-epoch at (epoch 1, offset 1).
    interrupted = run_cli("int.pt", fault="sigterm@step=2")
    assert interrupted.returncode == 75, (interrupted.stdout[-2000:],
                                          interrupted.stderr[-2000:])
    assert "emergency checkpoint" in interrupted.stderr
    ds = load_checkpoint(str(tmp_path / "int.pt")).data_state
    assert ds["epoch"] == 1 and ds["offset"] == 1

    resumed = run_cli("int.pt", extra=["--resume"])
    assert resumed.returncode == 0, (resumed.stdout[-2000:],
                                     resumed.stderr[-2000:])
    assert "fast-forwarding epoch 1 to batch offset 1" in resumed.stdout

    want = load_checkpoint(str(tmp_path / "full.pt"))
    got = load_checkpoint(str(tmp_path / "int.pt"))
    _params_equal(want.params, got.params)
    assert want.step == got.step


@pytest.mark.slow
def test_cli_sdc_drill_flip_detected_and_restored(tmp_path):
    """Round-12 CI drill: a flipped parameter bit on one virtual replica
    is caught by the drift audit within K steps, the drift_detected
    event (leaf paths + replica) lands in the metrics spill, and
    --drift_action restore rolls back and completes with exit 0 and
    finite losses."""
    env = _clean_env(8)
    env[faults.FAULT_ENV] = "flip_param_bit@step=2,replica=1"
    mpath = str(tmp_path / "metrics.jsonl")
    out = subprocess.run(
        [sys.executable, "multigpu.py", "3", "1", "--batch_size", "4",
         "--synthetic", "--model", "deepnn", "--lr", "0.05",
         "--synthetic_size", "64", "--seed", "3",
         "--drift_audit_every", "2", "--drift_action", "restore",
         "--metrics_path", mpath,
         "--snapshot_path", str(tmp_path / "sdc.pt")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "silent data corruption" in out.stderr
    records = [json.loads(line) for line in open(mpath)]
    ev = [r for r in records if r.get("event") == "drift_detected"]
    assert len(ev) == 1
    assert ev[0]["action"] == "restore" and ev[0]["replicas"] == [1]
    assert ev[0]["leaves"]  # offending leaf paths are named
    assert ev[0]["step"] <= 2 + 1 + 2  # within K=2 of the corrupt step
    losses = [r["loss"] for r in records if "loss" in r]
    assert losses and all(np.isfinite(l) for l in losses)


@pytest.mark.slow
def test_watchdog_exits_stalled_single_process_run(tmp_path):
    """CLI watchdog drill that runs on ANY backend: the (single) process
    wedges after epoch 0 (DDP_TPU_FAULT stall) and the watchdog must
    hard-exit 124 well under the 300 s graceful-shutdown ride."""
    env = _clean_env(8)
    env[faults.FAULT_ENV] = "stall@epoch=0,secs=600"
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "multigpu.py", "3", "1", "--batch_size", "4",
         "--synthetic", "--model", "deepnn", "--lr", "0.05",
         "--synthetic_size", "64", "--watchdog_secs", "15",
         "--snapshot_path", str(tmp_path / "wd.pt")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    elapsed = time.monotonic() - t0
    assert out.returncode == WATCHDOG_EXIT_STATUS, (out.stdout[-2000:],
                                                    out.stderr[-2000:])
    assert "WATCHDOG" in out.stderr
    assert elapsed < 240


@pytest.mark.slow
def test_watchdog_unsticks_stalled_two_process_run(tmp_path):
    """Acceptance: a stalled rank in a 2-process CPU run must NOT hang its
    peer for the 300 s graceful-shutdown timeout — the healthy rank's
    watchdog fires well under it, exits 124, and tears the coordination
    service down non-blockingly."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"localhost:{s.getsockname()[1]}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["MH_NUM_PROCESSES"] = "2"
    env["MH_LOCAL_DEVICES"] = "4"
    # Rank 1 wedges after epoch 1; rank 0's 15 s watchdog must fire while
    # it waits in the next cross-host collective.
    env[faults.FAULT_ENV] = "stall@epoch=1,rank=1,secs=600"
    worker = os.path.join(_REPO, "tests", "_mh_worker.py")
    ckpt = str(tmp_path / "mh.pt")
    t0 = time.monotonic()
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), coord, ckpt, "cli_watchdog"],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT) for pid in range(2)]
    try:
        out0 = procs[0].communicate(timeout=240)[0].decode()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    elapsed = time.monotonic() - t0
    assert procs[0].returncode == WATCHDOG_EXIT_STATUS, out0[-3000:]
    assert "WATCHDOG" in out0
    assert elapsed < 240  # well under the 300 s graceful-shutdown ride
