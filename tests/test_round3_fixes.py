"""Round-3 hardening: resident HBM-budget guard, bf16 evaluation,
honest bf16 bench baseline, and the spawn-abbreviation strip (VERDICT r2
#3/#5/#6, ADVICE r2 #1)."""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_tpu import cli
from ddp_tpu.data import EvalLoader, synthetic
from ddp_tpu.models import get_model
from ddp_tpu.parallel import make_mesh
from ddp_tpu.train import evaluate


def test_resident_rejects_dataset_beyond_hbm_budget(monkeypatch):
    """A dataset that cannot fit the per-device HBM budget must fail with
    instructions BEFORE any upload (VERDICT r2 #6) — not as a raw XLA OOM
    mid-upload.  The device-capacity probe is mocked: the CPU backend
    reports no limit."""
    import ddp_tpu.data.resident as resident_mod

    ds, _ = synthetic(n_train=64)
    mesh = make_mesh(2)
    needed = (np.ascontiguousarray(ds.images).nbytes
              + np.ascontiguousarray(ds.labels, dtype=np.int32).nbytes)

    uploads = []
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: uploads.append(1) or
                        jax.numpy.zeros(()))
    monkeypatch.setattr(resident_mod, "_device_bytes_limit",
                        lambda d: int(needed / resident_mod.
                                      HBM_BUDGET_FRACTION) - 1)
    with pytest.raises(ValueError, match="Drop --resident"):
        resident_mod.ResidentData(ds, mesh)
    assert not uploads  # failed before touching the device

    # Exactly at the budget: accepted (and on a backend with no reported
    # limit — the real CPU path — the guard stays out of the way).
    monkeypatch.undo()
    for limit in [int(needed / resident_mod.HBM_BUDGET_FRACTION) + 1, None]:
        monkeypatch.setattr(resident_mod, "_device_bytes_limit",
                            lambda d, _l=limit: _l)
        res = resident_mod.ResidentData(ds, mesh)
        assert res.images.row_shape == ds.images.shape[1:]
        assert res.images.data.size == ds.images.size
        monkeypatch.undo()


def test_device_bytes_limit_probe():
    """The capacity probe returns an int (backends with memory_stats) or
    None (CPU backend / mocked failures) — never raises."""
    from ddp_tpu.data.resident import _device_bytes_limit

    got = _device_bytes_limit(jax.devices()[0])
    assert got is None or (isinstance(got, int) and got > 0)

    class Broken:
        def memory_stats(self):
            raise NotImplementedError

    class Empty:
        def memory_stats(self):
            return None

    class Reporting:
        def memory_stats(self):
            return {"bytes_limit": 123}

    assert _device_bytes_limit(Broken()) is None
    assert _device_bytes_limit(Empty()) is None
    assert _device_bytes_limit(Reporting()) == 123


def test_cli_eval_computes_in_trained_precision(tmp_path, monkeypatch):
    """--bf16 must reach evaluation (VERDICT r2 weak #3): the reference
    evaluates the very model it trained (multigpu.py:247), so a bf16 CLI
    run's eval computes in bf16 — asserted by spying the compute_dtype the
    CLI hands to evaluate(), for both the streaming and resident paths."""
    seen = []
    real_evaluate = cli.evaluate

    def spy(model, params, stats, loader, mesh, *, compute_dtype=None,
            progress=True):
        seen.append(compute_dtype)
        return real_evaluate(model, params, stats, loader, mesh,
                             compute_dtype=compute_dtype, progress=progress)

    monkeypatch.setattr(cli, "evaluate", spy)
    monkeypatch.chdir(tmp_path)
    args = cli.build_parser("t").parse_args(
        ["1", "100", "--batch_size", "8", "--synthetic", "--model", "deepnn",
         "--lr", "0.05", "--num_devices", "2", "--synthetic_size", "32",
         "--bf16", "--snapshot_path", "none.pt"])
    acc_bf16 = cli.run(args, num_devices=None)
    assert seen == [jnp.bfloat16]
    assert 0.0 <= acc_bf16 <= 100.0

    from ddp_tpu.train.evaluate import evaluate_resident

    seen_res = []
    real_res = evaluate_resident

    def spy_res(model, params, stats, resident, loader, mesh, *,
                compute_dtype=None):
        seen_res.append(compute_dtype)
        return real_res(model, params, stats, resident, loader, mesh,
                        compute_dtype=compute_dtype)

    # ddp_tpu.train re-exports the evaluate FUNCTION under the submodule's
    # name, so attribute-style import resolves to the function; grab the
    # real submodule from sys.modules.
    import sys
    eval_mod = sys.modules["ddp_tpu.train.evaluate"]
    monkeypatch.setattr(eval_mod, "evaluate_resident", spy_res)
    args2 = cli.build_parser("t").parse_args(
        ["1", "100", "--batch_size", "8", "--synthetic", "--model", "deepnn",
         "--lr", "0.05", "--num_devices", "2", "--synthetic_size", "32",
         "--bf16", "--resident", "--snapshot_path", "none2.pt"])
    cli.run(args2, num_devices=None)
    assert seen_res == [jnp.bfloat16]


def test_eval_bf16_close_to_fp32():
    """bf16 evaluation stays within tolerance of fp32 evaluation on the
    same weights (the accuracy metric is argmax-based, so bf16 rounding
    only moves samples whose top-2 logits nearly tie)."""
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(0))
    _, test_ds = synthetic(n_train=8, n_test=64)
    mesh = make_mesh(2)
    loader = EvalLoader(test_ds, 16, 2)
    acc32 = evaluate(model, params, stats, loader, mesh, progress=False)
    accbf = evaluate(model, params, stats, loader, mesh,
                     compute_dtype=jnp.bfloat16, progress=False)
    assert abs(acc32 - accbf) <= 5.0  # 64 samples -> <= ~3 tied flips


def test_bench_bf16_record_has_no_unmeasured_baseline():
    """No chip measurement exists on the installed stack, so a record's
    vs_baseline is 1.0 — never a ratio against an unrecorded constant
    (ROADMAP A1 supplies the measured denominator)."""
    import bench

    args = argparse.Namespace(
        model="deepnn", batch_size=4, steps=1, warmup=1, repeats=1,
        num_devices=2, dispatch="step", profile_dir=None,
        shard_update=False)
    rec = bench._bench_step(args, bf16=True, extras=False)[0]
    assert rec["vs_baseline"] == 1.0
    assert not hasattr(bench, "BASELINE_BENCH_BF16")
    assert "bf16" in rec["metric"]


def test_bench_step_shard_update_mode():
    """--shard_update benches the ZeRO step (reduce-scatter + sharded SGD +
    all-gather) — the composed mode the scaling sweep forwards to children
    (VERDICT r2 #8)."""
    import bench

    args = argparse.Namespace(
        model="deepnn", batch_size=4, steps=1, warmup=1, repeats=1,
        num_devices=2, dispatch="step", profile_dir=None,
        shard_update=True)
    rec = bench._bench_step(args, bf16=False, extras=False)[0]
    assert "zero-sharded update" in rec["metric"]
    assert rec["value"] > 0
    # No recorded baseline constant exists for the zero step: a ratio
    # against the replicated-step constant would misread as regression.
    assert rec["vs_baseline"] == 1.0


def test_sweep_forwards_composed_mode_flags(monkeypatch):
    """The sweep must pass --shard_update / --resident through to its
    children (VERDICT r2 #8) — asserted on the constructed child argv, no
    subprocess compile cost."""
    import bench

    calls = []

    class FakeOut:
        returncode = 0
        stdout = json.dumps({"value": 1.0}) + "\n"
        stderr = ""

    def fake_run(child, env=None, capture_output=None, text=None):
        calls.append(child)
        return FakeOut()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    args = argparse.Namespace(
        model="deepnn", batch_size=4, steps=1, warmup=1, repeats=1,
        sweep="1,2", sweep_platform="cpu", dispatch="step", bf16=False,
        shard_update=True, resident=True, e2e=False, e2e_steps=4)
    bench._bench_sweep(args)
    assert len(calls) == 2
    for child in calls:
        assert "--shard_update" in child
        assert "--resident" in child and "--e2e" in child

    # Host-fed e2e (--e2e without --resident) must ride through too.
    calls.clear()
    args.shard_update, args.resident, args.e2e = False, False, True
    bench._bench_sweep(args)
    for child in calls:
        assert "--e2e" in child and "--resident" not in child


def test_sweep_tolerates_stdout_chatter(monkeypatch, capsys):
    """ADVICE r2: a child that prints library chatter before its JSON line
    must not crash the sweep — the first cleanly-parsing line wins."""
    import bench

    class ChattyOut:
        returncode = 0
        # Plain chatter, VALID-json-but-not-a-record chatter (a bare
        # number parses cleanly and must not be taken as the record), an
        # unrelated dict, then the real record.
        stdout = ("some library banner\n100\n" + json.dumps({"x": 1})
                  + "\n" + json.dumps({"value": 2.5}) + "\n")
        stderr = ""

    monkeypatch.setattr(bench.subprocess, "run",
                        lambda *a, **k: ChattyOut())
    args = argparse.Namespace(
        model="deepnn", batch_size=4, steps=1, warmup=1, repeats=1,
        sweep="1", sweep_platform="cpu", dispatch="step", bf16=False,
        shard_update=False, resident=False, e2e=False, e2e_steps=4)
    bench._bench_sweep(args)
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["samples_per_sec_per_chip"] == {"1": 2.5}


def test_spawn_strips_every_abbreviation(monkeypatch):
    """ADVICE r2: argparse (allow_abbrev) accepts --sp/--spa/--spaw for
    --spawn; every spelling must be stripped from the re-exec'd child argv
    or children would fork recursively."""
    spawned = []

    class FakeProc:
        def wait(self):
            return 0

    def fake_popen(cmd, env=None):
        spawned.append((cmd, env))
        return FakeProc()

    import subprocess as sp
    monkeypatch.setattr(sp, "Popen", fake_popen)
    for spelling in (["--sp", "2"], ["--spa", "2"], ["--spaw", "2"],
                     ["--spawn", "2"], ["--spawn=2"], ["--sp=2"]):
        spawned.clear()
        monkeypatch.setattr("sys.argv",
                            ["multigpu.py", "2", "1", *spelling, "--lr",
                             "0.1"])
        rc = cli.spawn_local(2)
        assert rc == 0 and len(spawned) == 2
        for cmd, env in spawned:
            argv = cmd[2:]  # strip interpreter + script
            assert argv == ["2", "1", "--lr", "0.1"], (spelling, cmd)
            assert env["DDP_TPU_NUM_PROCESSES"] == "2"


def test_synthetic_label_noise_knob():
    """The non-saturated-regime knob for accuracy-parity recordings:
    ``label_noise=p`` relabels ~0.9*p of each split uniformly at random
    (a redraw matches the original label 1/10 of the time), deterministic
    in the seed, and leaves the images of the SAME split bit-identical to
    the noise-free dataset (flips are drawn after the split's pixels)."""
    clean_train, _ = synthetic(n_train=2048, seed=7)
    a_train, a_test = synthetic(n_train=2048, seed=7, label_noise=0.25)
    b_train, b_test = synthetic(n_train=2048, seed=7, label_noise=0.25)

    np.testing.assert_array_equal(a_train.images, b_train.images)
    np.testing.assert_array_equal(a_train.labels, b_train.labels)
    np.testing.assert_array_equal(a_test.labels, b_test.labels)

    np.testing.assert_array_equal(a_train.images, clean_train.images)
    frac = (a_train.labels != clean_train.labels).mean()
    assert 0.15 < frac < 0.30, frac  # E = 0.9 * 0.25 = 0.225

    # Flips ride an independent stream: the TEST split's images and clean
    # labels are also bit-identical across noise settings, so the noisy
    # dataset's empirical accuracy ceiling is measurable as agreement
    # with the clean counterpart.
    clean_test = synthetic(n_train=2048, seed=7)[1]
    np.testing.assert_array_equal(a_test.images, clean_test.images)
    ceiling = (a_test.labels == clean_test.labels).mean()
    assert 0.70 < ceiling < 0.85, ceiling

    # Default stays the exact pre-knob dataset (artifact compatibility).
    d_train, _ = synthetic(n_train=2048, seed=7, label_noise=0.0)
    np.testing.assert_array_equal(d_train.labels, clean_train.labels)


def test_momentum_weight_decay_flags_reach_sgd_config(monkeypatch):
    """--momentum/--weight_decay expose the reference's hardcoded SGD
    constants (multigpu.py:131-133) as defaulted flags, completing the
    config-system claim in PARITY.md.  Wiring test: the parsed values
    must arrive in the Trainer's SGDConfig."""
    captured = {}

    class _Spy(Exception):
        pass

    def fake_trainer(*a, **kw):
        captured.update(kw)
        raise _Spy()

    monkeypatch.setattr(cli, "Trainer", fake_trainer)
    args = cli.build_parser("t").parse_args(
        ["1", "1", "--synthetic", "--synthetic_size", "64",
         "--batch_size", "8", "--num_devices", "2",
         "--momentum", "0.5", "--weight_decay", "0.01"])
    with pytest.raises(_Spy):
        cli.run(args, num_devices=None)
    cfg = captured["sgd_config"]
    assert cfg.momentum == 0.5 and cfg.weight_decay == 0.01
    assert cfg.lr == 0.4

    d = cli.build_parser("t").parse_args(["1", "1"])
    assert d.momentum == 0.9 and d.weight_decay == 5e-4


def test_conv_probe_flops_and_shapes():
    """conv_probe's FLOP accounting and shape table stay consistent with
    the VGG architecture (the emitter analysis rests on them): 8 convs
    total, spatial sizes halving at each pool, and the
    summed fwd FLOPs matching the known ~1.2 GFLOP/sample VGG forward
    at batch 1."""
    from ddp_tpu.ops.conv_probe import VGG_CONV_SHAPES, conv_flops

    assert sum(reps for *_s, reps in VGG_CONV_SHAPES) == 8
    fwd = sum(conv_flops(1, h, cin, cout) * reps
              for h, cin, cout, reps in VGG_CONV_SHAPES)
    # 3.6 GFLOP/sample trained = 3x forward.
    assert 1.0e9 < fwd < 1.4e9, fwd
    # Spatial sizes follow the pool structure of VGG.ARCH.
    assert [h for h, *_ in VGG_CONV_SHAPES] == [32, 32, 16, 16, 8, 8, 4]
