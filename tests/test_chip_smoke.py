"""chip_smoke.py's phase runner at tiny size on the CPU backend, and the
script itself refusing a machine without a chip."""
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_runner_tiny_on_cpu(tmp_path, monkeypatch):
    """The real commands as children — gather check, the attention kernel's
    and both models' scan kernels' checks (off the chip: through the
    interpreter), the routed experts' products over the live row tiles
    against the every-tile form,
    a step of the SambaY stage (off the chip: at the tiny width alone),
    train + checkpoint + eval, the same train again adding nothing to the
    compile cache,
    serve + /predict + SIGTERM drain, resume at epoch 1 — with every check
    of the smoke applied.  The expected platform is this test's argument;
    ``python chip_smoke.py`` itself always expects tpu."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    # A cache of this run's own: the suite's is written by every other
    # worker at once, and "the second child adds no entries" counts files.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    result = chip_smoke.run_smoke(
        "cpu", model="deepnn", batch=8, out=str(tmp_path / "out"),
        phases=("gather", "attention", "ssd", "selscan", "moe", "sambay",
                "train", "train_again", "serve", "resume"),
        loss_band=(2.0, 2.7))
    assert result == {"ok": True, "device": {"platform": "cpu",
                                             "kind": "cpu", "count": 1}}
    assert (tmp_path / "out" / "train.pt").exists()


def test_train_again_refuses_an_empty_cache(tmp_path):
    """"The second child adds no entries" must not pass of no cache at
    all: an empty directory after the first train child is a failure,
    before the second child is even started."""
    s = chip_smoke.Smoke("cpu", "deepnn", 8, str(tmp_path))
    s.cache_dir = str(tmp_path / "nobody_writes_here")
    s.train_dp = lambda tag: pytest.fail("second child was started")
    with pytest.raises(chip_smoke.SmokeFailure, match="is empty after"):
        s.train_again()


def test_summary_refuses_an_empty_cache(tmp_path, monkeypatch):
    """A run whose cache directory holds nothing at the end fails, so a
    cold run must have added entries to report success."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "empty"))
    with pytest.raises(chip_smoke.SmokeFailure, match="is empty after"):
        chip_smoke.run_smoke("cpu", out=str(tmp_path / "out"), phases=())


def test_script_fails_without_a_chip():
    """No accelerator (this sandbox): non-zero exit, no result line, and
    the failing command named — JAX_PLATFORMS=cpu in the caller's
    environment does not turn the smoke into a CPU run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"ok"' not in out.stdout
    assert "FAILED: exit code" in out.stdout
    assert "ddp_tpu.ops.gather" in out.stdout
