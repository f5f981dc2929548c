"""Real-CIFAR-10 acceptance run, gated on data presence (VERDICT r3 #4).

The reference's actual acceptance check is the final accuracy print after a
real 20-epoch run (/root/reference/singlegpu.py:248-249, multigpu.py:247-248).
This box has zero egress and no cached dataset, so the test skips here with
a reason — but the moment the official ``cifar-10-batches-py`` files appear
under ``data/cifar10/`` in any future environment, the reference-config run
executes and the accuracy band is asserted for free.

The run happens in a SUBPROCESS with the conftest's CPU pinning stripped, so
it uses the environment's real accelerator (the conftest pins THIS process
to an 8-device virtual CPU mesh, which would turn 20 real epochs into
hours).
"""
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BATCH_DIR = os.path.join(_REPO, "data", "cifar10", "cifar-10-batches-py")
_FILES = [f"data_batch_{i}" for i in range(1, 6)] + ["test_batch"]
_PRESENT = all(os.path.exists(os.path.join(_BATCH_DIR, f)) for f in _FILES)


@pytest.mark.skipif(
    not _PRESENT,
    reason="real CIFAR-10 not present (this box has no egress); put the "
           f"official cifar-10-batches-py files under {_BATCH_DIR} to run "
           "the reference-config acceptance check")
def test_reference_config_20_epoch_accuracy():
    """The reference-exact invocation (multigpu.py argv: 20 epochs,
    save_every 5, batch 512) on the real dataset must land in the
    established band for this VGG-11 recipe: the reference trains to
    ~92-94% test accuracy, so anything in [90, 96] is parity and anything
    outside is a real regression (or a data problem)."""
    # Strip the conftest's CPU default: the reference-exact run goes to
    # whatever backend the machine has.
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = _REPO
    snapshot = os.path.join(_REPO, "tests", ".acceptance_ck.pt")
    out = subprocess.run(
        [sys.executable, "multigpu.py", "20", "5", "--batch_size", "512",
         "--snapshot_path", snapshot],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=5400)
    if os.path.exists(snapshot):
        os.unlink(snapshot)
    assert out.returncode == 0, out.stderr[-3000:]
    m = re.search(r"fp32 model has accuracy=([0-9.]+)%", out.stdout)
    assert m, out.stdout[-3000:]
    acc = float(m.group(1))
    assert 90.0 <= acc <= 96.0, (
        f"reference-config accuracy {acc:.2f}% outside the established "
        "92-94% band (±2 margin) for this recipe")
