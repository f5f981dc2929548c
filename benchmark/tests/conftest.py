"""The benchmark's own tests, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 suite (``tests/``).  A
rehearsal of a cell takes 20 to 80 s on a CPU, the whole directory a few
minutes.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_cell(workload: str, *extra: str, chips: int = 1, root: str = ROOT,
             pythonpath: str = ""):
    """``run.py`` of the tree at ``root`` as the driver starts it, on
    ``chips`` virtual CPU devices; returns (returncode, last stdout line
    parsed or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         workload, "--seed", "3", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and proc.returncode == 0 else None
    return proc.returncode, result, proc.stderr
