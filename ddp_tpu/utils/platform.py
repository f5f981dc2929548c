"""Where a process runs and where it keeps what it builds.

``JAX_PLATFORMS`` is the one way to choose a backend and
``JAX_COMPILATION_CACHE_DIR`` the one way to place the compile cache;
nothing here overrides either.  The four entry points (cli.py,
serve/__main__.py, train/lm.py, bench.py) share:

- :func:`enable_compile_cache` — called before the first compile;
- :func:`device_line` — the one start-up line saying where the program
  runs (``chip_smoke.py`` parses it);
- :func:`cpu_device_env` — the child environment for an N-wide virtual
  CPU mesh (bench sweeps, the ``--spawn`` drills, the driver dry run).

What the program builds at run time (compiled executables, the native
augment kernel) lives inside the checkout, so a sealed machine that
throws the home directory away still finds it on the next process.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

# jax is imported by the two functions that need it: the path constants
# below are read by modules that have no other use for it (data/native.py).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(CHECKOUT, ".jax_cache")
NATIVE_BUILD_DIR = os.path.join(CHECKOUT, ".native_cache")

_DEVCOUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+")


def enable_compile_cache() -> None:
    """Persistent XLA compile cache.  If ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX bound it at import and nothing is done; otherwise the cache
    is ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what makes a later process find the entries."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)


def device_line(mesh, **fields) -> str:
    """``device: platform=… device_kind="…" visible=N mesh=axis=n,… ids=…``
    for the mesh a program is about to run on, plus caller ``fields``
    (``key=value``, appended in order)."""
    import jax
    devs = list(mesh.devices.flat)
    parts = [
        f"platform={devs[0].platform}",
        f'device_kind="{devs[0].device_kind}"',
        f"visible={len(jax.devices())}",
        "mesh=" + ",".join(f"{a}={n}" for a, n in mesh.shape.items()),
        "ids=" + ",".join(str(d.id) for d in devs),
    ]
    if jax.process_count() > 1:
        parts.append(f"process={jax.process_index()}/{jax.process_count()}")
    parts += [f"{k}={v}" for k, v in fields.items()]
    return "device: " + " ".join(parts)


def cpu_device_env(n_devices: int,
                   base_env: Optional[Dict[str, str]] = None
                   ) -> Dict[str, str]:
    """Child-process environment for an ``n_devices``-wide virtual CPU
    mesh: ``JAX_PLATFORMS=cpu`` and exactly one
    ``--xla_force_host_platform_device_count`` flag."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _DEVCOUNT_RE.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    return env
