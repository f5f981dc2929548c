"""Executables the backend COMPILED during set-up and did not read back
from the persistent cache: the sum of ``n`` over the ``prepare_compile``
spans before the window.  0.0 on a warm cache; above it, the run's
``setup_s`` was a cold reading, in whole or in part."""
from benchmark.layer_metrics import _startup


def read(ctx):
    found = _startup.before_window(ctx)
    if found is None:
        return None
    return float(sum(s["n"] or 0 for s in found[0]
                     if s["phase"] == "prepare_compile"))
