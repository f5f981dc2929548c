"""Process age at the start of the program's first span (``trainer_init``
in the benchmark's runs): interpreter, imports, the backend's start, and
whatever the launcher did first (data and weights from ``--seed``; in the
token cells the plain reference too).  The part of ``setup_s`` that lies
before anything the program times."""
from benchmark.layer_metrics import _startup


def read(ctx):
    found = _startup.before_window(ctx)
    if found is None:
        return None
    spans, zero_age_s = found
    return zero_age_s + min((s["start_s"] for s in spans + ctx["spans"]),
                            default=0.0)
