"""Executables the training loop prepared on its own first calls: the
``prepare_compile`` spans before the window whose innermost enclosing
span of the program is a ``dispatch`` (compiled or read back alike).  A
shape costs one; the program's double preparation of its step or epoch
program (ROADMAP A2) shows as the same executable twice.  0.0 where a
launcher prepared the step itself, outside any ``dispatch``."""
from benchmark.layer_metrics import _startup


def read(ctx):
    found = _startup.before_window(ctx)
    if found is None:
        return None
    from ddp_tpu.obs.export import span_parents
    spans = found[0]
    return float(sum(
        s["phase"] == "prepare_compile" and parent is not None
        and spans[parent]["phase"] == "dispatch"
        for s, parent in zip(spans, span_parents(spans))))
