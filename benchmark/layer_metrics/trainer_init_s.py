"""The ``trainer_init`` span: ``Trainer.__init__`` from where its tracer
is known (state built or restored, the resident table's upload, the step
builders).  0.0 where the timeline holds none before the window."""
from benchmark.layer_metrics import _startup


def read(ctx):
    found = _startup.before_window(ctx)
    if found is None:
        return None
    return float(sum(s["dur_s"] for s in found[0]
                     if s["phase"] == "trainer_init"))
