"""What the five readers of set-up share: the program's own timeline
BEFORE the window.  The runners hand readers ``ctx["spans"]``, the
window's spans only; readers run in the Trainer's process, so the rest
is asked of the program itself (``ddp_tpu.obs.startup.timeline()``: the
attached tracer's spans since its construction, among them JAX's
preparation of every executable as ``prepare_trace``, ``prepare_lower``,
``prepare_compile``).  None where the program keeps no such timeline (a
program from before ``obs/startup.py``) or no tracer was attached; a
reader that finds a timeline and nothing of its kind on it reads 0.0."""
PREPARE_PHASES = ("prepare_trace", "prepare_lower", "prepare_compile")


def before_window(ctx):
    """``(spans, zero_age_s)``: the timeline's spans that start before
    the first of the window's (same clock), and the process's age at that
    clock's zero; or None."""
    try:
        from ddp_tpu.obs import startup
    except ImportError:
        return None
    found = startup.timeline()
    if found is None:
        return None
    spans, zero_age_s = found
    opens_at = min((s["start_s"] for s in ctx["spans"]),
                   default=float("inf"))
    return [s for s in spans if s["start_s"] < opens_at], zero_age_s

