"""Set-up on the program's own timeline: JAX's preparation of every
executable as spans of the attached :class:`~ddp_tpu.obs.tracer.SpanTracer`,
and the process's age at that tracer's zero.

``jax.monitoring`` publishes, for every executable JAX prepares, a time
span for tracing, for lowering and for the backend's compile (the last
holds the read from the persistent compilation cache, announced by a
``cache_hits`` event inside it).  :func:`attach` makes each a span of the
tracer — ``prepare_trace``, ``prepare_lower``, ``prepare_compile``, each
with the executable's ``name``; ``prepare_compile`` carries ``n``: 1
where the backend compiled, 0 where the executable was read back — so a
slow start reads from the same spill, phase table and Perfetto export as
a slow step (RUNBOOK section 7).  Which span of the program caused a
preparation is decided afterwards from the intervals
(:func:`~ddp_tpu.obs.export.span_parents`): a ``dispatch``,
``trainer_init``, or none where a launcher calls a jitted function
itself.

A jitted function traced INSIDE another's trace (every ``jnp`` operation
of a step's body is one: thousands an executable) reports its own trace
first; only the outermost is recorded, whose interval holds them all.

Nothing is kept here: no ring, no file.  The listeners are registered
once a process, at the first :func:`attach`, forward to the tracer
attached last (held weakly) and do nothing when it is gone.  A run whose
tracer is a ``NullTracer`` never imports this module, so it registers
nothing and reads no clock.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from typing import List, Optional, Tuple

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
PHASE_OF = {
    TRACE_EVENT: "prepare_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "prepare_lower",
    "/jax/core/compile/backend_compile_duration": "prepare_compile",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _process_start() -> float:
    """``time.monotonic()`` at this process's start, from the kernel's
    record of it (``/proc/self/stat`` field 22, in clock ticks since
    boot), so that interpreter start-up and imports are on the timeline;
    this module's import where the kernel's record cannot be read."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return now - (time.clock_gettime(time.CLOCK_BOOTTIME)
                      - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now


PROCESS_START = _process_start()

_attached: Optional[weakref.ref] = None  # the tracer attached last
_serial_thread: Optional[int] = None  # the thread that attached it
_registered = False
# A thread's own: ``hit_at``, time.time() of its newest cache_hits event
# (JAX sends it inside the compile's interval and the interval itself at
# its end); ``tracing``, the traces it has begun and not ended.
_thread = threading.local()


def attach(tracer) -> None:
    """Send JAX's preparation spans to ``tracer`` from now on.  Call it
    on the thread that will drive the jitted programs: a preparation on
    another thread is recorded ``overlap``."""
    global _attached, _serial_thread, _registered
    _attached = weakref.ref(tracer)
    _serial_thread = threading.get_ident()
    if not _registered:
        from jax import monitoring
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_start)
        _registered = True


def _tracer():
    return _attached() if _attached is not None else None


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT and _tracer() is not None:
        _thread.hit_at = time.time()


def _on_start(event: str, _start_time: float, **_kw) -> None:
    # JAX announces the start of each of the three as a scalar.
    if event == TRACE_EVENT:
        _thread.tracing = getattr(_thread, "tracing", 0) + 1


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kw) -> None:
    phase = PHASE_OF.get(event)
    if phase is None:
        return
    if event == TRACE_EVENT:
        _thread.tracing = max(getattr(_thread, "tracing", 1) - 1, 0)
        if _thread.tracing:
            return  # traced inside another, whose span holds this one
    tracer = _tracer()
    if tracer is None:
        return
    # JAX read time.time(); the tracer's clock is time.monotonic().  This
    # call is the span's end, so one pair of readings moves it across.
    wall_to_monotonic = time.monotonic() - time.time()
    n = None
    if phase == "prepare_compile":
        hit_at = getattr(_thread, "hit_at", None)
        n = 0 if (hit_at is not None
                  and start_time <= hit_at <= end_time) else 1
    tracer.add_span(phase, start_time + wall_to_monotonic,
                    end_time - start_time,
                    overlap=threading.get_ident() != _serial_thread,
                    n=n, name=kw.get("fun_name"))


def timeline() -> Optional[Tuple[List[dict], float]]:
    """The attached tracer's spans since its construction and the
    process's age, in seconds, at that tracer's zero (a span's ``start_s``
    plus it is the process's age at the span's start); None where no
    tracer was attached or it is gone.  For a reader that is handed a
    window's spans only and runs in the Trainer's process."""
    tracer = _tracer()
    if tracer is None:
        return None
    return tracer.spans_since(float("-inf")), tracer.t0 - PROCESS_START
