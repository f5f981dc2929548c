"""Run-wide telemetry (observability) subsystem.

- ``tracer``    — low-overhead span tracer every hot path reports into
                  (bounded ring + JSONL spill; ``--obs_off`` = no-op).
- ``startup``   — JAX's preparation of every executable as spans of the
                  attached tracer, and the process's age on its clock.
- ``export``    — Perfetto ``trace_event`` export + the terminal reports
                  behind ``python -m ddp_tpu.obs``.
- ``live``      — rolling live stats (median/p90 step time, samples/sec,
                  MFU, prefetch occupancy) through MetricsLogger.
- ``aggregate`` — cross-host per-phase straggler attribution.
"""
from .tracer import NullTracer, SpanTracer, get_tracer, set_tracer

__all__ = ["NullTracer", "SpanTracer", "get_tracer", "set_tracer"]
