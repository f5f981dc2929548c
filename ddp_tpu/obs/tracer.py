"""Low-overhead span tracer — the one clock every hot path reports into.

The reference's only telemetry is an epoch-header print and two
``time.time()`` calls (SURVEY.md §5); before this subsystem our own
replacements were fragmented (``MetricsLogger`` scalars, offline xplane
analysis, ``PrefetchStats`` counters, one-off attribution math in
bench.py) and none could answer "where did step 4817 go" on a live run.
A :class:`SpanTracer` records one *span* per phase occurrence —

    with tracer.span("h2d", step=s):
        shard_batch(batch, mesh)

— with ``time.monotonic()`` timestamps (NTP/clock-jump safe, same basis
as the watchdog), into a bounded in-memory ring (the watchdog's
last-completed-span stall report and the per-epoch straggler aggregation
read it) and, when a spill path is given, as append-only JSON lines the
offline tooling consumes (``python -m ddp_tpu.obs``: phase breakdown,
step histogram, slowest-K, Perfetto export — obs/export.py).

Phases are free-form strings; the canonical training phases live in
:data:`~ddp_tpu.obs.export.PHASE_ORDER` (the set-up phases backend_start
.. trainer_init and JAX's prepare_trace, prepare_lower, prepare_compile,
which obs/startup.py records; then epoch_setup, data_wait,
host_augment, h2d, dispatch, epoch_close, loss_flush, ckpt_write, eval).
A span may carry two counts, ``n`` (items: samples, losses) and
``nbytes``, given to ``span()`` or, where they are known only inside the
body, through ``count()`` on the open span; a site computes a count that
costs anything only if ``tracer.enabled``.  ``overlap=True`` marks spans
recorded on *producer* threads (prefetch workers, the async checkpoint
writer) whose wall time hides behind the consumer loop — reports sum
only non-overlap spans when comparing against wall time, or concurrent
work would be double-counted.

Kill-switch contract (``--obs_off``): the module-level default tracer is
a :class:`NullTracer` whose ``span()`` returns one shared, reusable
no-op context manager — no allocation, no lock, no clock read — so
instrumented hot paths cost two trivial method calls when tracing is
off.  Spans are recorded only on *clean* exit: a span whose body raises
(including the ``StopIteration`` probe at iterator exhaustion) never
lands, which is also what makes "last completed span" the right stall
diagnostic.

Profiler mirror: while a ``jax.profiler`` session runs (``--profile_dir``,
``/debug/profile``, the benchmark's traced epochs), every span a
:class:`SpanTracer` times is also a host event ``ddp:<phase>`` of that
trace, on the thread that ran it, with ``step`` and the counts as its
arguments (one ``jax.profiler.TraceAnnotation`` entered and left with the
span).  With no session the price is one ``is_enabled()`` call a span.
``add_span`` records an interval that is already over, so it has no
event (the threaded engine's ``data_wait``, ``pp_bubble``, JAX's
preparation of an executable, which alone carries a ``name``).

Thread safety: producers (prefetch pool/thread, checkpoint writer) and
the consumer loop record concurrently; the ring, last-span table and
spill handle are guarded by one lock taken only *after* the body ran —
never around user code.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import IO, Dict, List, Optional


def default_spill_path(snapshot_path: str, filename: str) -> str:
    """Default spill location for a run: next to its checkpoint head,
    NOT the process CWD.  A bare-CWD default litters whatever directory
    the CLI happened to launch from (and once landed a spill in the repo
    root); anchoring on ``--snapshot_path`` puts the telemetry where the
    run's other artifacts live.  Explicit ``--trace_spill`` paths are
    always honored verbatim — this only fills the unset default."""
    head = os.path.dirname(snapshot_path)
    return os.path.join(head, filename) if head else filename


class _NullSpan:
    """Shared no-op context manager — the entire cost of a disabled span."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def count(self, n: Optional[int] = None,
              nbytes: Optional[int] = None) -> None:
        pass

    def end(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled tracer: every operation is a no-op (``--obs_off``)."""
    enabled = False

    def span(self, phase: str, step: Optional[int] = None,
             overlap: bool = False, req: Optional[str] = None,
             n: Optional[int] = None,
             nbytes: Optional[int] = None) -> _NullSpan:
        return _NULL_SPAN

    def add_span(self, phase: str, start_monotonic: float, dur_s: float,
                 step: Optional[int] = None, overlap: bool = False,
                 req: Optional[str] = None, n: Optional[int] = None,
                 nbytes: Optional[int] = None,
                 name: Optional[str] = None) -> None:
        pass

    def now(self) -> float:
        return 0.0

    def spans_since(self, t: float) -> List[dict]:
        return []

    def last_spans(self, lock_timeout: Optional[float] = None
                   ) -> Dict[str, dict]:
        return {}

    def describe_last(self, lock_timeout: Optional[float] = None) -> str:
        return ""

    def flush(self, fsync: bool = False,
              lock_timeout: Optional[float] = None) -> None:
        pass

    def close(self) -> None:
        pass


def _given(**kw) -> dict:
    """The arguments that were given: what a profiler event shows."""
    return {k: v for k, v in kw.items() if v is not None}


class _Span:
    """One in-flight span; records itself on clean ``__exit__`` only.

    Most sites use it as a context manager.  A phase that opens in one
    function and closes in another (``epoch_setup``: opened by the
    trainer, closed by the prefetch engine when it is built) enters it by
    hand and calls :meth:`end`."""
    __slots__ = ("_tracer", "phase", "step", "overlap", "req", "n",
                 "nbytes", "_start", "_event")

    def __init__(self, tracer: "SpanTracer", phase: str,
                 step: Optional[int], overlap: bool,
                 req: Optional[str] = None, n: Optional[int] = None,
                 nbytes: Optional[int] = None):
        self._tracer = tracer
        self.phase = phase
        self.step = step
        self.overlap = overlap
        self.req = req
        self.n = n
        self.nbytes = nbytes

    def __enter__(self) -> "_Span":
        # The profiler's event opens before the span's clock read and
        # closes after it, so the event holds the span.
        self._event = self._tracer._profiler_event(self)
        self._start = time.monotonic()
        return self

    def count(self, n: Optional[int] = None,
              nbytes: Optional[int] = None) -> None:
        """Counts known only inside the body (the bytes of an index
        matrix built under ``epoch_setup``)."""
        if n is not None:
            self.n = n
        if nbytes is not None:
            self.nbytes = nbytes
        if self._event is not None:
            self._event.set_metadata(**_given(n=n, nbytes=nbytes))

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.monotonic()
        if self._event is not None:
            self._event.__exit__(exc_type, exc, tb)
        if exc_type is None:  # an aborted body is not a completed phase
            self._tracer._record(self.phase, self.step, self._start,
                                 end - self._start, self.overlap, self.req,
                                 self.n, self.nbytes)
        return False

    def end(self) -> None:
        self.__exit__(None, None, None)


class SpanTracer:
    """Per-process span recorder: bounded ring + optional JSONL spill.

    ``host`` tags every record with this process's rank so multi-host
    spills merge into one timeline (one Perfetto process per host);
    pass ``jax.process_index()`` — the tracer asks jax for nothing but
    the profiler's annotation class (module docstring), and works
    without it.
    ``ring`` bounds in-memory retention (the spill file is the full
    record); ``t0`` (a ``time.monotonic()`` reading) anchors relative
    timestamps and defaults to construction time; ``cli.run`` passes the
    process's start, so its spill reads in process age.

    The spill is TRUNCATED per run (the same overwrite-in-place
    discipline as ``checkpoint.pt``): timestamps are relative to this
    tracer's ``t0``, so appending a second run's spans onto a first's
    would stack two timelines at t=0 and double-count every report
    built from the file.
    """

    enabled = True

    def __init__(self, spill_path: Optional[str] = None, *,
                 ring: int = 4096, host: int = 0,
                 t0: Optional[float] = None):
        self.host = int(host)
        self.spill_path = spill_path
        self._t0 = time.monotonic() if t0 is None else float(t0)
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._last: Dict[str, tuple] = {}
        self._f: Optional[IO[str]] = (open(spill_path, "w")
                                      if spill_path else None)
        try:  # here, not at import: ``python -m ddp_tpu.obs`` needs no jax
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = None
        self._annotation = TraceAnnotation

    # -- recording ---------------------------------------------------------

    def span(self, phase: str, step: Optional[int] = None,
             overlap: bool = False, req: Optional[str] = None,
             n: Optional[int] = None,
             nbytes: Optional[int] = None) -> _Span:
        return _Span(self, phase, step, overlap, req, n, nbytes)

    def _profiler_event(self, span: _Span):
        """The span's ``ddp:<phase>`` event in the running profiler
        session, entered; None when no session runs."""
        cls = self._annotation
        if cls is None or not cls.is_enabled():
            return None
        event = cls("ddp:" + span.phase,
                    **_given(step=span.step, n=span.n, nbytes=span.nbytes))
        event.__enter__()
        return event

    def add_span(self, phase: str, start_monotonic: float, dur_s: float,
                 step: Optional[int] = None, overlap: bool = False,
                 req: Optional[str] = None, n: Optional[int] = None,
                 nbytes: Optional[int] = None,
                 name: Optional[str] = None) -> None:
        """Record a span measured by the caller (``start_monotonic`` on
        the ``time.monotonic`` clock) — for sites that only know AFTER
        timing whether the interval was a real phase occurrence (e.g. the
        prefetch consumer's queue get, which may return the end-of-stream
        sentinel rather than a batch), or that are told of it afterwards
        (obs/startup.py; ``name``: the executable JAX prepared)."""
        self._record(phase, step, start_monotonic, dur_s, overlap, req,
                     n, nbytes, name)

    def _record(self, phase: str, step: Optional[int], start: float,
                dur: float, overlap: bool, req: Optional[str] = None,
                n: Optional[int] = None, nbytes: Optional[int] = None,
                name: Optional[str] = None) -> None:
        rec = (phase, step, start - self._t0, dur, overlap, req, n, nbytes,
               name)
        # Serialize OUTSIDE the lock: json.dumps is pure CPU on local
        # data, and holding the one shared lock through it would make
        # every producer thread contend on exactly the work being timed.
        body = {
            "phase": phase, "step": step,
            "start_s": round(rec[2], 6), "dur_s": round(dur, 6),
            "overlap": overlap, "host": self.host,
        }
        if req is not None:  # request-scoped spans only — lines stay lean
            body["req"] = req
        if n is not None:  # counts only where the site gave them
            body["n"] = n
        if nbytes is not None:
            body["nbytes"] = nbytes
        if name is not None:  # JAX's preparation spans only
            body["name"] = name
        line = (json.dumps(body) + "\n") if self._f is not None else None
        with self._lock:
            self._ring.append(rec)
            self._last[phase] = rec
            if line is not None and self._f is not None:
                try:
                    self._f.write(line)
                except OSError as e:
                    # Telemetry must never kill the run it observes: a
                    # disk-full/read-only spill mid-run (hours in) gets
                    # the same degrade-to-ring-only treatment cli.py
                    # applies when the spill cannot be OPENED — warn
                    # once, keep tracing in memory.
                    import sys
                    print(f"WARNING: span spill write failed ({e}); "
                          "dropping the spill file, tracing continues "
                          "in-memory only", file=sys.stderr)
                    try:
                        self._f.close()
                    except OSError:
                        pass
                    self._f = None

    # -- reading -----------------------------------------------------------

    @property
    def t0(self) -> float:
        """The tracer's zero as a ``time.monotonic()`` reading."""
        return self._t0

    def now(self) -> float:
        """Current time on the tracer's own clock (span ``start_s`` basis)
        — the window marker ``spans_since`` consumes."""
        return time.monotonic() - self._t0

    @staticmethod
    def _as_dict(rec: tuple) -> dict:
        phase, step, start, dur, overlap, req, n, nbytes, name = rec
        return {"phase": phase, "step": step, "start_s": start,
                "dur_s": dur, "overlap": overlap, "req": req,
                "n": n, "nbytes": nbytes, "name": name}

    def spans_since(self, t: float) -> List[dict]:
        """Completed spans whose start is at or after tracer-time ``t``
        (ring-bounded: at most the newest ``ring`` spans survive)."""
        with self._lock:
            return [self._as_dict(r) for r in self._ring if r[2] >= t]

    def last_spans(self, lock_timeout: Optional[float] = None
                   ) -> Dict[str, dict]:
        """Newest completed span per phase — the stall diagnostic.

        ``lock_timeout`` bounds the lock wait: the watchdog's expire path
        calls this while another thread may be WEDGED inside ``_record``
        (a spill write to a hung mount holds the lock), and the expire
        path must never block — it exists to escape exactly such stalls.
        On timeout the answer is empty rather than late."""
        if not self._lock.acquire(
                timeout=-1 if lock_timeout is None else lock_timeout):
            return {}
        try:
            return {p: self._as_dict(r) for p, r in self._last.items()}
        finally:
            self._lock.release()

    def describe_last(self, lock_timeout: Optional[float] = None) -> str:
        """One-line 'last completed span per phase' summary, newest first
        — what the watchdog prints per host when a run stalls."""
        last = sorted(self.last_spans(lock_timeout).values(),
                      key=lambda r: r["start_s"] + r["dur_s"], reverse=True)
        if not last:
            return "no spans completed"
        return "; ".join(
            f"{r['phase']}"
            + (f"[step {r['step']}]" if r["step"] is not None else "")
            + f" ended @{r['start_s'] + r['dur_s']:.3f}s "
            + f"({r['dur_s'] * 1e3:.2f} ms)"
            for r in last)

    # -- lifecycle ---------------------------------------------------------

    def flush(self, fsync: bool = False,
              lock_timeout: Optional[float] = None) -> None:
        """Flush the spill buffer; ``fsync=True`` additionally forces the
        bytes to disk — the preemption emergency-checkpoint path uses it
        so the span tail survives the SIGTERM that is about to land.
        ``lock_timeout`` (watchdog expire path) gives up rather than
        block behind a wedged writer."""
        if not self._lock.acquire(
                timeout=-1 if lock_timeout is None else lock_timeout):
            return
        try:
            if self._f is not None:
                try:
                    self._f.flush()
                    if fsync:
                        os.fsync(self._f.fileno())
                except OSError:
                    pass  # same never-kill-the-run rule as _record
        finally:
            self._lock.release()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                try:
                    self._f.close()  # flushes the buffered tail
                except OSError:
                    pass  # never-kill-the-run: same rule as _record/flush
                self._f = None

    def __enter__(self) -> "SpanTracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# Module-level tracer: hot paths that cannot take a tracer argument
# (evaluate(), save_checkpoint()) read this; cli.run installs the real
# tracer for the run's duration and restores the null one after.  The
# default being a NullTracer is the zero-overhead-when-disabled contract.
_tracer: object = NullTracer()


def get_tracer():
    return _tracer


def set_tracer(tracer) -> None:
    global _tracer
    _tracer = tracer if tracer is not None else NullTracer()
