"""Background host->device prefetch — the streaming overlap engine.

The reference hides input-pipeline latency with ``pin_memory=True`` +
DataLoader worker processes (singlegpu.py:177); the TPU analogue here is a
thread pool that materialises (gather + augment) upcoming batches
concurrently, plus a device_put up to ``depth`` steps ahead of consumption,
so host augment, H2D transfer, and device compute pipeline instead of
serializing.  Loaders exposing ``materialize(k)`` (order-independent,
per-batch-seeded — ``TrainLoader``) get true parallel workers; any other
batch iterable falls back to a single pipelining thread.

Contracts the tests pin (tests/test_prefetch.py):

- **Order/equality**: the yielded stream is the loader's batches, in order,
  bit-for-bit — prefetch is a scheduling change, never a data change, at
  every depth/worker setting (including ``depth=0`` = no overlap, the
  plain-loop shape).
- **Clean shutdown**: abandoning the iterator (consumer exception, early
  ``break``, preemption unwinding the epoch loop) stops and joins the
  producer machinery — no thread left blocked on a queue, no pending
  future still materialising.  This is what lets the engine compose with
  the resilience paths (SIGTERM/watchdog) without leaking threads.
- **Error transparency**: a producer-side exception re-raises in the
  consumer, after shutdown.

``PrefetchStats`` (opt-in) attributes where streaming time goes — producer
host busy time (materialise + augment), H2D enqueue time, and consumer
wait time (the dispatch gap: how long the device-feeding loop sat waiting
for a batch that was not ready).  ``bench.py --stream_attr`` builds the
streaming-gap table from these plus the tracer's span record
(utils/profiling.py:attribute_streaming).

Telemetry (round 7): every stage also reports into the run's span tracer
(obs/tracer.py) — ``host_augment`` and ``h2d`` spans from wherever they
actually run, ``data_wait`` from the consumer's side of the queue.  Which
thread that is depends on the engine: the pool's workers materialise
(``host_augment``, ``overlap=True``: their time hides behind the consumer
loop) while its ``h2d`` runs on the CONSUMER thread, serial, after each
``data_wait``; the single pipelining thread does both (``overlap=True``);
at depth 0 both are the consumer's, serial.  Every ``h2d`` carries
``nbytes``, the host batch it ships.  The engine's ends are named too:
``on_ready`` fires when it is built, just before it first waits for a
batch (the trainer ends its ``epoch_setup`` span there), and its shutdown
(workers joined) is an ``epoch_close`` span at ``step0``.  ``step0``
anchors span step numbers at the trainer's global step so "where did step
4817 go" is answerable from the spill.  With the default NullTracer the
spans are shared no-op context managers — the ``--obs_off`` zero-overhead
contract.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, Optional

import numpy as np

from ..obs.tracer import get_tracer
from ..train.step import shard_batch

_DONE = object()
_ERROR = "__error__"


class PrefetchStats:
    """Thread-safe wall-time attribution counters for one streaming run.

    ``host_s``  — producer time materialising/augmenting batches (sums
    across pool workers, so it can exceed wall time when workers overlap);
    ``h2d_s``   — time in ``shard_batch`` (device_put enqueue; on CPU
    this is where the copy cost lands);
    ``wait_s``  — consumer time blocked waiting for a batch that was not
    ready: the measured pipeline bubble.  ``wait_s`` ~ 0 with the engine
    keeping up means the input pipeline is fully hidden behind compute —
    occupancy as a number, not an argument (VERDICT r5 next #4).

    ``registry`` (a :class:`~ddp_tpu.obs.registry.MetricsRegistry`)
    mirrors the four fields as function-backed ``ddp_prefetch_*``
    instruments — this object stays the source of truth; the registry
    reads it at scrape time.
    """

    def __init__(self, registry=None, metric_labels=None) -> None:
        self._lock = threading.Lock()
        self.host_s = 0.0   # analysis: shared-under(_lock)
        self.h2d_s = 0.0    # analysis: shared-under(_lock)
        self.wait_s = 0.0   # analysis: shared-under(_lock)
        self.batches = 0    # analysis: shared-under(_lock)
        if registry is not None:
            labels = dict(metric_labels or {})
            names = tuple(sorted(labels))
            for metric, help_, fn in (
                    ("ddp_prefetch_host_seconds_total",
                     "Producer time materialising/augmenting batches",
                     lambda: self.host_s),
                    ("ddp_prefetch_h2d_seconds_total",
                     "Host-to-device enqueue time",
                     lambda: self.h2d_s),
                    ("ddp_prefetch_wait_seconds_total",
                     "Consumer time blocked on an unready batch (the "
                     "pipeline bubble)",
                     lambda: self.wait_s),
                    ("ddp_prefetch_batches_total",
                     "Batches yielded to the consumer loop",
                     lambda: float(self.batches))):
                registry.counter(metric, help_,
                                 names).labels(**labels).set_function(fn)

    def _add(self, field: str, dt: float) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + dt)

    def count_batch(self) -> None:
        with self._lock:
            self.batches += 1

    def per_step_ms(self) -> Dict[str, float]:
        # Under the lock: pool workers are still adding while the epoch
        # summary reads, and the numbers must be one consistent snapshot
        # (a torn host_s/batches pair misattributes the bubble).
        with self._lock:
            n = max(self.batches, 1)
            return {"host_ms_per_step": round(self.host_s / n * 1e3, 3),
                    "h2d_enqueue_ms_per_step":
                        round(self.h2d_s / n * 1e3, 3),
                    "consumer_wait_ms_per_step":
                        round(self.wait_s / n * 1e3, 3),
                    "batches": self.batches}


def _nothing() -> None:
    pass


def prefetch_to_device(batches: Iterable[Dict[str, np.ndarray]], mesh,
                       depth: int = 2, workers: int = 4,
                       stats: Optional[PrefetchStats] = None,
                       shard_fn=None, tracer=None,
                       step0: int = 0, start: int = 0,
                       on_ready=_nothing) -> Iterator[dict]:
    """Yield device-resident, data-sharded batches ahead of consumption.

    ``depth`` is how many batches may be in flight beyond the workers'
    own hands (the bounded-queue size); ``depth=0`` disables overlap
    entirely — materialise + device_put inline, the unprefetched loop
    (bit-identical stream, pinned by tests).  ``workers`` only applies to
    loaders with ``materialize(k)`` random access.  ``shard_fn(batch,
    mesh)`` overrides the host->device placement (default
    :func:`~ddp_tpu.train.step.shard_batch`; the accumulation path passes
    ``shard_batch_stacked`` for its ``[A, B, ...]`` group stacks).
    ``tracer`` (default: the process tracer) receives host_augment/h2d/
    data_wait spans, step-numbered from ``step0``.  ``on_ready()`` is
    called once on the consumer thread, inside the first ``next()``, when
    the engine is built (pool or thread started, skipped prefix dropped)
    and about to produce its first batch.

    ``start`` fast-forwards the epoch to batch index ``start`` — the
    mid-epoch resume path (resilience/preemption): batches ``[0, start)``
    are never materialised for ``materialize(k)`` loaders (random access
    jumps straight to ``start``) and are materialised-but-dropped for
    plain iterators (no random access to skip with).  The yielded stream
    is bit-identical to the tail of the unoffset stream because batch
    content is a function of ``(seed, epoch, k)`` alone, never of which
    batches were consumed before it.
    """
    shard = shard_batch if shard_fn is None else shard_fn
    tracer = tracer if tracer is not None else get_tracer()
    start = max(int(start), 0)
    if depth <= 0:
        if start and hasattr(batches, "materialize") \
                and hasattr(batches, "__len__"):
            loader = batches  # bind NOW: the genexpr must not see itself
            batches = (loader.materialize(k)
                       for k in range(start, len(loader)))
            start = 0
        yield from _passthrough(iter(batches), mesh, stats, shard, tracer,
                                step0, start, on_ready)
    elif hasattr(batches, "materialize") and hasattr(batches, "__len__"):
        yield from _pooled(batches, mesh, depth, max(workers, 1), stats,
                           shard, tracer, step0, start, on_ready)
    else:
        yield from _threaded(iter(batches), mesh, depth, stats, shard,
                             tracer, step0, start, on_ready)


def _nbytes(tracer, batch) -> Optional[int]:
    """Bytes of a host batch, for its ``h2d`` span; not counted when
    nothing records it."""
    if not tracer.enabled:
        return None
    return sum(v.nbytes for v in batch.values())


def _timed(stats: Optional[PrefetchStats], field: str, fn, *args):
    if stats is None:
        return fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    stats._add(field, time.perf_counter() - t0)
    return out


def _skip(batches: Iterator, start: int) -> None:
    """Advance a plain iterator past its first ``start`` items — the
    no-random-access fast-forward (materialise cost is paid, device_put
    is not).  Exhaustion before ``start`` just leaves an empty stream."""
    for _ in range(start):
        try:
            next(batches)
        except StopIteration:
            return


def _passthrough(batches: Iterator[Dict[str, np.ndarray]], mesh,
                 stats: Optional[PrefetchStats], shard, tracer,
                 step0: int, start: int = 0,
                 on_ready=_nothing) -> Iterator[dict]:
    """The unpipelined reference shape: one batch materialised, shipped,
    then consumed, strictly in sequence (singlegpu.py:104-107's loop).
    Everything runs on the consumer thread, so the spans are serial
    (overlap=False) — exactly the attribution the depth-0 mode exists
    to expose.  A span whose body raises StopIteration is not recorded
    (tracer contract), so the exhaustion probe leaves no bogus span."""
    _skip(batches, start)
    on_ready()
    k = step0
    while True:
        try:
            with tracer.span("host_augment", step=k):
                batch = _timed(stats, "host_s", lambda: next(batches))
        except StopIteration:
            return
        with tracer.span("h2d", step=k, nbytes=_nbytes(tracer, batch)):
            out = _timed(stats, "h2d_s", shard, batch, mesh)
        if stats is not None:
            stats.count_batch()
        k += 1
        yield out


def _materialize_traced(tracer, stats, loader, k: int, step0: int):
    """Worker-side materialise: host_augment span marked overlap=True —
    pool workers run concurrently with the consumer loop, so their wall
    time must not be summed against it."""
    with tracer.span("host_augment", step=step0 + k, overlap=True):
        return _timed(stats, "host_s", loader.materialize, k)


def _pooled(loader, mesh, depth: int, workers: int,
            stats: Optional[PrefetchStats], shard, tracer,
            step0: int, start: int = 0, on_ready=_nothing) -> Iterator[dict]:
    n = len(loader)
    pool = ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix="ddp_tpu_prefetch")
    futures: deque = deque()
    try:
        # ``start`` is the mid-epoch resume offset: random access means
        # the skipped prefix is simply never submitted.
        futures.extend(pool.submit(_materialize_traced, tracer, stats,
                                   loader, k, step0)
                       for k in range(start,
                                      min(start + workers + depth, n)))
        next_k = start + len(futures)
        on_ready()
        i = 0
        while futures:
            with tracer.span("data_wait", step=step0 + i):
                batch = _timed(stats, "wait_s", futures.popleft().result)
            if next_k < n:
                futures.append(pool.submit(_materialize_traced, tracer,
                                           stats, loader, next_k, step0))
                next_k += 1
            with tracer.span("h2d", step=step0 + i,
                             nbytes=_nbytes(tracer, batch)):
                out = _timed(stats, "h2d_s", shard, batch, mesh)
            if stats is not None:
                stats.count_batch()
            i += 1
            yield out
    finally:
        # Abandoned mid-epoch (consumer exception/break/preemption): drop
        # the queued work and JOIN the workers — an in-flight materialize
        # finishes (bounded: one batch per worker) and nothing else runs.
        with tracer.span("epoch_close", step=step0):
            pool.shutdown(wait=True, cancel_futures=True)


def _threaded(batches: Iterator[Dict[str, np.ndarray]], mesh, depth: int,
              stats: Optional[PrefetchStats], shard, tracer,
              step0: int, start: int = 0, on_ready=_nothing) -> Iterator[dict]:
    _skip(batches, start)
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that gives up when the consumer is gone — the
        producer must never block forever on a full queue (the dangling-
        thread leak the pre-round-6 engine had)."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def worker() -> None:
        # Producer thread: host_augment + h2d both run here, hidden
        # behind the consumer's dispatch — overlap=True spans.
        k = step0
        try:
            while not stop.is_set():
                try:
                    with tracer.span("host_augment", step=k, overlap=True):
                        batch = _timed(stats, "host_s",
                                       lambda: next(batches))
                except StopIteration:
                    break
                with tracer.span("h2d", step=k, overlap=True,
                                 nbytes=_nbytes(tracer, batch)):
                    item = _timed(stats, "h2d_s", shard, batch, mesh)
                if not _put(item):
                    return
                k += 1
        except BaseException as e:  # surfaced in the consumer thread
            _put((_ERROR, e))
            return
        _put(_DONE)

    t = threading.Thread(target=worker, daemon=True,
                         name="ddp_tpu_prefetch")
    t.start()
    on_ready()
    i = 0
    try:
        while True:
            # Timed by hand, recorded only for REAL batches: the get that
            # returns the end-of-stream/error sentinel is not a step's
            # data wait, and spanning it would invent a phantom step
            # numbered as the next epoch's first (add_span's reason).
            t0 = time.monotonic() if tracer.enabled else 0.0
            item = _timed(stats, "wait_s", q.get)
            if item is _DONE:
                return
            if isinstance(item, tuple) and len(item) == 2 \
                    and item[0] == _ERROR:
                raise item[1]
            if tracer.enabled:
                tracer.add_span("data_wait", t0, time.monotonic() - t0,
                                step=step0 + i)
            i += 1
            if stats is not None:
                stats.count_batch()
            yield item
    finally:
        with tracer.span("epoch_close", step=step0):
            stop.set()
            try:  # unblock a producer mid-put immediately
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=10.0)
