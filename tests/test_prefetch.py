"""Streaming overlap engine (data/prefetch.py): prefetch is a SCHEDULING
change, never a data change — the yielded stream is bit-identical to the
unprefetched loader at every depth/worker setting, epoch boundaries
included, and abandoning the stream (exception, break, preemption
unwinding) leaves no thread behind.

These pin the ISSUE-2 default contract: ``--prefetch_depth``/
``--prefetch_workers`` default to the established behavior (depth 2,
4 workers) and every setting — including depth 0, the unpipelined
reference loop shape — produces the bit-for-bit identical training
trajectory.
"""
import threading
import time

import jax
import numpy as np
import pytest

from ddp_tpu.data import (PrefetchStats, TrainLoader, prefetch_to_device,
                          synthetic)
from ddp_tpu.parallel import make_mesh


def _collect(it):
    return [{k: np.asarray(v) for k, v in b.items()} for b in it]


def _assert_streams_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["image"], np.asarray(w["image"]))
        np.testing.assert_array_equal(g["label"], np.asarray(w["label"]))


@pytest.mark.parametrize("depth,workers", [(0, 1), (1, 1), (2, 4), (5, 3)])
def test_stream_bit_identical_across_settings(depth, workers):
    """Pooled path: batch order and contents equal the loader's own
    materialize(k) sequence at every depth/worker combination — including
    the ragged final batch and a reshuffled second epoch."""
    ds, _ = synthetic(n_train=100, n_test=8)  # 100 % (8*2) != 0: ragged
    mesh = make_mesh(2)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=5)
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        want = [loader.materialize(k) for k in range(len(loader))]
        loader.set_epoch(epoch)  # fresh shard cache, same stream
        got = _collect(prefetch_to_device(loader, mesh, depth=depth,
                                          workers=workers))
        _assert_streams_equal(got, want)


@pytest.mark.parametrize("depth,workers", [(0, 1), (2, 4), (5, 3)])
@pytest.mark.parametrize("start", [1, 3, 7])
def test_fast_forward_yields_identical_suffix(depth, workers, start):
    """Mid-epoch resume contract (round 12): ``start=k`` yields exactly
    the suffix ``[k, n)`` of the unoffset stream, bit for bit, on every
    engine path — batch content is a function of (seed, epoch, k) alone,
    so fast-forwarding replays nothing and changes nothing."""
    ds, _ = synthetic(n_train=100, n_test=8)  # ragged tail included
    mesh = make_mesh(2)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=5)
    loader.set_epoch(1)
    want = [loader.materialize(k) for k in range(start, len(loader))]
    loader.set_epoch(1)
    got = _collect(prefetch_to_device(loader, mesh, depth=depth,
                                      workers=workers, start=start))
    _assert_streams_equal(got, want)


@pytest.mark.parametrize("depth", [0, 2])
def test_fast_forward_threaded_iterable_suffix(depth):
    """A plain iterable (no random access) still fast-forwards: the
    skipped prefix is materialised-but-dropped, the suffix identical."""
    ds, _ = synthetic(n_train=64, n_test=8)
    mesh = make_mesh(2)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=1)
    loader.set_epoch(0)
    want = [loader.materialize(k) for k in range(len(loader))]
    got = _collect(prefetch_to_device(iter(want), mesh, depth=depth,
                                      start=2))
    _assert_streams_equal(got, want[2:])


def test_fast_forward_past_end_is_empty_stream():
    """start >= len: nothing to replay — an empty stream, not an error
    (the resume-at-final-batch edge of the emergency data_state)."""
    ds, _ = synthetic(n_train=64, n_test=8)
    mesh = make_mesh(2)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=1)
    loader.set_epoch(0)
    assert _collect(prefetch_to_device(loader, mesh, depth=2,
                                       start=len(loader))) == []
    loader.set_epoch(0)
    assert _collect(prefetch_to_device(iter(list(loader)), mesh, depth=1,
                                       start=99)) == []


def test_threaded_path_matches_iterable():
    """A generic iterable (no materialize) takes the single-thread path
    and must yield the same stream."""
    ds, _ = synthetic(n_train=64, n_test=8)
    mesh = make_mesh(2)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=1)
    loader.set_epoch(0)
    want = [loader.materialize(k) for k in range(len(loader))]
    got = _collect(prefetch_to_device(iter(want), mesh, depth=3))
    _assert_streams_equal(got, want)


def test_trainer_final_state_bitwise_across_depths():
    """The trajectory contract end to end: identical loss history and
    final params, bit for bit, with the engine off (depth 0), at the
    default depth, and deeper — across TWO epochs (epoch-boundary
    reshuffle included) with a ragged tail."""
    import functools

    from ddp_tpu.models import get_model
    from ddp_tpu.optim import SGDConfig, triangular_lr
    from ddp_tpu.train import Trainer

    def run(depth):
        ds, _ = synthetic(n_train=52, n_test=8, seed=4)
        mesh = make_mesh(2)
        model = get_model("deepnn")
        params, stats = model.init(jax.random.key(2))
        loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2,
                             seed=2)
        sched = functools.partial(triangular_lr, base_lr=0.02, num_epochs=2,
                                  steps_per_epoch=len(loader))
        tr = Trainer(model, loader, params, stats, mesh=mesh,
                     lr_schedule=sched, sgd_config=SGDConfig(lr=0.02),
                     save_every=10**9, snapshot_path=None, seed=2,
                     prefetch_depth=depth)
        tr.train(2)
        return tr

    base = run(0)
    for depth in (2, 5):
        other = run(depth)
        np.testing.assert_array_equal(np.asarray(base.loss_history),
                                      np.asarray(other.loss_history))
        for a, b in zip(jax.tree_util.tree_leaves(base.state.params),
                        jax.tree_util.tree_leaves(other.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(base.state.step) == int(other.state.step)


def test_grad_accum_group_stream_prefetch_bitwise():
    """The accumulation path now pipelines its group stacks through the
    threaded engine (shard_batch_stacked via shard_fn): bit-identical to
    the engine-off run."""
    import functools

    from ddp_tpu.models import get_model
    from ddp_tpu.optim import SGDConfig, triangular_lr
    from ddp_tpu.train import Trainer

    def run(depth):
        ds, _ = synthetic(n_train=64, n_test=8, seed=7)
        mesh = make_mesh(2)
        model = get_model("deepnn")
        params, stats = model.init(jax.random.key(3))
        loader = TrainLoader(ds, per_replica_batch=4, num_replicas=2,
                             seed=3)
        sched = functools.partial(
            triangular_lr, base_lr=0.02, num_epochs=1,
            steps_per_epoch=loader.optimizer_steps_per_epoch(2))
        tr = Trainer(model, loader, params, stats, mesh=mesh,
                     lr_schedule=sched, sgd_config=SGDConfig(lr=0.02),
                     save_every=10**9, snapshot_path=None, seed=3,
                     grad_accum=2, prefetch_depth=depth)
        tr.train(1)
        return tr

    a, b = run(0), run(2)
    np.testing.assert_array_equal(np.asarray(a.loss_history),
                                  np.asarray(b.loss_history))
    for la, lb in zip(jax.tree_util.tree_leaves(a.state.params),
                      jax.tree_util.tree_leaves(b.state.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _settled_thread_count(baseline: int, timeout_s: float = 5.0) -> int:
    """Thread count after giving shutdown machinery a moment to join."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if threading.active_count() <= baseline:
            break
        time.sleep(0.02)
    return threading.active_count()


def test_threaded_shutdown_no_dangling_thread():
    """Abandoning the single-thread path mid-stream (the queue FULL, a
    producer mid-put) must stop and join the worker — the epoch loop
    unwinding on an exception/preemption cannot leak a thread blocked on
    q.put (this hung forever before round 6)."""
    ds, _ = synthetic(n_train=128, n_test=8)
    mesh = make_mesh(1)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=1, seed=0)
    loader.set_epoch(0)
    baseline = threading.active_count()
    it = prefetch_to_device(iter(list(loader)), mesh, depth=1)
    next(it)  # queue is full and the producer is blocked mid-put now
    it.close()
    assert _settled_thread_count(baseline) <= baseline


def test_pooled_shutdown_cancels_pending_work():
    """Abandoning the pooled path cancels queued materialize futures and
    joins the pool: at most (workers + depth) batches were ever built."""

    class CountingLoader:
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0
            self._lock = threading.Lock()

        def __len__(self):
            return len(self.inner)

        def materialize(self, k):
            with self._lock:
                self.calls += 1
            return self.inner.materialize(k)

    ds, _ = synthetic(n_train=256, n_test=8)
    mesh = make_mesh(1)
    loader = CountingLoader(TrainLoader(ds, per_replica_batch=8,
                                        num_replicas=1, seed=0))
    loader.inner.set_epoch(0)
    baseline = threading.active_count()
    it = prefetch_to_device(loader, mesh, depth=2, workers=2)
    next(it)
    it.close()
    assert _settled_thread_count(baseline) <= baseline
    # 1 consumed + at most (workers + depth) speculative + 1 resubmit.
    assert loader.calls <= 2 + 2 + 2, loader.calls
    assert loader.calls < len(loader.inner)


@pytest.mark.parametrize("pooled", [True, False])
def test_producer_exception_propagates_and_joins(pooled):
    """A producer-side failure surfaces in the consumer as the original
    exception, after the machinery shut down."""
    ds, _ = synthetic(n_train=64, n_test=8)
    mesh = make_mesh(1)
    inner = TrainLoader(ds, per_replica_batch=8, num_replicas=1, seed=0)
    inner.set_epoch(0)

    class Poisoned:
        def __len__(self):
            return len(inner)

        def materialize(self, k):
            if k == 3:
                raise ValueError("poisoned batch 3")
            return inner.materialize(k)

    def poisoned_iter():
        for k in range(len(inner)):
            if k == 3:
                raise ValueError("poisoned batch 3")
            yield inner.materialize(k)

    baseline = threading.active_count()
    src = Poisoned() if pooled else poisoned_iter()
    with pytest.raises(ValueError, match="poisoned batch 3"):
        _collect(prefetch_to_device(src, mesh, depth=2, workers=2))
    assert _settled_thread_count(baseline) <= baseline


def test_prefetch_stats_attribution_counters():
    """PrefetchStats counts every batch and accumulates host/H2D/wait
    time — the occupancy evidence bench.py --stream_attr records."""
    ds, _ = synthetic(n_train=64, n_test=8)
    mesh = make_mesh(1)
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=1, seed=0)
    loader.set_epoch(0)
    stats = PrefetchStats()
    n = len(_collect(prefetch_to_device(loader, mesh, depth=2, workers=2,
                                        stats=stats)))
    assert stats.batches == n == len(loader)
    per = stats.per_step_ms()
    assert per["batches"] == n
    assert per["host_ms_per_step"] > 0.0
    assert per["h2d_enqueue_ms_per_step"] >= 0.0
    assert per["consumer_wait_ms_per_step"] >= 0.0


def test_cli_prefetch_flags_end_to_end(tmp_path, capsys, monkeypatch):
    """The new CLI knobs drive a real run: non-default depth/workers and
    the --augment_device alias both parse and train (the CI smoke that
    keeps the flags from rotting)."""
    from ddp_tpu import cli

    monkeypatch.chdir(tmp_path)
    args = cli.build_parser("t").parse_args(
        ["1", "100", "--batch_size", "8", "--model", "deepnn",
         "--lr", "0.02", "--synthetic", "--synthetic_size", "64",
         "--num_devices", "2", "--prefetch_depth", "4",
         "--prefetch_workers", "2", "--snapshot_path",
         str(tmp_path / "ck.pt")])
    assert args.prefetch_depth == 4 and args.prefetch_workers == 2
    acc = cli.run(args, num_devices=None)
    assert 0.0 <= acc <= 100.0
    assert "Total training time:" in capsys.readouterr().out
    # The issue-named alias spelling maps onto the same destination.
    assert cli.build_parser("t").parse_args(
        ["1", "1", "--augment_device"]).device_augment


# ---------------------------------------------------------------------------
# the host's timeline: epoch_setup, the steps, epoch_close, and the counts


def tiny_trainer(tracer, depth=2, resident=False, grad_accum=1):
    """A two-replica deepnn Trainer over 52 samples (3 full steps of 16
    and a ragged tail of 4) that reports into ``tracer``."""
    from ddp_tpu.models import get_model
    from ddp_tpu.optim import SGDConfig
    from ddp_tpu.train import Trainer

    ds, _ = synthetic(n_train=52, n_test=8, seed=4)
    model = get_model("deepnn")
    params, stats = model.init(jax.random.key(2))
    loader = TrainLoader(ds, per_replica_batch=8, num_replicas=2, seed=2,
                         augment=not resident)
    trainer = Trainer(model, loader, params, stats, mesh=make_mesh(2),
                      lr_schedule=lambda step: 0.02,
                      sgd_config=SGDConfig(lr=0.02), save_every=10**9,
                      snapshot_path=None, seed=2, resident=resident,
                      device_augment=resident, grad_accum=grad_accum,
                      prefetch_depth=depth, prefetch_workers=2,
                      tracer=tracer)
    return trainer, loader


# What the consumer thread records for one step, by engine.
_STEP_PHASES = {
    "pooled": ["data_wait", "h2d", "dispatch"],
    "depth0": ["host_augment", "h2d", "dispatch"],
    "threaded": ["data_wait", "dispatch"],  # h2d on the producer thread
    "resident": ["dispatch"],
}


@pytest.mark.parametrize("mode", sorted(_STEP_PHASES))
def test_trainer_spans_tile_the_consumer_thread(mode):
    """Per epoch the consumer thread records ``epoch_setup``, then the
    steps' spans in the engine's order, then ``epoch_close`` (with the
    deferred ``loss_flush`` of the epoch before among it); no two of its
    spans overlap; ``dispatch.n`` sums to the epoch's samples and the
    shipped bytes to the batches' (or the index matrices')."""
    from ddp_tpu.obs.tracer import SpanTracer

    tr = SpanTracer()
    trainer, loader = tiny_trainer(
        tr, depth=0 if mode == "depth0" else 2, resident=mode == "resident",
        grad_accum=2 if mode == "threaded" else 1)
    t0 = tr.now()
    trainer.train(2)
    spans = tr.spans_since(t0)
    # What nests in a span of the loop is JAX's preparation of an
    # executable, inside the call that caused it (obs/startup.py); the
    # loop's own spans are the top level, and they tile the thread.
    from ddp_tpu.obs import export
    parents = export.span_parents(spans)
    assert {s["phase"] for s, p in zip(spans, parents) if p is not None} \
        <= {"prepare_trace", "prepare_lower", "prepare_compile"}
    assert any(p is not None and spans[p]["phase"] == "dispatch"
               for p in parents)
    serial = sorted((s for s, p in zip(spans, parents)
                     if not s["overlap"] and p is None),
                    key=lambda s: s["start_s"])
    for a, b in zip(serial, serial[1:]):
        assert a["start_s"] + a["dur_s"] <= b["start_s"] + 1e-9, (a, b)
    # Split at epoch_setup: one group an epoch.
    epochs = []
    for s in serial:
        if s["phase"] == "epoch_setup":
            epochs.append([])
        epochs[-1].append(s)  # IndexError: something before the first
    assert len(epochs) == 2
    samples = len(loader.samplers[0]) * 2
    sample_bytes = sum(v[0].nbytes for v in loader.materialize(0).values())
    for spans_e in epochs:
        phases = [s["phase"] for s in spans_e]
        n_step = phases.count("dispatch")
        body = phases[1:1 + n_step * len(_STEP_PHASES[mode])]
        assert body == _STEP_PHASES[mode] * n_step, phases
        tail = phases[1 + len(body):]
        assert "epoch_close" in tail
        assert set(tail) <= {"epoch_close", "loss_flush"}, phases
        first_step = spans_e[0]["step"]
        dispatches = [s for s in spans_e if s["phase"] == "dispatch"]
        assert dispatches[0]["step"] == first_step
        assert {s["step"] for s in spans_e
                if s["phase"] == "epoch_close"} == {first_step}
        assert sum(s["n"] for s in dispatches) == samples
        if mode == "resident":
            full, tail_idx = loader.epoch_index_matrix()
            assert spans_e[0]["nbytes"] == full.nbytes + tail_idx.nbytes
        else:
            assert spans_e[0]["nbytes"] is None
    # Every h2d, on whichever thread, carries the bytes of its batch.
    h2d = [s for s in spans if s["phase"] == "h2d"]
    if mode == "resident":
        assert h2d == []
    else:
        assert all(s["overlap"] == (mode == "threaded") for s in h2d)
        assert sum(s["nbytes"] for s in h2d) == 2 * samples * sample_bytes
    flushes = [s for s in spans if s["phase"] == "loss_flush"]
    assert sum(s["n"] for s in flushes) == len(trainer.loss_history)


def _engine_batches(engine):
    ds, _ = synthetic(n_train=24, n_test=8, seed=1)
    loader = TrainLoader(ds, per_replica_batch=4, num_replicas=2, seed=1)
    loader.set_epoch(0)
    if engine == "threaded":  # a plain iterable: no random access
        return iter([loader.materialize(k) for k in range(len(loader))]), 2
    return loader, (0 if engine == "depth0" else 2)


@pytest.mark.parametrize("engine", ["pooled", "depth0", "threaded"])
def test_engine_reports_ready_once_and_names_its_shutdown(engine):
    """``on_ready`` fires once, inside the first ``next()``, before any
    batch is waited for; the engine's shutdown (workers joined) is an
    ``epoch_close`` span at ``step0``, also when the consumer abandons
    the stream."""
    from ddp_tpu.obs.tracer import SpanTracer

    tr = SpanTracer()
    ready = []
    batches, depth = _engine_batches(engine)
    stream = prefetch_to_device(
        batches, make_mesh(2), depth=depth, workers=2, tracer=tr, step0=40,
        on_ready=lambda: ready.append(
            [s["phase"] for s in tr.spans_since(0.0) if not s["overlap"]]))
    assert ready == []  # a generator: nothing runs before the first next()
    first = next(stream)
    assert ready == [[]]  # once, and no consumer-side span before it
    nbytes = sum(np.asarray(v).nbytes for v in first.values())
    stream.close()  # abandoned after one batch
    spans = tr.spans_since(0.0)
    assert [s["nbytes"] for s in spans
            if s["phase"] == "h2d" and s["step"] == 40] == [nbytes]
    closes = [s for s in spans if s["phase"] == "epoch_close"]
    if engine == "depth0":
        assert closes == []  # nothing to shut down
    else:
        assert [(s["step"], s["overlap"]) for s in closes] == [(40, False)]
        assert spans[-1]["phase"] == "epoch_close"
    assert len(ready) == 1
