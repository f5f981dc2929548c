"""Runner of the training cells: the program's real ``Trainer`` driven
over whole epochs, in this process, which is the one that holds the chips.

The method is ``bench.py --e2e``'s (a Trainer built in-process, its own
span tracer, executables counted through ``jax.monitoring``), at real
epoch lengths.  What the job mix decides, it decides as data
(``traffic/<mix>.json``): how many samples, the batch a chip, whether the
batches stream from the host loader or are gathered from a table in HBM.
What the configuration decides is in ``configs/<config>.json``: the
program's model, the precision, the optimizer and its schedule, and the
plain reference that the first-step check compares against.

One run: set-up (backend, data and weights from ``--seed``, two warm-up
epochs of the cell's two shapes, the reference check), then the window —
ONE ``Trainer.train(n)`` call of ``n`` whole epochs, ``n`` fixed from the
warm-up's rate so that it lasts at least ``--seconds`` — and, with
``--trace 1``, a few more epochs under the profiler.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import datagen, flops, trace_reduce

# Everything JAX does to prepare an executable: tracing, lowering, and
# the backend compile or its read from the persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PREPARE_EVENTS = (COMPILE_EVENT,
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
SYNC_ANNOTATION = "bench:clock_sync"
TRACE_TARGET_S = 2.5
CALIBRATE_UNDER_S = 1.0


def _fail(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(2)


def run(resolved: dict, args, process_age_s) -> dict:
    cell, config, mix = resolved["cell"], resolved["config"], resolved["mix"]
    chips = int(cell["chips"])

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    platform = devices[0].platform
    if len(devices) < chips:
        _fail(f"cell {cell['name']} needs {chips} device(s), JAX sees "
              f"{len(devices)} ({platform})")
    if platform != "tpu" and not args.rehearse:
        _fail(f"JAX found no TPU (platform {platform!r}); a device metric "
              "comes only from the chip.  --rehearse runs the tiny preset "
              "on any backend.")
    device_kind = devices[0].device_kind
    on_chip = platform == "tpu"
    peak = (flops.peak_for(resolved["peaks"], device_kind)
            if on_chip else None)

    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.cifar10 import Dataset
    from ddp_tpu.models import get_model
    from ddp_tpu.obs.tracer import SpanTracer
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    from ddp_tpu.utils.platform import device_line, enable_compile_cache

    # The program's one cache rule (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache); here every executable is kept, however
    # quickly it compiled, so that a second run compiles nothing.
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    prepared = []  # (event, seconds) of everything JAX prepared so far
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: prepared.append((event, secs))
        if event in PREPARE_EVENTS else None)

    def compiles() -> int:
        return sum(event == COMPILE_EVENT for event, _secs in prepared)

    mesh = make_mesh(chips)
    print(device_line(mesh, cell=cell["name"]), file=sys.stderr)

    # -- data, weights, trainer: all from --seed ---------------------------
    seed = int(args.seed)
    batch = int(mix["batch_per_chip"])
    resident = bool(mix["resident"])
    images, labels = datagen.make(mix["data"], seed)
    loader = TrainLoader(Dataset(images, labels), batch, chips,
                         augment=not resident, seed=seed)
    if mix.get("require_native_augment"):
        from ddp_tpu.data import native
        native_ok = native.get_lib() is not None
    else:
        native_ok = True
    steps_per_epoch = loader.optimizer_steps_per_epoch()
    samples_per_epoch = len(loader.samplers[0]) * chips

    opt = config["optimizer"]
    peak_lr = opt["peak_lr"]
    sched_kw = dict(num_epochs=opt["schedule_epochs"],
                    steps_per_epoch=steps_per_epoch,
                    peak_frac=opt["peak_frac"])
    schedule = functools.partial(triangular_lr, base_lr=peak_lr, **sched_kw)
    sgd = SGDConfig(lr=peak_lr, momentum=opt["momentum"],
                    weight_decay=opt["weight_decay"])
    compute_dtype = {"bfloat16": jnp.bfloat16, "float32": None}[
        config["compute_dtype"]]

    model = get_model(config["model"])
    params, stats = model.init(jax.random.key(seed))
    params_host, stats_host = jax.device_get((params, stats))

    tracer = SpanTracer(ring=1 << 20) if args.trace else None
    first_step = {}

    def probe(_step):
        # Fires after the trainer's first dispatch (a step when batches
        # stream, the scanned epoch when they are resident): wait for it
        # once, note the time, then cost nothing.
        if not first_step:
            jax.block_until_ready(trainer.state.step)
            first_step["s"] = process_age_s()

    trainer = Trainer(
        model, loader, params, stats, mesh=mesh, lr_schedule=schedule,
        sgd_config=sgd, save_every=10**9, snapshot_path=None,
        compute_dtype=compute_dtype, seed=seed, resident=resident,
        device_augment=resident,
        prefetch_depth=int(mix.get("prefetch_depth", 2)),
        prefetch_workers=int(mix.get("prefetch_workers", 4)),
        tracer=tracer, step_probe=probe)

    # -- warm-up: the cell's two shapes, twice ----------------------------
    # The second epoch prepares the same programs again (the state the
    # first call returns carries the mesh sharding in its type; PERF.md).
    trainer.train(1)
    mark = len(prepared)
    t0 = time.monotonic()
    trainer.train(1)
    warm_epoch_s = time.monotonic() - t0
    preparing_s = sum(secs for _event, secs in prepared[mark:])
    epoch_est_s = max(warm_epoch_s - preparing_s, 1e-3)
    if epoch_est_s < CALIBRATE_UNDER_S:
        # What a call costs beyond its epochs (re-tracing, the drain) is
        # a few tenths of a second: nothing against a long epoch, half of
        # a short one.  Short epochs are cheap, so time a few more.
        k = int(math.ceil(CALIBRATE_UNDER_S / epoch_est_s)) + 1
        t0 = time.monotonic()
        trainer.train(k)
        epoch_est_s = (time.monotonic() - t0) / k
    # One tenth over, because a call ends in a drain that the epochs
    # inside it overlap: the window must not fall short.
    n_epochs = int(1.1 * args.seconds / epoch_est_s) + 1

    # -- first step against the plain reference ---------------------------
    from .. import reference_check
    check = reference_check.first_step(
        config=config, mix=mix, mesh=mesh, model=model, sgd=sgd,
        schedule=schedule, sched_kw=sched_kw,
        compute_dtype=compute_dtype, params_host=params_host,
        stats_host=stats_host, images=images, labels=labels,
        trainer=trainer)
    print(f"reference check: {check}", file=sys.stderr)

    # -- the window -----------------------------------------------------------
    setup_s = process_age_s()
    losses_before = len(trainer.loss_history)
    compiles_before = compiles()
    window_t0 = tracer.now() if tracer else 0.0
    t0 = time.monotonic()
    trainer.train(n_epochs)
    window_s = time.monotonic() - t0
    compiles_in_window = compiles() - compiles_before
    losses = np.asarray(trainer.loss_history[losses_before:], np.float64)
    first_epoch = np.asarray(trainer.loss_history[:steps_per_epoch])
    last_epoch = losses[-steps_per_epoch:]
    mem = [d.memory_stats() or {} for d in mesh.devices.flat]
    memory_peak = max(_peak_bytes(m) for m in mem)

    rate = n_epochs * samples_per_epoch / window_s / chips
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    layers = reference.layer_shapes(config)
    flops_per_sample = flops.train_flops_per_sample(layers)

    checks = {
        "first_step_matches_reference": bool(check["ok"]),
        "losses_finite": bool(np.isfinite(losses).all()
                              and np.isfinite(first_epoch).all()),
        "loss_fell": bool(last_epoch.mean() < first_epoch.mean()),
        "no_compile_in_window": compiles_in_window == 0,
        "replicas_identical": _replicas_identical(trainer.state.params),
        "native_augment": native_ok,
    }
    print(f"checks: {checks}; window {window_s:.3f}s, {n_epochs} epochs of "
          f"{steps_per_epoch} steps; loss {first_epoch.mean():.4f} -> "
          f"{last_epoch.mean():.4f}", file=sys.stderr)

    end_to_end = {"setup_s": setup_s}
    if on_chip:
        end_to_end["train_samples_per_s_per_chip"] = rate
        end_to_end["train_mfu_pct"] = flops.mfu_pct(
            rate, flops_per_sample, peak["bf16_flops_per_s"])

    device = {"platform": platform, "kind": device_kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    result = {
        # A rehearsal proves the control flow, never the numbers.
        "correct": bool(all(checks.values())) and not args.rehearse,
        "attempted": int(losses.size),
        "failed": int((~np.isfinite(losses)).sum()),
        "metrics": {},
        "device": device,
    }
    # For people and tools, on standard error: the result line holds the
    # contract's keys and no others.
    print("benchmark-detail: " + json.dumps({
        "checks": checks, "reference_check": check,
        "window_s": window_s, "epochs": n_epochs,
        "steps_per_epoch": steps_per_epoch,
        "samples_per_epoch": samples_per_epoch,
        "global_batch": batch * chips, "epoch_est_s": epoch_est_s,
        "flops_per_sample": flops_per_sample, "rate_per_chip": rate,
        "memory_peak_bytes": memory_peak, "memory_stats": mem[0],
        "first_step_s": first_step.get("s"), "setup_s": setup_s}),
        file=sys.stderr)

    if not args.trace:
        wanted = resolved["end_to_end"]
        values = end_to_end
    else:
        spans = tracer.spans_since(window_t0)
        # On any backend, so that a rehearsal walks the same path; only
        # a TPU's trace has device planes to reduce.
        trace = _traced_epochs(trainer, loader, tracer, epoch_est_s,
                               steps_per_epoch, samples_per_epoch,
                               args.trace_dir)
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = trace["breakdown"]
        ctx = {
            "cell": cell, "config": config, "mix": mix, "chips": chips,
            "peak": peak, "layers": layers,
            "steps_per_epoch": steps_per_epoch,
            "samples_per_epoch": samples_per_epoch,
            "window_s": window_s, "window_steps": int(losses.size),
            "spans": spans,
            "compiles_in_window": compiles_in_window,
            "first_step_s": first_step.get("s"),
            "memory_peak_bytes": memory_peak if on_chip else None,
            "table": ({"rows": int(images.shape[0]),
                       "row_elems": int(np.prod(images.shape[1:]))}
                      if resident else None),
            "trace": trace,
        }
        wanted = resolved["per_layer"]
        values = {m["name"]: importlib.import_module(
            "benchmark.layer_metrics." + m["name"]).read(ctx)
            for m in wanted}

    for m in wanted:
        v = values.get(m["name"])
        if v is not None and math.isfinite(v):
            result["metrics"][m["name"]] = {"value": float(v),
                                            "unit": m["unit"]}
    if args.trace and on_chip:
        # The driver holds a traced line to every metric listed for the
        # cell; say which are missing before it does.
        note = not_read_note(cell["name"], wanted, result["metrics"])
        if note:
            print(note, file=sys.stderr)
    return result


def not_read_note(cell_name: str, wanted: list, metrics: dict):
    """One line naming the metrics listed for the cell that the result
    line lacks (a reader returned None or no finite number), or None
    where the line is whole."""
    missing = [m["name"] for m in wanted if m["name"] not in metrics]
    if not missing:
        return None
    return (f"benchmark: listed for {cell_name}, not read: "
            + ", ".join(missing))


def _peak_bytes(stats: dict) -> int:
    """Peak HBM of one chip from its ``memory_stats()``.  The TPU runtime
    counts live arrays (``peak_bytes_in_use``) apart from what it
    reserves for a running program's temporaries
    (``peak_bytes_reserved``: activations, layout copies); the chip holds
    both at once while a step runs."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def _program_texts(trainer, loader) -> list:
    """The compiled HLO of the cell's programs (full step and ragged
    tail), which is what says which device operation holds a
    convolution.  Lowered again from the live arguments and read back
    from the compile cache: nothing runs, nothing is donated."""
    texts = []
    if trainer.resident is not None:
        from ddp_tpu.train.epoch import put_index_matrix
        full, tail = loader.epoch_index_matrix()
        for idx in (full, None if tail is None else tail[None, :]):
            if idx is not None and idx.shape[0]:
                texts.append(trainer.train_epoch.lower(
                    trainer.state, trainer.resident.images,
                    trainer.resident.labels,
                    put_index_matrix(idx, trainer.mesh),
                    trainer.rng).compile().as_text())
    else:
        from ddp_tpu.train.step import shard_batch
        for k in sorted({0, len(loader) - 1}):
            texts.append(trainer.train_step.lower(
                trainer.state, shard_batch(loader.materialize(k),
                                           trainer.mesh),
                trainer.rng).compile().as_text())
    return texts


def _replicas_identical(params) -> bool:
    """Every replica holds the same bits of every parameter."""
    import jax
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s, equal_nan=True)
               for s in shards[1:]):
            return False
    return True


def _traced_epochs(trainer, loader, tracer, epoch_est_s: float,
                   steps_per_epoch: int, samples_per_epoch: int,
                   keep_dir):
    """A few more epochs under the profiler, reduced to what the
    per-layer readers need.  The host spans of the same stretch go with
    it, moved onto the profiler's clock through one annotation whose
    start both clocks saw."""
    import jax
    k = max(1, int(math.ceil(TRACE_TARGET_S / epoch_est_s)))
    out_dir = keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(SYNC_ANNOTATION):
                sync_tracer_s = tracer.now()
            t0 = tracer.now()
            trainer.train(k)
            window_s = tracer.now() - t0
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.load_newest(out_dir)
    finally:
        if not keep_dir:
            shutil.rmtree(out_dir, ignore_errors=True)
    spans = tracer.spans_since(t0)
    if not trace_reduce.device_planes(trace):
        return None
    reduced = trace_reduce.reduce(
        trace, host_spans=spans, sync_name=SYNC_ANNOTATION,
        sync_host_s=sync_tracer_s,
        hlo_texts=_program_texts(trainer, loader))
    if reduced is None:
        return None
    reduced.update(epochs=k, steps=k * steps_per_epoch,
                   samples=k * samples_per_epoch, host_window_s=window_s)
    return reduced
