"""Runner of the token-trained cells: the program's real ``Trainer``
driven over whole epochs of sequences, in this process, which is the one
that holds the chip.

The method is ``runners/train.py``'s, line for line where it can be: a
Trainer built in-process with its own span tracer, executables counted
through ``jax.monitoring``, two warm-up epochs, the first-step check, ONE
``Trainer.train(n)`` window of whole epochs, traced epochs after it
(``train.py``'s own ``_traced_epochs``), ``memory_stats()`` for the peak.
What differs:

- a sample is a SEQUENCE: the job mix's ``data`` block gives ``n_train``
  sequences of ``seq_len`` ids (``datagen_tokens.py``), streamed by the
  host loader as input rows and target rows; the rate is sequences a
  second a chip, and ``train_mfu_pct`` counts ``flops_seq.py``'s
  operations a sequence;
- the model is built from the configuration file (``get_model(name,
  config)``), and its module is imported before the device is touched,
  so that a tree without the model fails at once;
- the first-step check runs the reference BEFORE the Trainer's state is
  on the chip and the system's side on the Trainer's own step program
  (``reference_check_lm.py``); the reference's own seconds (two minutes
  of float32 at ``highest`` precision: the instrument's, not the
  system's) are taken out of ``setup_s`` and ``first_step_s``, and the
  detail line's ``setup_timeline`` says where the rest went;
- ``correct`` also holds the program to its routing counters: no
  assignment dropped;
- the traced line's ``breakdown`` names each device operation with the
  program's scope in front (``scope_reduce.py``), and ``ctx`` carries the
  scopes' device time, the routing counts of the window and of the traced
  stretch, and the layer sizes, for the readers under ``layer_metrics/``.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
import sys
import time

import numpy as np

from .. import datagen_tokens, flops, flops_seq, scope_reduce
from .train import (CALIBRATE_UNDER_S, COMPILE_EVENT, PREPARE_EVENTS, _fail,
                    _peak_bytes, _program_texts, _replicas_identical,
                    _traced_epochs, not_read_note)


def run(resolved: dict, args, process_age_s) -> dict:
    cell, config, mix = resolved["cell"], resolved["config"], resolved["mix"]
    chips = int(cell["chips"])
    # Before the device: a tree without the model fails here, at once.
    importlib.import_module("ddp_tpu.models." + config["model"])

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

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    prepared = []  # (event, seconds) of everything JAX prepared so far
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: prepared.append((event, secs))
        if event in PREPARE_EVENTS else None)
    timeline = []  # [phase, process age at its end, seconds JAX prepared]

    def mark_phase(phase: str) -> None:
        done = sum(secs for _e, secs in prepared)
        timeline.append([phase, round(process_age_s(), 1), round(
            done - sum(row[2] for row in timeline), 1)])

    def compiles() -> int:
        return sum(event == COMPILE_EVENT for event, _secs in prepared)

    mesh = make_mesh(chips)
    print(device_line(mesh, cell=cell["name"]), file=sys.stderr)

    # -- data, weights: all from --seed ------------------------------------
    seed = int(args.seed)
    batch = int(mix["batch_per_chip"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    dm = reference.layer_shapes(config)
    seq_len = int(mix["data"]["seq_len"])
    ids, targets = datagen_tokens.make(mix["data"], dm["vocab"], seed)
    loader = TrainLoader(Dataset(ids, targets), batch, chips,
                         augment=False, seed=seed)
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

    model = get_model(config["model"], config)
    params, stats = model.init(jax.random.key(seed))
    params_host, stats_host = jax.device_get((params, stats))
    del params, stats  # the reference needs the room
    mark_phase("data_and_weights")

    # -- the reference's side of the first-step check -----------------------
    from .. import reference_check_lm
    loader.set_epoch(0)
    first_batch = loader.materialize(0)
    check_step = int(round(opt["peak_frac"] * opt["schedule_epochs"]
                           * steps_per_epoch))
    t0 = time.monotonic()
    ref = reference_check_lm.reference_side(
        config, params_host, stats_host, first_batch["image"],
        first_batch["label"])
    reference_s = time.monotonic() - t0
    print(f"reference: loss {ref['loss']:.5f} in {reference_s:.1f}s",
          file=sys.stderr)
    mark_phase("reference")

    tracer = SpanTracer(ring=1 << 20) if args.trace else None
    first_step = {}

    def probe(_step):
        if not first_step:
            jax.block_until_ready(trainer.state.step)
            first_step["s"] = process_age_s() - reference_s

    trainer = Trainer(
        model, loader, jax.device_put(params_host),
        jax.device_put(stats_host), mesh=mesh, lr_schedule=schedule,
        sgd_config=sgd, save_every=10**9, snapshot_path=None,
        compute_dtype=compute_dtype, seed=seed, resident=False,
        prefetch_depth=int(mix.get("prefetch_depth", 2)),
        prefetch_workers=int(mix.get("prefetch_workers", 4)),
        tracer=tracer, step_probe=probe)

    # -- the system's side: the timed program on the timed shape -----------
    check = reference_check_lm.system_side(
        trainer=trainer, model=model, batch=first_batch,
        check_step=check_step, lr=float(schedule(check_step)),
        compute_dtype=compute_dtype, ref=ref,
        limits=config.get("first_step_limits") if args.rehearse else None)
    del ref
    print(f"reference check: {check}", file=sys.stderr)
    mark_phase("first_step_check")

    # -- warm-up: the cell's one shape, twice --------------------------------
    trainer.train(1)
    mark = len(prepared)
    t0 = time.monotonic()
    trainer.train(1)
    warm_epoch_s = time.monotonic() - t0
    preparing_s = sum(secs for _event, secs in prepared[mark:])
    epoch_est_s = max(warm_epoch_s - preparing_s, 1e-3)
    if epoch_est_s < CALIBRATE_UNDER_S:
        k = int(math.ceil(CALIBRATE_UNDER_S / epoch_est_s)) + 1
        t0 = time.monotonic()
        trainer.train(k)
        epoch_est_s = (time.monotonic() - t0) / k
    n_epochs = int(1.1 * args.seconds / epoch_est_s) + 1
    mark_phase("warm_up")

    # -- the window -----------------------------------------------------------
    # The plain reference's own seconds (its programs' preparation and its
    # run) are the instrument's, not the system's set-up.
    setup_s = process_age_s() - reference_s
    losses_before = len(trainer.loss_history)
    compiles_before = compiles()
    routed_before = _routing_totals(trainer)
    window_t0 = tracer.now() if tracer else 0.0
    t0 = time.monotonic()
    trainer.train(n_epochs)
    window_s = time.monotonic() - t0
    compiles_in_window = compiles() - compiles_before
    routing = _routing_delta(_routing_totals(trainer), routed_before)
    losses = np.asarray(trainer.loss_history[losses_before:], np.float64)
    first_epoch = np.asarray(trainer.loss_history[:steps_per_epoch])
    last_epoch = losses[-steps_per_epoch:]
    mem = [d.memory_stats() or {} for d in mesh.devices.flat]
    memory_peak = max(_peak_bytes(m) for m in mem)

    rate = n_epochs * samples_per_epoch / window_s / chips
    flops_per_sample = flops_seq.train_flops_per_sequence(dm, seq_len)
    dropped = sum(v["dropped"] for v in routing.values())

    checks = {
        "first_step_matches_reference": bool(check["ok"]),
        "losses_finite": bool(np.isfinite(losses).all()
                              and np.isfinite(first_epoch).all()),
        "loss_fell": bool(last_epoch.mean() < first_epoch.mean()),
        "no_compile_in_window": compiles_in_window == 0,
        "replicas_identical": _replicas_identical(trainer.state.params),
        "none_dropped": bool(routing) and dropped == 0,
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
    print("benchmark-detail: " + json.dumps({
        "checks": checks, "reference_check": check,
        "window_s": window_s, "epochs": n_epochs,
        "steps_per_epoch": steps_per_epoch,
        "samples_per_epoch": samples_per_epoch,
        "global_batch": batch * chips, "seq_len": seq_len,
        "epoch_est_s": epoch_est_s,
        "flops_per_sample": flops_per_sample, "rate_per_chip": rate,
        "memory_peak_bytes": memory_peak, "memory_stats": mem[0],
        "routing": {k: {"assignments": v["assignments"].tolist(),
                        "dropped": v["dropped"]}
                    for k, v in routing.items()},
        "first_step_s": first_step.get("s"), "setup_s": setup_s,
        "reference_s": reference_s, "setup_timeline": timeline}),
        file=sys.stderr)

    if not args.trace:
        wanted = resolved["end_to_end"]
        values = end_to_end
    else:
        spans = tracer.spans_since(window_t0)
        routed_before = _routing_totals(trainer)
        trace = _traced_epochs(trainer, loader, tracer, epoch_est_s,
                               steps_per_epoch, samples_per_epoch,
                               args.trace_dir)
        if trace is not None:
            # The device time by the program's scopes, and a breakdown
            # that names each operation's scope.
            keys = scope_reduce.scope_keys_from_hlo(
                set(_program_texts(trainer, loader)), config["scopes"])
            trace["scope_s"] = scope_reduce.scope_seconds(trace["ops"], keys)
            trace["breakdown"]["device_ops"] = scope_reduce.breakdown(
                trace["ops"], keys)
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = trace["breakdown"]
            trace["assignments"] = sum(
                int(v["assignments"].sum()) for v in _routing_delta(
                    _routing_totals(trainer), routed_before).values())
        ctx = {
            "cell": cell, "config": config, "mix": mix, "chips": chips,
            "peak": peak, "layers": dm, "seq_len": seq_len,
            "steps_per_epoch": steps_per_epoch,
            "samples_per_epoch": samples_per_epoch,
            "window_s": window_s, "window_steps": int(losses.size),
            "spans": spans,
            "compiles_in_window": compiles_in_window,
            "first_step_s": first_step.get("s"),
            "memory_peak_bytes": memory_peak if on_chip else None,
            "table": None,
            "routing": routing,
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
        note = not_read_note(cell["name"], wanted, result["metrics"])
        if note:
            print(note, file=sys.stderr)
    return result


def _routing_totals(trainer) -> dict:
    """The program's routing counters so far, copied: ``{layer:
    {"assignments": int64[E], "dropped": int}}`` (obs/routing.py; updated
    where the Trainer flushes an epoch's losses)."""
    return {k: {"assignments": np.array(v["assignments"], np.int64),
                "dropped": int(v["dropped"])}
            for k, v in trainer.routing.totals.items()}


def _routing_delta(after: dict, before: dict) -> dict:
    zero = {"assignments": 0, "dropped": 0}
    return {k: {"assignments": v["assignments"]
                - before.get(k, zero)["assignments"],
                "dropped": v["dropped"] - before.get(k, zero)["dropped"]}
            for k, v in after.items()}
