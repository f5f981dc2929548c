"""Runner of a token-trained cell of ANY sequence model whose reference
module brings its own first-step check: ``train_seq.py``'s method line
for line (the program's real ``Trainer`` over whole epochs of sequences
in this process, executables counted through ``jax.monitoring``, the
first-step check with the reference run BEFORE the Trainer's state is on
the chip and its seconds taken out of ``setup_s``, two warm-up epochs,
ONE ``Trainer.train(n)`` window, traced epochs after it, device time by
the program's scopes, the ``benchmark-scopes:`` line), with three things
moved from the harness into the configuration's own files:

- the tiny preset of ``--rehearse`` is the configuration file's own
  ``tiny`` block, laid over it here (``tests/tiny/train_tok.json`` only
  shortens the mix's sequences), so a new configuration brings its own;
- the first-step check is ``reference_check_tok.py``'s: every limit is
  the reference module's ``TOLERANCE`` (the tiny block's
  ``first_step_limits`` on top under ``--rehearse``), the leaves held one
  by one are its ``THIN_LEAVES``, and every prediction depth's
  first-sequence logits are compared;
- ``correct`` holds the program to "no assignment dropped" wherever the
  model's state carries routing counters, and to routing counters being
  there where the reference's sizes name a router.

Nothing here branches on a model's or a cell's name: a later
configuration can name this runner.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
import sys
import time

import numpy as np

from .. import datagen_tokens, flops, scope_reduce
from ..run import overlay
from .train import (CALIBRATE_UNDER_S, COMPILE_EVENT, PREPARE_EVENTS, _fail,
                    _peak_bytes, _program_texts, _replicas_identical,
                    _traced_epochs, not_read_note)
from .train_lm import _routing_delta, _routing_totals
from .train_seq import _hold_the_other_leaves


def run(resolved: dict, args, process_age_s) -> dict:
    cell, config, mix = resolved["cell"], resolved["config"], resolved["mix"]
    if args.rehearse:
        # The configuration's own tiny preset (the mix's was laid on by
        # run.py:resolve from tests/tiny/<runner>.json).
        resolved["config"] = config = overlay(config, config.get("tiny", {}))
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
    flops_seq = importlib.import_module(
        "benchmark." + config.get("flops", "flops_seq"))
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
    from .. import reference_check_tok
    loader.set_epoch(0)
    first_batch = loader.materialize(0)
    check_step = int(round(opt["peak_frac"] * opt["schedule_epochs"]
                           * steps_per_epoch))
    t0 = time.monotonic()
    ref = reference_check_tok.reference_side(
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
    noisy = tuple(f"['{name}']" for name in getattr(
        reference, "NOISY_LEAVES", ()))
    limits = {**reference.TOLERANCE,
              **(config.get("first_step_limits", {}) if args.rehearse
                 else {})}
    check = reference_check_tok.system_side(
        trainer=trainer, model=model, batch=first_batch,
        check_step=check_step, lr=float(schedule(check_step)),
        compute_dtype=compute_dtype, ref=ref, tolerance=limits,
        thin=reference.THIN_LEAVES, leaves=bool(noisy))
    if noisy:
        _hold_the_other_leaves(check, noisy,
                               limits["momentum_rel_worst_held"])
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
    }
    if routing or "router" in dm:
        # A model with a router carries routing counters, and none of
        # the window's assignments found no room.
        checks["none_dropped"] = bool(routing) and dropped == 0
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
            # Every scope's milliseconds a step, and what they add up to
            # beside the device's busy time a step (self times of one
            # device's operations: the two agree unless operations
            # overlap).
            per_step = {k: 1000.0 * v / trace["steps"]
                        for k, v in sorted(trace["scope_s"].items(),
                                           key=lambda kv: -kv[1])}
            print("benchmark-scopes: " + json.dumps({
                "ms_per_step": per_step, "sum_ms": sum(per_step.values()),
                "busy_ms_per_step": 1000.0 * trace["busy_s"]
                / trace["steps"]}), file=sys.stderr)
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

