"""The two readings that the first-step limits of a ``train_tok`` cell are
set between (the reference module's ``TOLERANCE``), over several seeds in
one process (the programs are prepared once):

    python3 benchmark/tools/tok_check_readings.py --workload <cell> \\
        --seeds 11,12,13 [--control_seeds 3] [--budget_s 600] \\
        [--out chiprun_out/readings.jsonl]

``lm_check_readings.py``'s method through ``reference_check_tok.py``: for
each seed, by the check's own measures against the configuration's
reference in float32,

- ``system``: the Trainer's own step program on the seed's first two
  rows, through ``reference_check_tok.system_side`` as a run does it;
- ``bf16_reference`` (the first ``--control_seeds`` seeds): the same
  reference computed in bfloat16 throughout (the nearest precision below
  the configuration's: router, softmax and norms included).  It must come
  out as NOT correct.

One JSON line a seed (every leaf's own error included) goes to ``--out``
and a summary to stdout.  No new seed is started after ``--budget_s``.
Needs the chip at the published sizes; ``--rehearse`` runs the
configuration's tiny preset anywhere.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="0",
                    help="comma-separated whole numbers")
    ap.add_argument("--control_seeds", type=int, default=10**9,
                    help="read the bfloat16 control on the first N seeds")
    ap.add_argument("--budget_s", type=float, default=1e9)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    from benchmark import datagen_tokens, reference_check_tok
    from benchmark.run import overlay, resolve
    resolved = resolve(args.workload, args.rehearse)
    config, mix = resolved["config"], resolved["mix"]
    if args.rehearse:
        config = overlay(config, config.get("tiny", {}))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_tpu.data import TrainLoader
    from ddp_tpu.data.cifar10 import Dataset
    from ddp_tpu.models import get_model
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh
    from ddp_tpu.train import Trainer
    from ddp_tpu.train.step import init_train_state
    from ddp_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    dm = reference.layer_shapes(config)
    batch_n = int(mix["batch_per_chip"])
    opt = config["optimizer"]
    compute_dtype = {"bfloat16": jnp.bfloat16, "float32": None}[
        config["compute_dtype"]]
    model = get_model(config["model"], config)
    mesh = make_mesh(1)
    trainer = None
    out = open(args.out, "a") if args.out else None
    rows = []

    tolerance, thin = reference.TOLERANCE, reference.THIN_LEAVES
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        if time.monotonic() - t_start > args.budget_s:
            print(f"budget spent before seed {seed}", file=sys.stderr)
            break
        t0 = time.monotonic()
        ids, targets = datagen_tokens.make(mix["data"], dm["vocab"], seed)
        loader = TrainLoader(Dataset(ids, targets), batch_n, 1,
                             augment=False, seed=seed)
        loader.set_epoch(0)
        batch = loader.materialize(0)  # the run's own first batch
        params, state = jax.device_get(model.init(jax.random.key(seed)))
        if trainer is not None:
            trainer.state = None  # the reference needs the room
        full = reference_check_tok.reference_side(
            config, params, state, batch["image"], batch["label"])
        control = None
        if n < args.control_seeds:
            low = reference_check_tok.reference_side(
                config, params, state, batch["image"], batch["label"],
                cast=jnp.bfloat16)
            control = reference_check_tok.compare(
                full, loss=low["loss"], logits0=low["logits0"],
                momentum=low["grads"], lr=1.0, leaves=True,
                tolerance=tolerance, thin=thin,
                update=jax.tree_util.tree_map(lambda g: -g, low["grads"]))
            del low
        t_ref = time.monotonic() - t0

        steps = loader.optimizer_steps_per_epoch()
        schedule = functools.partial(
            triangular_lr, base_lr=opt["peak_lr"],
            num_epochs=opt["schedule_epochs"], steps_per_epoch=steps,
            peak_frac=opt["peak_frac"])
        check_step = int(round(opt["peak_frac"] * opt["schedule_epochs"]
                               * steps))
        if trainer is None:
            trainer = Trainer(
                model, loader, jax.device_put(params), jax.device_put(state),
                mesh=mesh, lr_schedule=schedule,
                sgd_config=SGDConfig(lr=opt["peak_lr"],
                                     momentum=opt["momentum"],
                                     weight_decay=opt["weight_decay"]),
                save_every=10**9, snapshot_path=None,
                compute_dtype=compute_dtype, seed=seed, resident=False)
        else:
            trainer.state = init_train_state(jax.device_put(params),
                                             jax.device_put(state))
        system = reference_check_tok.system_side(
            trainer=trainer, model=model, batch=batch,
            check_step=check_step, lr=float(schedule(check_step)),
            compute_dtype=compute_dtype, ref=full, leaves=True,
            tolerance=tolerance, thin=thin)
        del full
        row = {"workload": args.workload, "seed": seed,
               "device": jax.devices()[0].device_kind,
               "system": {"ok": system["ok"], "errors": system["errors"],
                          "info": system["info"]},
               "bf16_reference": control and {
                   "ok": control["ok"], "errors": control["errors"],
                   "info": control["info"]},
               "reference_s": t_ref, "seed_s": time.monotonic() - t0}
        rows.append(row)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
        print(f"seed {seed}: system {system['ok']} "
              f"{_short(system['errors'])}; bf16 reference "
              + (f"{control['ok']} {_short(control['errors'])}" if control
                 else "not read") + f"; {row['seed_s']:.0f}s",
              file=sys.stderr, flush=True)

    summary = {"workload": args.workload, "seeds": [r["seed"] for r in rows],
               "tolerance": tolerance}
    for side in ("system", "bf16_reference"):
        read = [r[side] for r in rows if r[side]]
        summary[side] = {
            k: [min(r["errors"][k] for r in read),
                max(r["errors"][k] for r in read)]
            for k in read[0]["errors"]} if read else {}
        summary[side + "_ok"] = [r["ok"] for r in read]
    print("tok-check-readings: " + json.dumps(summary))
    return 0


def _short(errors: dict) -> str:
    return " ".join(f"{k}={v:.4g}" for k, v in errors.items())


if __name__ == "__main__":
    sys.exit(main())
