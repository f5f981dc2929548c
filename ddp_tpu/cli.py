"""Shared CLI driver behind the ``singlegpu.py`` / ``multigpu.py`` entry
points — reference ``main()`` + argparse block (singlegpu.py:228-263 /
multigpu.py:224-263).

The reference's two scripts differ only in their distribution plumbing
(SURVEY.md §1); here both entry points call :func:`run` and differ only in
the mesh size (1 vs all devices) — the idiomatic-TPU expression of that diff.
The argv surface is the reference's exactly: positional ``total_epochs`` and
``save_every``, ``--batch_size`` default 512 (help text corrected from the
reference's stale "default: 32", multigpu.py:259).  Extra optional flags
(model/data/precision/resume) are framework extensions, defaulting to
reference behavior.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import re
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .data import EvalLoader, TrainLoader, cifar10
from .models import get_model
from .optim import SGDConfig, triangular_lr
from .parallel import dist, make_mesh
from .train import Trainer, evaluate
from .utils import MiB, get_model_size
from .utils.metrics import MetricsLogger
from .utils.platform import device_line, enable_compile_cache


def build_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    # Reference argv (multigpu.py:255-259).
    p.add_argument("total_epochs", type=int,
                   help="Total epochs to train the model")
    p.add_argument("save_every", type=int,
                   help="How often to save a snapshot")
    p.add_argument("--batch_size", default=512, type=int,
                   help="Input batch size on each device (default: 512)")
    # Framework extensions (all default to reference behavior).
    p.add_argument("--model", default="vgg",
                   choices=["vgg", "deepnn", "resnet18", "tinylm", "nemotron_h",
                            "sambay", "glm4_moe_lite", "laguna",
                            "granite_hybrid"],
                   help="Model to train (reference trains VGG)")
    p.add_argument("--model_config", default=None, metavar="FILE",
                   help="Configuration file (JSON) of a model that is "
                        "built from one: --model nemotron_h, --model "
                        "sambay, --model glm4_moe_lite, --model laguna and "
                        "--model granite_hybrid read their layers, widths "
                        "and the share held here from it, e.g. "
                        "benchmark/configs/nemotron3_nano_30b_a3b_ep16.json,"
                        " phi4_mini_flash_stage14_19.json, "
                        "glm47_flash_ep8.json, laguna_xs2_ep8.json and "
                        "granite4_h_micro_stage0.json.  With "
                        "--synthetic such a model trains on the seeded "
                        "token generator (data/tokens.py) at the file's "
                        "seq_len")
    p.add_argument("--data_root", default=cifar10.DEFAULT_ROOT,
                   help="CIFAR-10 root (reference: data/cifar10)")
    p.add_argument("--synthetic", action="store_true",
                   help="Use a synthetic dataset (no CIFAR files needed)")
    p.add_argument("--synthetic_size", default=2048, type=int,
                   help="Training-set size for --synthetic (default 2048)")
    p.add_argument("--synthetic_label_noise", default=0.0, type=float,
                   help="Relabel this fraction of --synthetic examples "
                        "(train and test) uniformly at random, putting "
                        "held-out accuracy in a non-saturated regime "
                        "(Bayes ceiling = 1 - 0.9*p) for acceptance runs")
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 compute (BASELINE.json config #4)")
    p.add_argument("--resume", action="store_true",
                   help="Resume from the checkpoint if present")
    p.add_argument("--snapshot_path", default="checkpoint.pt",
                   help="Checkpoint path (reference: checkpoint.pt)")
    p.add_argument("--lr", default=0.4, type=float,
                   help="Peak learning rate (reference: 0.4)")
    p.add_argument("--momentum", default=0.9, type=float,
                   help="SGD momentum (reference hardcodes 0.9, "
                        "multigpu.py:132)")
    p.add_argument("--weight_decay", default=5e-4, type=float,
                   help="SGD weight decay, applied to ALL params incl. BN "
                        "like the reference (hardcoded 5e-4, "
                        "multigpu.py:133)")
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--num_devices", default=None, type=int,
                   help="Mesh size override (default: entry-point specific)")
    p.add_argument("--mesh_shape", default=None, metavar="D,M[,S]",
                   help="2-D (data x model) mesh for tensor-model "
                        "parallelism (parallel/tp/): D-way data parallel "
                        "x M-way model parallel over the first D*M "
                        "devices, params sharded per the model's "
                        "TP_RECIPE (plan table printed at startup; "
                        "python -m ddp_tpu.parallel.tp shows it offline). "
                        "A third S entry adds S-way PIPELINE parallelism "
                        "(parallel/pp/): the model's PP_BLOCKS are cut "
                        "into S balanced stages (stage table printed at "
                        "startup; python -m ddp_tpu.parallel.pp shows it "
                        "offline) and each optimizer step runs "
                        "--grad_accum micro-batches through the "
                        "--pp_schedule pipeline.  S=1 is bit-identical "
                        "to the 2-D mesh.  Batches split over the data "
                        "axis only; checkpoints stay canonical, so "
                        "snapshots interchange with any other mesh shape "
                        "(incl. 1-D serving).  Default: 1-D data-parallel "
                        "mesh")
    p.add_argument("--pp_schedule", default="1f1b",
                   choices=("1f1b", "gpipe"),
                   help="Microbatch schedule for the pipeline stage axis "
                        "(--mesh_shape D,M,S with S>1): '1f1b' "
                        "interleaves one-forward-one-backward (min(S,A) "
                        "in-flight activations), 'gpipe' runs all "
                        "forwards then all backwards (A in flight).  "
                        "Same math, bit-identical results, same bubble "
                        "fraction (S-1)/(A+S-1) — the choice is an "
                        "activation-memory knob")
    p.add_argument("--auto_plan", default=None, metavar="PLAN.json",
                   help="Train under a searched sharding plan "
                        "(python -m ddp_tpu.parallel.tp --search --out "
                        "PLAN.json): the plan doc carries the mesh shape, "
                        "per-layer layout recipe and ZeRO choice, so this "
                        "one flag replaces --mesh_shape [+ --shard_update] "
                        "for the searched configuration.  --mesh_shape/"
                        "--num_devices may still be passed but must agree "
                        "with the doc; --shard_update still force-enables "
                        "ZeRO on top of a zero=off plan.  TP_RECIPE "
                        "remains the no-flag default (MIGRATING.md)")
    p.add_argument("--spawn", default=0, type=int, metavar="N",
                   help="Fork N local processes wired by a fresh rendezvous "
                        "and run this exact command in each (the reference's "
                        "mp.spawn fan-out, multigpu.py:262-263).  For "
                        "multi-process drills on a virtual CPU mesh only: "
                        "on a TPU host one process drives every local chip, "
                        "so --spawn refuses there")
    p.add_argument("--metrics_path", default=None,
                   help="Append per-step {step, epoch, loss, lr, wall_s} "
                        "JSON lines here (the loss stream the reference "
                        "lacks, SURVEY.md section 5)")
    p.add_argument("--profile_dir", default=None,
                   help="Capture a jax.profiler trace of the training loop "
                        "into this directory (view with TensorBoard)")
    p.add_argument("--tensorboard_dir", default=None,
                   help="Also mirror the per-step loss/LR (and periodic "
                        "eval accuracy) as TensorBoard scalars into this "
                        "directory (rank 0; needs tensorflow)")
    # Observability surface (ddp_tpu/obs/): always-on span tracing with a
    # kill-switch, plus the rolling live-stats cadence.
    p.add_argument("--trace_spill", default=None,
                   metavar="PATH",
                   help="Span-tracer spill file (obs/tracer.py): one JSON "
                        "line per completed phase span (data_wait/"
                        "host_augment/h2d/dispatch/loss_flush/ckpt_write/"
                        "eval); analyze or export to Perfetto with "
                        "python -m ddp_tpu.obs.  Multi-host ranks >0 "
                        "append a .hostN suffix.  Default: "
                        "trace_spill.jsonl NEXT TO --snapshot_path (the "
                        "run's output dir, same always-on overwrite "
                        "discipline as checkpoint.pt); '' keeps the "
                        "in-memory tracer (watchdog/straggler telemetry) "
                        "without a spill file")
    p.add_argument("--obs_off", action="store_true",
                   help="Telemetry kill-switch: no span tracer, no spill "
                        "file, no live stats, no per-epoch straggler "
                        "record — hot paths see the shared no-op tracer "
                        "(zero measurable step-time overhead, the "
                        "contract CI checks)")
    p.add_argument("--inspect_port", default=None, type=int, metavar="PORT",
                   help="Serve live run introspection over HTTP on "
                        "127.0.0.1:PORT (rank 0; obs/inspect.py): GET "
                        "/metrics (live registry exposition), /healthz "
                        "(step/epoch, guard/drift/mirror/watchdog state), "
                        "/spans (recent tracer ring), /debug/profile?"
                        "steps=N (capture the next N steps' spans + a "
                        "jax.profiler trace where supported; SIGUSR1 arms "
                        "the same capture on headless boxes).  0 = an "
                        "ephemeral port (printed at startup).  Off by "
                        "default: no socket is bound and the run is "
                        "bit-identical")
    p.add_argument("--log_every", default=50, type=int, metavar="N",
                   help="Emit a live telemetry record (obs/live.py: "
                        "rolling median/p90 step time, samples/sec, MFU "
                        "when the model+device have a FLOP model, "
                        "prefetch occupancy) into the metrics stream "
                        "every N steps (rank 0; needs --metrics_path or "
                        "--tensorboard_dir to have a sink; 0 = off)")
    p.add_argument("--device_augment", "--augment_device",
                   action="store_true",
                   help="Run RandomCrop+HFlip on the TPU inside the train "
                        "step instead of on the host (same distribution): "
                        "the host ships raw uint8 once and the crop/flip "
                        "cost moves onto the chip (data/device_augment.py)")
    p.add_argument("--prefetch_depth", default=2, type=int, metavar="D",
                   help="Streaming input engine (data/prefetch.py): keep "
                        "up to D prepared batches in flight beyond the "
                        "augment workers' hands (bounded queue), so host "
                        "augment, H2D and compute pipeline.  0 disables "
                        "the overlap — materialise + upload inline, the "
                        "reference's serial loop shape (singlegpu.py:"
                        "104-107).  Default 2 (the established behavior; "
                        "the batch stream is bit-identical at every "
                        "setting — tests/test_prefetch.py)")
    p.add_argument("--prefetch_workers", default=4, type=int, metavar="W",
                   help="Concurrent host materialise/augment workers "
                        "feeding the streaming path (default 4; only "
                        "applies to random-access loaders — the "
                        "accumulation group stream pipelines on one "
                        "thread)")
    p.add_argument("--resident", action="store_true",
                   help="Keep the whole dataset resident in HBM and run "
                        "each epoch as one jitted lax.scan: no per-step "
                        "host->device batch traffic or dispatch (implies "
                        "on-device augmentation)")
    p.add_argument("--eval_every", type=int, default=0, metavar="E",
                   help="Evaluate on the test set every E epochs during "
                        "training (0 = only the reference's single "
                        "end-of-run eval)")
    p.add_argument("--grad_accum", type=int, default=1, metavar="A",
                   help="Accumulate gradients over A micro-batches per "
                        "optimizer step (one jitted scan; effective batch "
                        "= A * --batch_size per replica)")
    p.add_argument("--sync_bn", action="store_true",
                   help="Synchronise BatchNorm statistics across replicas "
                        "(the SyncBatchNorm line the reference keeps "
                        "commented out, multigpu.py:127)")
    p.add_argument("--shard_update", action="store_true",
                   help="ZeRO-1-style weight-update sharding: "
                        "reduce-scatter grads, update a 1/R momentum+param "
                        "slice per chip, all-gather params (same math as "
                        "plain DP, 1/R optimizer memory)")
    p.add_argument("--init_from_torch", default=None, metavar="STATE_DICT",
                   help="Initialise weights from a torch state_dict "
                        "checkpoint of the reference (e.g. its "
                        "checkpoint.pt) instead of random init")
    p.add_argument("--export_torch", default=None, metavar="PATH",
                   help="After training, also write the model in the "
                        "reference's torch state_dict checkpoint format "
                        "(reference keys for vgg/deepnn, torchvision keys "
                        "for resnet18)")
    p.add_argument("--ckpt_format", default="gathered",
                   choices=["gathered", "sharded"],
                   help="Checkpoint layout (train/ckpt_shard.py): "
                        "'gathered' = the canonical single-file v1 npz "
                        "(model-sharded leaves are all-gathered at save "
                        "time — O(model) host memory and write stream); "
                        "'sharded' = one shard file per model-axis slot "
                        "plus a small index, written by per-host parallel "
                        "writers with no gather — O(model/m) save path.  "
                        "RESTORE accepts either format on any mesh shape "
                        "regardless of this flag: --resume redistributes "
                        "a sharded set onto the live (d', m') mesh "
                        "shard-by-shard (elastic resume after a "
                        "pod-shrinking preemption)")
    p.add_argument("--keep_checkpoints", default=1, type=int, metavar="N",
                   help="Retain the newest N checkpoints: the head plus "
                        "N-1 rotated snapshots with a sha-256 manifest "
                        "(resilience/lineage.py); --resume falls back to "
                        "the newest verifiable one when the head is torn. "
                        "Default 1 = head only, the reference's "
                        "overwrite-in-place (multigpu.py:111)")
    p.add_argument("--mirror", default=None, metavar="URI",
                   help="Second checkpoint durability tier "
                        "(resilience/store.py): asynchronously mirror "
                        "every committed checkpoint to this object-store "
                        "URI — a directory path (or dir://PATH) runs the "
                        "bundled DirStore backend; gs://-style schemes "
                        "name the CheckpointStore paste point (RUNBOOK "
                        "§18).  Uploads run on a background thread AFTER "
                        "each lineage commit with per-op timeouts and "
                        "bounded jittered retries: a flaky or dead remote "
                        "degrades to a visible ddp_mirror_lag_epochs "
                        "gauge, never a blocked or failed step.  --resume "
                        "falls back to verifiable mirror objects when "
                        "every local candidate is gone — training "
                        "survives total local-disk loss (the supervisor "
                        "preserves this flag across relaunches)")
    p.add_argument("--on_nan", default="abort",
                   choices=["abort", "skip", "restore"],
                   help="Non-finite loss policy, checked on the existing "
                        "deferred-loss flush (zero extra D2H): abort = "
                        "fail fast (default); skip = log and continue; "
                        "restore = reload the last good checkpoint and "
                        "re-seed the step RNG.  Alias into the step "
                        "health guard (resilience/guard.py), which also "
                        "hosts the spike detector below")
    p.add_argument("--guard_window", default=64, type=int, metavar="W",
                   help="Rolling window (steps) for the guard's "
                        "median/MAD loss-spike detector (default 64; "
                        "only read when --guard_spike_factor > 0)")
    p.add_argument("--guard_spike_factor", default=0.0, type=float,
                   metavar="F",
                   help="Flag a step whose loss exceeds median * F + "
                        "3*MAD over the last --guard_window finite "
                        "losses (checked on the same deferred flush as "
                        "--on_nan — zero extra D2H).  0 = spike "
                        "detection off (default)")
    p.add_argument("--guard_action", default="rollback",
                   choices=["abort", "skip", "lr_backoff", "rollback"],
                   help="What a loss spike triggers: abort = fail fast; "
                        "skip = log and continue; lr_backoff = halve the "
                        "LR schedule going forward; rollback (default) = "
                        "restore the last verified checkpoint, re-seed, "
                        "and skip the poisoned batch window on replay "
                        "(shares the --on_nan restore budget)")
    p.add_argument("--drift_audit_every", default=0, type=int, metavar="K",
                   help="Cross-replica SDC audit (resilience/drift.py): "
                        "every K optimizer steps, fingerprint each "
                        "replica's parameters bit-level (uint32 checksum "
                        "per leaf, NOT a float sum) and compare across "
                        "the data axis with one tiny psum pair (~2*L*4 "
                        "bytes; priced as drift_audit@dp8 in "
                        "BUDGETS.json).  Replicated params must agree "
                        "bit-for-bit, so any mismatch is silent data "
                        "corruption: a drift_detected event names the "
                        "offending leaves and replicas.  Streaming 1-D "
                        "data-parallel only.  0 = off (default)")
    p.add_argument("--drift_action", default="abort",
                   choices=["abort", "restore"],
                   help="What a drift detection triggers: abort = fail "
                        "fast with the event on disk (default); restore "
                        "= reload the newest verifiable checkpoint "
                        "(shares the guard's restore budget, so "
                        "persistent corruption cannot restore-loop)")
    p.add_argument("--watchdog_secs", default=0.0, type=float, metavar="S",
                   help="Abort the run (non-blocking dist.abort + exit "
                        f"status 124) when no step/epoch progress happens "
                        "for S seconds — a stalled peer then fails the job "
                        "fast instead of riding the 300 s shutdown "
                        "timeout.  Must exceed the worst epoch wall time "
                        "INCLUDING compile.  0 = off (default)")
    p.add_argument("--schedule_epochs", default=None, type=int,
                   help="Pin the LR triangle's epoch span (the reference "
                        "hardcodes 20, multigpu.py:136; default: "
                        "total_epochs)")
    p.add_argument("--schedule_steps_per_epoch", default=None, type=int,
                   help="Pin steps_per_epoch in the LR schedule (the "
                        "reference hardcodes 98/49, multigpu.py:137; "
                        "default: derived from the real shard size)")
    p.add_argument("--audit", action="store_true",
                   help="Pre-flight: run the program auditor (python -m "
                        "ddp_tpu.analysis --strict) over the registered "
                        "program families for this --model and mesh shape "
                        "before training — collective axes/counts vs the "
                        "TP plan, donation, constant capture, the static "
                        "cost/peak-liveness estimates diffed against "
                        "BUDGETS.json (the cost-regression gate), plus "
                        "the host-sync, lockset and multi-host-"
                        "divergence lints — and abort on any error "
                        "finding (RUNBOOK.md sections 12-13)")
    return p


def spawn_local(num_processes: int) -> int:
    """The reference's local fan-out UX (``mp.spawn(main, nprocs=world_size)``,
    multigpu.py:262-263): fork ``num_processes`` copies of the *current*
    command — minus ``--spawn`` — each wired to a fresh localhost
    rendezvous via the DDP_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID env
    surface (parallel/dist.py).  Children inherit stdout/stderr, so the
    per-rank prints interleave exactly as the reference's do.  Returns the
    max child exit code."""
    import socket
    import subprocess
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # Strip --spawn in every argparse-accepted spelling, including
    # unambiguous abbreviations (--sp/--spa/--spaw; allow_abbrev is on and
    # no other option starts with "--sp") — a surviving spelling would make
    # every child re-spawn recursively (the DDP_TPU_PROCESS_ID check in
    # main() is the backstop, but this function must be safe on its own).
    spawn_re = re.compile(r"--sp(a(wn?)?)?(=.*)?$")
    argv, skip = [], False
    for a in sys.argv[1:]:
        if skip:
            skip = False
            continue
        if spawn_re.fullmatch(a):
            skip = "=" not in a  # bare flag consumes the following N
            continue
        argv.append(a)
    # Re-exec the current command.  A plain script (python multigpu.py ...)
    # needs the interpreter prepended; an installed console shim
    # (ddp-tpu-multi, possibly a binary launcher) is itself executable and
    # must NOT be fed to python.
    cmd = ([sys.executable, sys.argv[0]] if sys.argv[0].endswith(".py")
           else [sys.argv[0]])
    procs = []
    for pid in range(num_processes):
        env = dict(os.environ,
                   DDP_TPU_COORDINATOR=f"localhost:{port}",
                   DDP_TPU_NUM_PROCESSES=str(num_processes),
                   DDP_TPU_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen([*cmd, *argv], env=env))
    return max(p.wait() for p in procs)


def main(args: argparse.Namespace, *, num_devices: Optional[int]) -> None:
    """Entry-point body shared by singlegpu.py/multigpu.py: fan out if
    ``--spawn N`` was asked for, otherwise train in-process.  A process
    that is already a spawned child (rendezvous env set) never re-spawns —
    the backstop against any recursion."""
    if args.spawn and "DDP_TPU_PROCESS_ID" not in os.environ:
        # The one backend touch a --spawn parent makes: on CPU it costs
        # the children nothing; on a TPU no child is started at all.
        if jax.default_backend() == "tpu":
            raise SystemExit(
                "--spawn refuses on a TPU host: every child would claim all "
                "local chips and all but one would fail or hang; run one "
                "process per host (python multigpu.py drives every local "
                "chip) or set JAX_PLATFORMS=cpu for a multi-process drill")
        raise SystemExit(spawn_local(args.spawn))
    if args.audit and "DDP_TPU_PROCESS_ID" not in os.environ:
        _preflight_audit(args)
    run(args, num_devices=num_devices)


def _parse_mesh_shape(text: str) -> tuple:
    """``--mesh_shape`` 'D,M' / 'D,M,S' (or x-separated) as an int tuple.
    Rejections name all three axes — the flag's contract is the mesh's
    (data, model, stage) order, and the error must say so rather than
    surface an unpacking traceback."""
    try:
        dims = tuple(int(x) for x in str(text).replace("x", ",").split(","))
    except ValueError:
        dims = ()
    if len(dims) not in (2, 3) or any(v < 1 for v in dims):
        raise SystemExit(
            f"--mesh_shape wants 'D,M' or 'D,M,S' — positive ints, in "
            f"(data, model, pipeline stage) order, e.g. 2,4 or 2,1,2 — "
            f"got {text!r}")
    return dims


def _preflight_audit(args: argparse.Namespace) -> None:
    """``--audit``: trace-audit the program families this run will build
    BEFORE any device state exists (ddp_tpu/analysis).  Tracing is
    abstract, so the cost is seconds; an error finding (wrong-axis
    collective, missing donation, captured constant, cost-budget
    overrun, lockset/host-sync violation, unguarded divergent
    collective) aborts the run here instead of wasting a chip
    reservation."""
    from .analysis.__main__ import run as audit_run
    if getattr(args, "auto_plan", None):
        from .parallel.tp.autoplan import read_plan_doc
        dims = read_plan_doc(args.auto_plan)["mesh_shape"]
        shape = ",".join(str(int(v)) for v in dims)
    elif args.mesh_shape:
        shape = str(args.mesh_shape)
    else:
        import jax  # backend decides the 1-D width, same as run() will
        shape = f"{args.num_devices or jax.device_count()},1"
    rc = audit_run(["--strict", "--model", args.model,
                    "--mesh-shape", shape])
    if rc:
        raise SystemExit(
            f"--audit: program auditor reported error findings (exit {rc});"
            " fix them or drop --audit to proceed at your own risk")


def _load_torch_init(model_name: str, path: str):
    """Weights from a reference torch checkpoint (its ``checkpoint.pt``,
    multigpu.py:110-112) — the migration path for users switching over.
    torch is imported lazily: the framework itself has no torch dependency."""
    try:
        import torch
    except ImportError as e:  # pragma: no cover
        raise SystemExit(
            "--init_from_torch needs torch installed to unpickle the "
            f"state_dict: {e}")
    from .utils import torch_interop
    sd = torch.load(path, map_location="cpu", weights_only=True)
    loaders = {
        "vgg": torch_interop.vgg_from_torch_state_dict,
        "deepnn": torch_interop.deepnn_from_torch_state_dict,
        "resnet18": torch_interop.resnet18_from_torch_state_dict,
    }
    return loaders[model_name](sd)


def build_schedule(args: argparse.Namespace, derived_steps_per_epoch: int):
    """Triangular schedule (reference singlegpu.py:142-149).  Defaults
    derive steps_per_epoch from the real shard size and tie the triangle
    span to the CLI epoch count (the two sanctioned fixes, SURVEY.md
    appendix); ``--schedule_epochs``/``--schedule_steps_per_epoch``
    reproduce the reference's hardcoded curve bit-for-bit."""
    return functools.partial(
        triangular_lr, base_lr=args.lr,
        num_epochs=args.schedule_epochs or args.total_epochs,
        steps_per_epoch=(args.schedule_steps_per_epoch
                         or derived_steps_per_epoch))


def _export_torch(model_name: str, path: str, trainer) -> None:
    """Write the trained model as a reference-format torch state_dict
    (the exact artifact ``torch.save(model.module.state_dict())`` produces,
    multigpu.py:110-112) so reference tooling can consume it."""
    try:
        import torch
    except ImportError as e:  # pragma: no cover
        raise SystemExit(f"--export_torch needs torch to write the pickle: "
                         f"{e}")
    from .utils import torch_interop
    params = jax.device_get(trainer.state.params)
    stats = jax.device_get(trainer.state.batch_stats)
    if model_name == "vgg":
        sd = torch_interop.vgg_to_torch_state_dict(params, stats)
    elif model_name == "deepnn":
        sd = torch_interop.deepnn_to_torch_state_dict(params)
    else:
        sd = torch_interop.resnet18_to_torch_state_dict(params, stats)
    out = {k: torch.from_numpy(np.array(v))  # copy: writable + contiguous
           for k, v in sd.items()}
    # strict load_state_dict compatibility: torch BN carries a
    # num_batches_tracked buffer the reference checkpoints too.
    for k in list(out):
        if k.endswith(".running_mean"):
            out[k[:-len("running_mean")] + "num_batches_tracked"] = \
                torch.zeros((), dtype=torch.long)
    torch.save(out, path)
    print(f"Torch state_dict exported to {path}")


def run(args: argparse.Namespace, *, num_devices: Optional[int]) -> float:
    """Train + report, reference ``main()`` order (multigpu.py:224-250):
    setup -> objs -> loader -> train -> time print -> size print -> eval ->
    accuracy print -> teardown.  Returns the final accuracy (%).

    Teardown is exception-safe on multi-host: an exception anywhere in the
    body (data load, training, final eval, ``--export_torch``) on ONE
    process would otherwise leave its peers hanging in their next
    collective — the reference's ``destroy_process_group()``
    (multigpu.py:250) has the same unprotected shape.  Here the failing
    process reports the error, tears down its coordination state
    (``dist.abort``), and HARD-EXITS (``os._exit``): interpreter
    finalization cannot run, because shutdown GC destroys the runtime's
    collective machinery whose destructor blocks on the very peers that
    are stuck waiting for us (measured: a 2-process run's failing worker
    hung forever in ``Garbage-collecting`` after its traceback printed).
    The process death closes the sockets and the peers' coordinator
    heartbeat/error machinery aborts them within its timeout — the same
    hard-kill discipline NCCL watchdogs use.  Single-host keeps plain
    raise semantics (there is no peer to unblock and the caller may want
    the exception)."""
    from .resilience.preemption import (EMERGENCY_CHECKPOINT_EXIT_STATUS,
                                        PreemptionInterrupt)
    dist.initialize()  # no-op single-host (reference ddp_setup, multigpu.py:225)
    try:
        accuracy = _run_body(args, num_devices=num_devices)
    except PreemptionInterrupt as e:
        # COORDINATED exit, not a failure: every host raised at the same
        # epoch boundary (resilience/preemption.py's collective decision)
        # with the emergency checkpoint already on disk, so the graceful
        # shutdown barrier completes — no peer is left in a collective.
        cue = ("the supervisor relaunches with --resume automatically"
               if os.environ.get("DDP_TPU_SUPERVISED")
               else "relaunch with --resume to continue")
        print(f"preempted: {e}; exiting with status "
              f"{EMERGENCY_CHECKPOINT_EXIT_STATUS} — {cue}",
              file=sys.stderr)
        sys.stdout.flush()
        sys.stderr.flush()
        dist.shutdown()
        raise SystemExit(EMERGENCY_CHECKPOINT_EXIT_STATUS)
    except BaseException as err:
        if jax.process_count() > 1:
            print(f"FATAL: process {jax.process_index()} failed with "
                  f"{err!r}; aborting the coordination service and "
                  "hard-exiting so peer processes abort instead of "
                  "hanging in their next collective", file=sys.stderr)
            import traceback
            traceback.print_exc()
            sys.stdout.flush()
            sys.stderr.flush()
            dist.abort()  # non-graceful: never blocks (dist.py)
            _hard_exit(1)
        raise
    dist.shutdown()  # reference destroy_process_group (multigpu.py:250)
    return accuracy


def _hard_exit(code: int) -> None:  # monkeypatch seam for tests
    os._exit(code)


@contextlib.contextmanager
def _early_phase(early: Optional[list], phase: str):
    """A set-up phase that ends before the run's tracer exists (its ring
    is sized from the loader): the clock readings are kept in ``early``
    and become spans once it does.  ``early`` is None under
    ``--obs_off``, and then no clock is read."""
    if early is None:
        yield
        return
    t0 = time.monotonic()
    yield
    early.append((phase, t0, time.monotonic() - t0))


def _build_mesh(args: argparse.Namespace, auto_doc: Optional[dict],
                num_devices: Optional[int]):
    """The run's mesh from the plan doc, ``--mesh_shape`` or the device
    count, redundant flags checked against each other.  ``make_mesh`` is
    the first call that starts the backend."""
    if auto_doc is not None:
        doc_dims = tuple(int(v) for v in auto_doc["mesh_shape"])
        doc_full = doc_dims + (1,) * (3 - len(doc_dims))
        doc_str = ",".join(map(str, doc_dims))
        if args.mesh_shape:
            dims = _parse_mesh_shape(args.mesh_shape)
            full = dims + (1,) * (3 - len(dims))
            if full != doc_full:
                # Stage-count contradictions get named specifically —
                # same drop-one contract as the d,m case.
                detail = (f" (the doc pins pipeline stage count "
                          f"s={doc_full[2]}, the flag asks s={full[2]})"
                          if full[:2] == doc_full[:2] else "")
                raise SystemExit(
                    f"--mesh_shape {args.mesh_shape} contradicts the auto "
                    f"plan's searched mesh {doc_str}{detail}; drop one")
        n_doc = doc_full[0] * doc_full[1] * doc_full[2]
        if args.num_devices and args.num_devices != n_doc:
            raise SystemExit(
                f"--num_devices {args.num_devices} contradicts the auto "
                f"plan's searched mesh {doc_str} (= {n_doc} devices); "
                "drop one")
        return make_mesh(shape=doc_dims)
    if args.mesh_shape:
        dims = _parse_mesh_shape(args.mesh_shape)
        n_mesh = 1
        for v in dims:
            n_mesh *= v
        if args.num_devices and args.num_devices != n_mesh:
            raise SystemExit(
                f"--num_devices {args.num_devices} contradicts "
                f"--mesh_shape {','.join(map(str, dims))} (= {n_mesh} "
                "devices); drop one")
        return make_mesh(shape=dims)
    return make_mesh(args.num_devices or num_devices)


def _load_data(args: argparse.Namespace, model):
    """The run's train and test sets: seeded token rows for a token
    model, the synthetic images or CIFAR-10 from ``--data_root``."""
    if model.tokens:
        if not args.synthetic:
            raise SystemExit(
                f"--model {args.model} takes token ids and trains on the "
                "seeded token generator: pass --synthetic (no token corpus "
                "reader exists yet)")
        if args.resident or args.device_augment or args.eval_every:
            raise SystemExit(
                f"--model {args.model}: --resident and --device_augment "
                "are the image pipeline's (a table of image rows in HBM, "
                "crops and flips; token rows stream through the host "
                "loader), and --eval_every scores accuracy over classes")
        from .data.tokens import synthetic_tokens
        vocab, seq_len = model.tokens
        if not seq_len:
            raise SystemExit(f"{args.model_config} gives no seq_len: the "
                             "length of the synthetic sequences")
        train_ds = synthetic_tokens(args.synthetic_size, seq_len, vocab,
                                    seed=args.seed)
        test_ds = synthetic_tokens(1, seq_len, vocab, seed=args.seed + 1)
    elif args.synthetic:
        train_ds, test_ds = cifar10.synthetic(
            n_train=args.synthetic_size,
            n_test=max(args.synthetic_size // 4, 64),
            label_noise=args.synthetic_label_noise)
    else:
        if args.synthetic_label_noise > 0:
            # Refuse rather than silently train on clean real data: the
            # noise knob only exists for the synthetic acceptance regime,
            # and a run that LOOKS noised but isn't would corrupt any
            # parity comparison made with it.
            raise SystemExit(
                "--synthetic_label_noise only applies to the --synthetic "
                "dataset; it would be silently ignored for real CIFAR-10. "
                "Pass --synthetic, or drop the flag.")
        train_ds, test_ds = cifar10.load(args.data_root)
    return train_ds, test_ds


def _run_body(args: argparse.Namespace, *, num_devices: Optional[int]) -> float:
    """The reference ``main()`` body proper (multigpu.py:224-248), between
    rendezvous and teardown — both owned by :func:`run`."""
    enable_compile_cache()
    early = None if args.obs_off else []  # (phase, start, seconds)
    # A searched plan doc (--auto_plan) IS the mesh/zero configuration:
    # the search already chose the shape and the ZeRO setting, so the doc
    # drives both and any redundant flags must agree rather than win.
    auto_doc = None
    if getattr(args, "auto_plan", None):
        from .parallel.tp.autoplan import read_plan_doc
        try:
            auto_doc = read_plan_doc(args.auto_plan)
        except (OSError, ValueError) as e:
            raise SystemExit(f"--auto_plan: {e}")
        if auto_doc["model"] != args.model:
            raise SystemExit(
                f"--auto_plan was searched for model "
                f"{auto_doc['model']!r} but this run trains "
                f"{args.model!r}; re-run the search for this model")
        if auto_doc.get("zero") and not args.shard_update:
            args.shard_update = True
            if jax.process_index() == 0:
                print("auto plan: ZeRO update sharding on "
                      "(plan doc zero=true)")
    with _early_phase(early, "backend_start"):
        mesh = _build_mesh(args, auto_doc, num_devices)
    # Batch math divides by the DATA axis only: on a 2-D mesh the model
    # axis replicates the batch (parallel/mesh.py:data_axis_size).
    from .parallel.mesh import data_axis_size
    n_replicas = data_axis_size(mesh)
    model_config = None
    if args.model_config:
        with open(args.model_config) as f:
            model_config = json.load(f)
    model = get_model(args.model, model_config)
    # The native kernel serves host augmentation only; a run that
    # augments on device, or trains on token ids, never builds it.
    if args.device_augment or args.resident or model.tokens:
        native_augment = "n/a"
    else:
        from .data import native
        native_augment = "on" if native.get_lib() is not None else "off"
    print(device_line(mesh, native_augment=native_augment), flush=True)

    with _early_phase(early, "data_load"):
        train_ds, test_ds = _load_data(args, model)

    with _early_phase(early, "model_init"):
        if args.init_from_torch:
            params, batch_stats = _load_torch_init(args.model,
                                                   args.init_from_torch)
        else:
            params, batch_stats = model.init(jax.random.key(args.seed))
    compute_dtype = jnp.bfloat16 if args.bf16 else None

    # Tensor-parallel plan (parallel/tp/plan.py): resolved against the
    # LIVE param pytree so the divisibility validation and the printed
    # table describe exactly what will train; built for any --mesh_shape
    # mesh (m=1 included — the tp code path then runs trivially).
    tp_plan = None
    if auto_doc is not None:
        from .parallel.tp.autoplan import plan_from_doc
        from .parallel.tp.plan import format_plan_table
        tp_plan = plan_from_doc(auto_doc, params, batch_stats)
        if jax.process_index() == 0:
            if tp_plan is not None:
                print(format_plan_table(tp_plan))
            else:
                print(f"auto plan: pure data parallelism over "
                      f"{mesh.devices.size} devices (searched layout "
                      "kept every layer replicated)")
    elif args.mesh_shape:
        from .parallel.mesh import model_axis_size
        from .parallel.tp.plan import format_plan_table, plan_for_model
        try:
            tp_plan = plan_for_model(args.model, params, batch_stats,
                                     model_size=model_axis_size(mesh))
        except ValueError as e:
            raise SystemExit(f"--mesh_shape: {e}")
        if jax.process_index() == 0:
            print(format_plan_table(tp_plan))

    # Pipeline stage plan (parallel/pp/partition.py): resolved whenever
    # the mesh grew the third ``stage`` axis — balanced cost-model cut of
    # the model's PP_BLOCKS, stage table printed at startup like the tp
    # plan table above.  The microbatch count is the grad-accum group
    # size: the pipeline injects exactly those micro-batches per
    # optimizer step, so the predicted-bubble footer describes this run.
    pp_plan = None
    from .parallel.mesh import model_axis_size as _masz, stage_axis_size
    if stage_axis_size(mesh) > 1:
        from .parallel.pp import format_stage_table, plan_stages
        try:
            pp_plan = plan_stages(args.model, stage_axis_size(mesh),
                                  model_size=_masz(mesh),
                                  params=params, batch_stats=batch_stats)
        except ValueError as e:
            raise SystemExit(f"--mesh_shape: {e}")
        if jax.process_index() == 0:
            print(format_stage_table(pp_plan,
                                     num_micro=max(args.grad_accum, 1)))

    # Each host materialises/augments only its own chips' rows (the per-host
    # shard DistributedSampler semantics, multigpu.py:153); single-host this
    # is the full range.  Derived from the mesh itself so a --num_devices
    # override (mesh smaller than the local device count) stays consistent.
    from .parallel.mesh import local_replica_ids
    local_replicas = local_replica_ids(mesh)
    device_augment = args.device_augment or args.resident
    train_loader = TrainLoader(train_ds, args.batch_size, n_replicas,
                               seed=args.seed, local_replicas=local_replicas,
                               augment=not (device_augment or model.tokens))
    # Triangular schedule (reference singlegpu.py:142-149) with
    # steps_per_epoch derived from the real shard size and the triangle span
    # tied to the CLI epoch count — the two sanctioned fixes to the
    # reference's hardcoded 98/49 and 20 (SURVEY.md appendix).  Under
    # gradient accumulation the schedule counts OPTIMIZER steps (one per
    # group of --grad_accum micro-batches), matching torch's
    # scheduler.step()-after-optimizer.step() convention.  The count comes
    # from the loader's knowledge of its own accumulation grouping (the
    # ragged tail is always its own optimizer step) — ceil(len/A) would
    # undercount by one whenever the full-batch count isn't divisible by A,
    # clipping the LR triangle early.
    opt_steps = train_loader.optimizer_steps_per_epoch(args.grad_accum)
    lr_schedule = build_schedule(args, opt_steps)

    if args.tensorboard_dir:
        # Validate the lazy tf dependency on EVERY rank: if only rank 0
        # (the writer rank) exited over a missing tensorflow, ranks 1+
        # would hang in their first collective.
        try:
            import tensorflow  # noqa: F401
        except ImportError as e:
            raise SystemExit(
                f"--tensorboard_dir needs tensorflow for tf.summary: {e}")
    # Event-file creation is itself a write, so the TB writer (unlike the
    # append-only JSONL handle) is constructed on rank 0 only.
    metrics = MetricsLogger(
        args.metrics_path,
        tensorboard_dir=(args.tensorboard_dir
                         if jax.process_index() == 0 else None))
    # Observability surface (ddp_tpu/obs/): the span tracer is installed
    # process-wide for the run's duration (evaluate()/save_checkpoint()
    # read the process tracer) and restored to the no-op tracer after —
    # embedding callers and back-to-back in-process runs must not inherit
    # a closed spill handle.  --obs_off keeps the NullTracer: hot paths
    # then cost two trivial method calls per span (the zero-overhead
    # kill-switch contract).
    from .obs.tracer import (NullTracer, SpanTracer, default_spill_path,
                             set_tracer)
    # Unset --trace_spill defaults to the run's output dir (next to the
    # checkpoint head), not the CWD; '' stays the explicit kill value.
    trace_spill = args.trace_spill
    if trace_spill is None:
        trace_spill = default_spill_path(args.snapshot_path,
                                         "trace_spill.jsonl")
    if args.obs_off:
        tracer = NullTracer()
        # Remove a previous traced run's spill at this path: leaving it
        # would hand `python -m ddp_tpu.obs` a stale run's timeline with
        # nothing marking it as such (same overwrite-in-place discipline
        # as the traced branch, which truncates).
        stale = trace_spill or None
        if stale and jax.process_index() > 0:
            stale = f"{stale}.host{jax.process_index()}"
        if stale:
            with contextlib.suppress(OSError):
                os.unlink(stale)
    else:
        spill = trace_spill or None
        if spill and jax.process_index() > 0:
            spill = f"{spill}.host{jax.process_index()}"
        # Ring sized to one epoch (~5 serial+overlap spans per step plus
        # boundary phases): the per-epoch straggler medians read
        # spans_since(epoch start), and a default-sized ring would
        # silently cover only a large epoch's tail (the no-silent-caps
        # rule bench.py documents).  The spill file is never truncated
        # by the ring — offline reports see every span regardless.
        ring = max(4096, len(train_loader) * 8)
        # The tracer's zero is the process's start, so the spill reads
        # in process age; JAX's preparation of every executable from
        # here on is a span of it (obs/startup.py).
        from .obs import startup
        anchor = dict(host=jax.process_index(), t0=startup.PROCESS_START)
        try:
            tracer = SpanTracer(spill_path=spill, ring=ring, **anchor)
        except OSError as e:
            # An unwritable spill location must not kill a training run
            # the way it would not have before telemetry existed —
            # degrade to ring-only (watchdog/straggler telemetry keeps
            # working; only the offline spill is lost), loudly.
            print(f"WARNING: cannot open --trace_spill {spill!r} ({e}); "
                  "tracing continues in-memory only (no spill file)",
                  file=sys.stderr)
            tracer = SpanTracer(spill_path=None, ring=ring, **anchor)
        startup.attach(tracer)
        for phase, start, dur_s in early:
            tracer.add_span(phase, start, dur_s)
    # Resilience surface (ddp_tpu/resilience/): graceful SIGTERM/SIGINT
    # handling is on whenever we own the main thread (signal.signal is
    # main-thread-only; embedded callers keep their own handlers), the
    # watchdog is opt-in via --watchdog_secs.
    import threading

    from .resilience.preemption import PreemptionGuard
    preemption = None
    try:
        # Install-and-restore both process-wide effects (tracer, signal
        # handlers) INSIDE one bracket: an exception anywhere between —
        # the guard install included — must not leak either into an
        # embedding process.
        set_tracer(tracer)
        preemption = (PreemptionGuard().install()
                      if threading.current_thread()
                      is threading.main_thread() else None)
        return _run_guarded(args, preemption, metrics, model, train_loader,
                            params, batch_stats, mesh, lr_schedule,
                            compute_dtype, device_augment, test_ds,
                            n_replicas, local_replicas, tracer, tp_plan,
                            pp_plan=pp_plan)
    finally:
        # Handlers must not outlive the run even when construction (e.g. a
        # resume with every checkpoint torn) raises before training starts
        # — an embedding process keeps its own signal behavior.
        if preemption is not None:
            preemption.uninstall()
        set_tracer(NullTracer())
        tracer.close()


def _run_guarded(args, preemption, metrics, model, train_loader, params,
                 batch_stats, mesh, lr_schedule, compute_dtype,
                 device_augment, test_ds, n_replicas, local_replicas,
                 tracer, tp_plan=None, pp_plan=None) -> float:
    """The trainer-lifetime tail of :func:`_run_body`, inside the
    preemption guard's install/uninstall bracket."""
    from .obs.registry import MetricsRegistry
    from .resilience.watchdog import Watchdog
    # One metrics registry per run: prefetch/guard/drift/watchdog mirror
    # their counters here, and the end-of-run exposition lands next to
    # the metrics JSONL (<metrics_path>.prom) so a run's final counter
    # state is scrapeable after the process exits.
    registry = MetricsRegistry()
    # A stall report that names the last completed span per host turns
    # "exit 124" into a diagnosis — wired only when the tracer is live.
    # on_expire force-lands the spill tail: the watchdog dies via
    # os._exit, which skips Python buffer flushing, and the spans leading
    # into the stall are exactly the ones the spill exists to preserve.
    # Every hook is BOUNDED — the tracer lock may be held by a thread
    # wedged in a spill write to a hung mount, and fsync itself can hang
    # on such a mount; the expire path must reach exit 124 regardless
    # (its entire reason to exist), so the flush runs on a side thread
    # with a join timeout and the span summary takes the lock with one.
    def _flush_spill_bounded() -> None:
        import threading as _threading
        t = _threading.Thread(
            target=lambda: tracer.flush(fsync=True, lock_timeout=2.0),
            daemon=True, name="obs-spill-flush")
        t.start()
        t.join(timeout=3.0)

    # The stall context additionally names the guard's last decision and
    # the last drift-audit step (round 12): a stall during a rollback or
    # an audit is diagnosable from the dump alone.  The trainer is built
    # below, after the watchdog — reach it through a cell.
    trainer_ref: list = []

    def _stall_context() -> str:
        parts = []
        if tracer.enabled:
            parts.append(tracer.describe_last(lock_timeout=2.0))
        if trainer_ref:
            t = trainer_ref[0]
            drift = getattr(t, "_drift", None)
            parts.append(
                f"guard: last decision {t._health.last_decision}; "
                f"drift audit: "
                + (f"last at step {drift.last_audit_step}"
                   if drift is not None else "off"))
            mirror = getattr(t, "_mirror", None)
            parts.append(
                "mirror: "
                + (f"lag {mirror.lag_epochs()} epoch(s)"
                   if mirror is not None else "off"))
        return "\n".join(p for p in parts if p)

    # /healthz snapshot — the one description of live run state, shared
    # verbatim by the inspect endpoint and the flight recorder's bundle
    # (a postmortem and a mid-run scrape must never disagree about what
    # "the run's state" means).  Every read is a host-side mirror or a
    # lock-free scrape — nothing here touches a device or blocks.
    def _health_snapshot() -> dict:
        snap: dict = {}
        if trainer_ref:
            t = trainer_ref[0]
            snap["step"] = t._host_step
            snap["epoch"] = t._host_epoch
            snap["guard_last_decision"] = t._health.last_decision
            snap["guard_restores"] = t._health.restores
            drift = getattr(t, "_drift", None)
            snap["drift_last_audit_step"] = (
                drift.last_audit_step if drift is not None else None)
            mirror = getattr(t, "_mirror", None)
            snap["mirror_lag_epochs"] = (
                mirror.lag_epochs() if mirror is not None else None)
        if watchdog is not None:
            snap["watchdog_last_beat_age_s"] = round(
                watchdog.last_beat_age(), 3)
            snap["watchdog_timeout_s"] = watchdog.timeout_s
        if pstats is not None:
            snap["prefetch"] = pstats.per_step_ms()
        return snap

    # Flight recorder (obs/blackbox.py): rank 0, needs --metrics_path for
    # a home (the bundle lands next to the JSONL) and respects the
    # --obs_off kill-switch like every other telemetry surface.
    from .obs.blackbox import POSTMORTEM_BASENAME, FlightRecorder
    recorder = None
    if (not args.obs_off and args.metrics_path
            and jax.process_index() == 0):
        recorder = FlightRecorder(
            os.path.join(
                os.path.dirname(os.path.abspath(args.metrics_path)),
                POSTMORTEM_BASENAME),
            config=vars(args), tracer=tracer, context=_health_snapshot)
        metrics.attach_recorder(recorder)

    # Watchdog expiry hook: land the spill tail AND the postmortem bundle
    # before os._exit(124).  Both are bounded (side thread + join
    # timeout) — the expire path must reach the exit regardless of a
    # wedged filesystem.
    from .resilience.watchdog import WATCHDOG_EXIT_STATUS

    def _on_expire() -> None:
        if tracer.enabled:
            _flush_spill_bounded()
        if recorder is not None:
            recorder.dump("watchdog_stall",
                          exit_status=WATCHDOG_EXIT_STATUS,
                          error="watchdog: no progress heartbeat within "
                                f"{args.watchdog_secs}s",
                          bounded=True)

    watchdog = (Watchdog(args.watchdog_secs,
                         context=_stall_context,
                         on_expire=(_on_expire
                                    if (tracer.enabled
                                        or recorder is not None)
                                    else None),
                         registry=registry)
                if args.watchdog_secs > 0 else None)
    # Live telemetry (obs/live.py): the PrefetchStats occupancy counters
    # feed the per-step metrics stream instead of dying with the engine
    # object; rank 0 only, and only when a metrics sink exists.
    from .data import PrefetchStats
    from .obs.live import LiveStats
    pstats = None
    live = None
    if (not args.obs_off and args.log_every > 0 and metrics.active
            and jax.process_index() == 0 and args.resident):
        # Resident mode has no per-step consumer loop to time: the whole
        # epoch is ONE async dispatch, so loop intervals would measure
        # enqueue time and report fantasy step rates.  Per-step resident
        # attribution lives inside XLA (--profile_dir); say so instead
        # of emitting wrong numbers.
        print("note: live telemetry (--log_every) covers the streaming "
              "path only; --resident epochs are one dispatch (use "
              "--profile_dir for per-step attribution)", file=sys.stderr)
    elif (not args.obs_off and args.log_every > 0 and metrics.active
            and jax.process_index() == 0):
        # The occupancy counters are only allocated when something will
        # read them (the LiveStats emitter) — otherwise the prefetch hot
        # path keeps its stats=None fast path (no perf_counter pairs).
        pstats = PrefetchStats(registry=registry)
        # One live 'step' is one optimizer step: under --grad_accum it
        # consumes A micro-batches, so the samples/sec numerator scales.
        live = LiveStats(metrics,
                         global_batch=(args.batch_size * n_replicas
                                       * max(args.grad_accum, 1)),
                         n_chips=n_replicas, log_every=args.log_every,
                         # Window >= cadence: a default 100-step window
                         # under --log_every 500 would silently describe
                         # only each interval's last 20% of steps.
                         window=max(100, args.log_every),
                         model=args.model,
                         device_kind=jax.devices()[0].device_kind,
                         prefetch_stats=pstats)
    # In-run introspection probes (obs/inspect.py), composed into the one
    # bounded per-step callable the trainer exposes.  The periodic .prom
    # rewrite runs whenever the end-of-run scrape file would exist (it
    # shares --obs_off-independence with that path: the registry always
    # exists); the profile trigger needs live spans, so it respects the
    # kill-switch.
    from .obs.inspect import (InspectServer, ProfileTrigger, PromFileWriter,
                              install_sigusr1)
    prom_writer = None
    if args.metrics_path and jax.process_index() == 0:
        prom_writer = PromFileWriter(registry, args.metrics_path + ".prom",
                                     every=max(args.log_every, 1))
    profile_trigger = None
    if not args.obs_off and jax.process_index() == 0:
        profile_trigger = ProfileTrigger(
            tracer,
            (os.path.dirname(os.path.abspath(args.metrics_path))
             if args.metrics_path else os.getcwd()),
            # --profile_dir already owns the process-wide jax profiler
            # for the whole run — a second start_trace would raise.  The
            # CPU backend is also excluded: a mid-run stop_trace there
            # serializes minutes of host-tracing data on the training
            # thread (measured: a 2-step capture stalled a run past its
            # watchdog limit), so on CPU the capture is spans-only.
            profiler_available=(not args.profile_dir
                                and jax.default_backend() != "cpu"))
    probes = [p.step for p in (prom_writer, profile_trigger)
              if p is not None]
    if args.log_every <= 0 and prom_writer is not None:
        probes.remove(prom_writer.step)  # end-of-run write only
    step_probe = None
    if len(probes) == 1:
        step_probe = probes[0]
    elif probes:
        def step_probe(step, _probes=tuple(probes)):
            for p in _probes:
                p(step)
    trainer = Trainer(model, train_loader, params, batch_stats, mesh=mesh,
                      lr_schedule=lr_schedule,
                      sgd_config=SGDConfig(lr=args.lr,
                                           momentum=args.momentum,
                                           weight_decay=args.weight_decay),
                      save_every=args.save_every,
                      snapshot_path=args.snapshot_path,
                      compute_dtype=compute_dtype, seed=args.seed,
                      resume=args.resume, metrics=metrics,
                      device_augment=device_augment, resident=args.resident,
                      shard_update=args.shard_update, sync_bn=args.sync_bn,
                      grad_accum=args.grad_accum,
                      keep_checkpoints=args.keep_checkpoints,
                      on_nan=args.on_nan,
                      watchdog=watchdog, preemption=preemption,
                      prefetch_depth=args.prefetch_depth,
                      prefetch_workers=args.prefetch_workers,
                      prefetch_stats=pstats, tracer=tracer, live=live,
                      tp_plan=tp_plan, pp_plan=pp_plan,
                      pp_schedule=getattr(args, "pp_schedule", "1f1b"),
                      ckpt_format=getattr(args, "ckpt_format", "gathered"),
                      drift_audit_every=getattr(args, "drift_audit_every",
                                                0),
                      drift_action=getattr(args, "drift_action", "abort"),
                      guard_window=getattr(args, "guard_window", 64),
                      guard_spike_factor=getattr(args,
                                                 "guard_spike_factor", 0.0),
                      guard_action=getattr(args, "guard_action",
                                           "rollback"),
                      registry=registry,
                      mirror=getattr(args, "mirror", None),
                      step_probe=step_probe)
    trainer_ref.append(trainer)
    # The inspect server binds ONLY when --inspect_port is given (the
    # zero-sockets contract); constructed after the trainer so /healthz
    # describes a live object from its first request.
    inspect_server = None
    uninstall_sigusr1 = None
    if args.inspect_port is not None and jax.process_index() == 0:
        try:
            inspect_server = InspectServer(args.inspect_port,
                                           registry=registry, tracer=tracer,
                                           health=_health_snapshot,
                                           profile=profile_trigger)
            print(f"inspect: serving /metrics /healthz /spans "
                  f"/debug/profile on 127.0.0.1:{inspect_server.port}",
                  file=sys.stderr)
        except OSError as e:
            # A taken port must not kill a training run — the run is the
            # product, the observation surface is not.
            print(f"WARNING: cannot bind --inspect_port "
                  f"{args.inspect_port}: {e}; continuing without the "
                  "inspect server", file=sys.stderr)
    if profile_trigger is not None and jax.process_index() == 0:
        uninstall_sigusr1 = install_sigusr1(profile_trigger)
    # Test-only fault injection drills (no-op unless DDP_TPU_FAULT is set
    # — resilience/faults.py; the subprocess drills in
    # tests/test_resilience.py drive preemption/NaN/stall through the real
    # CLI surface this way).
    from .resilience.faults import install_env_faults
    install_env_faults(trainer)

    eval_loader = EvalLoader(test_ds, min(args.batch_size, 512), n_replicas,
                             local_replicas=local_replicas)

    resident_test_cache: list = []  # test set uploaded to HBM at most once

    def _eval(progress: bool) -> float:
        # Evaluation computes in the SAME precision as training (the
        # reference evaluates the very model it trained, multigpu.py:247)
        # — under --bf16 that is bf16, which also halves eval's HBM
        # traffic; params themselves are stored fp32 either way.
        # ``plan`` is threaded only when a tp plan exists: the 1-D call
        # keeps the established evaluate()/evaluate_resident() signature
        # (which tests and callers monkeypatch/spy on).
        tp_kw = {} if tp_plan is None else {"plan": tp_plan}
        if pp_plan is not None:
            # Pipeline runs evaluate on stage 0's (data x model) submesh:
            # the stage-scattered params are gathered back onto it first
            # (host round-trip — the stages are disjoint device sets), and
            # the eval itself is the ordinary 2-D program.  d matches the
            # loader's replica count by construction, so EvalLoader's
            # sharding carries over unchanged.
            from .parallel.pp import stage_submesh
            from .parallel.pp.schedule import eval_params_for
            emesh = stage_submesh(mesh, 0)
            eparams, estats = eval_params_for(trainer.state, pp_plan,
                                              tp_plan, emesh)
            return evaluate(model, eparams, estats, eval_loader, emesh,
                            compute_dtype=compute_dtype, progress=progress,
                            **tp_kw)
        if args.resident:
            from .data.resident import ResidentData
            from .train.evaluate import evaluate_resident
            if not resident_test_cache:
                resident_test_cache.append(ResidentData(test_ds, mesh))
            return evaluate_resident(
                model, trainer.state.params, trainer.state.batch_stats,
                resident_test_cache[0], eval_loader, mesh,
                compute_dtype=compute_dtype, **tp_kw)
        return evaluate(model, trainer.state.params,
                        trainer.state.batch_stats, eval_loader, mesh,
                        compute_dtype=compute_dtype, progress=progress,
                        **tp_kw)

    last_periodic_eval: list = []  # [(epoch, accuracy)] — newest only

    def _epoch_callback(epoch: int) -> None:
        # --eval_every: periodic validation (no reference analogue — it
        # evaluates once, after training, multigpu.py:247).  The eval is a
        # collective (sharded psum counters) so every process runs it; the
        # print/metrics record is rank-0-gated like the Trainer's per-step
        # stream, keeping the two metric streams consistent on multi-host.
        if args.eval_every and (epoch + 1) % args.eval_every == 0:
            # Land this epoch's deferred loss records first so the
            # metrics stream stays chronological (the eval blocks on the
            # epoch anyway, so this flush costs nothing; non-eval epochs
            # skip it and keep the boundary pipelined).
            trainer.flush_losses()
            acc = _eval(progress=False)
            last_periodic_eval[:] = [(epoch, acc)]
            if jax.process_index() == 0:
                print(f"Epoch {epoch} | eval accuracy={acc:.2f}%")
                metrics.log_eval(epoch=epoch, accuracy=acc)

    # Postmortem classification for the trainer-lifetime exception wrap:
    # the bundle names WHY the run died in the recorder's closed reason
    # vocabulary, with the exit status the process will actually report.
    def _dump_on_failure(err: BaseException) -> None:
        if recorder is None or recorder.dumped is not None:
            return
        from .resilience.drift import DriftDetectedError
        from .resilience.guard import LossSpikeError, NonFiniteLossError
        from .resilience.preemption import (
            EMERGENCY_CHECKPOINT_EXIT_STATUS, PreemptionInterrupt)
        if isinstance(err, PreemptionInterrupt):
            reason, status = "preemption", EMERGENCY_CHECKPOINT_EXIT_STATUS
        elif isinstance(err, DriftDetectedError):
            reason, status = "drift_abort", 1
        elif isinstance(err, (NonFiniteLossError, LossSpikeError)):
            reason, status = "guard_abort", 1
        else:
            reason, status = "crash", 1
        recorder.dump(reason, exit_status=status, error=repr(err))

    start = time.time()
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        try:
            if watchdog is not None:
                watchdog.start()  # armed for training only (its documented
                #                   epoch/step scope; the heartbeats come
                #                   from the trainer's loops)
            trainer.train(
                args.total_epochs,
                epoch_callback=_epoch_callback if args.eval_every else None)
        except BaseException as err:
            # Flight-recorder dump BEFORE the error propagates into
            # run()'s teardown (which may hard-exit on multi-host): the
            # bundle is the black box an abnormal exit leaves behind.
            _dump_on_failure(err)
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
            # Stop the trace at the end of TRAINING (its documented scope),
            # even on a mid-run failure — an un-stopped trace is empty.
            if args.profile_dir:
                jax.profiler.stop_trace()
        training_time = time.time() - start
        # Reference report block (multigpu.py:230-248).
        print(f"Total training time: {training_time:.2f} seconds")
        fp32_model_size = get_model_size(trainer.state.params, 32)
        print(f"fp32 model has size={fp32_model_size/MiB:.2f} MiB")
        if args.export_torch and jax.process_index() == 0:
            _export_torch(args.model, args.export_torch, trainer)
        # When --eval_every already evaluated after the last epoch, the
        # weights are unchanged — reuse that accuracy instead of a second
        # identical full-test-set collective (minutes at scale).  Every
        # process took the same branch, so multi-host stays in lockstep.
        if model.tokens:
            # No accuracy over classes for a token model, and no held-out
            # corpus: its record is the mean next-token loss a step.
            last = trainer.loss_history[-len(train_loader):]
            print(f"evaluation skipped: {args.model} is a token model "
                  "(accuracy over classes does not apply); mean next-token "
                  f"loss of the last epoch's steps: {np.mean(last):.4f}")
            return float("nan")
        if last_periodic_eval and \
                last_periodic_eval[0][0] == args.total_epochs - 1:
            accuracy = last_periodic_eval[0][1]
        else:
            accuracy = _eval(progress=True)  # reference tqdm, multigpu.py:190
        print(f"fp32 model has accuracy={accuracy:.2f}%")
        if jax.process_index() == 0:
            # The run's headline metric (the accuracy print the reference
            # emits, multigpu.py:247-248) lands in the metrics stream too —
            # the last JSONL/TensorBoard record of the run.
            metrics.log_eval(epoch=args.total_epochs - 1, accuracy=accuracy,
                             final=True)
    finally:
        # A mid-run failure must still land the buffered telemetry: the
        # tf.summary writer buffers minutes of scalars (the JSONL handle
        # is line-buffered).
        metrics.close()
        # End-of-run scrape file: the registry's final exposition, next
        # to the metrics JSONL (rank 0 — same gate as the JSONL itself;
        # crash-atomic like every periodic rewrite, so a scraper racing
        # the run's death never reads a torn exposition).
        if prom_writer is not None:
            prom_writer.write()
        if uninstall_sigusr1 is not None:
            uninstall_sigusr1()
        if inspect_server is not None:
            inspect_server.close()
    return accuracy
