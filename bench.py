"""Benchmark: steady-state training throughput of the flagship model (VGG on
CIFAR-shaped data, the reference's workload — singlegpu.py:134, batch 512,
multigpu.py:259).

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"},
plus "wall_ms_per_step" (MEDIAN-of-windows WALL time per step — includes
host dispatch, so it upper-bounds device-busy time; the profiler gives
the device-only number), the variance-honest fields
"window_ms_per_step" / "median_ms_per_step" / "window_spread_pct" /
"best_window_ms_per_step" (every timed window, so a noisy run is
visible in the record itself and cannot be mistaken for a regression),
and — for models with a FLOP model, on a device kind in the peak table
(obs/live.py) — "mfu" (absolute efficiency, so the driver tail
self-interprets across rounds).  The headline "value"/"vs_baseline"/"mfu"
are computed from the MEDIAN window, not the best: round-over-round
comparisons are conservative by construction; the best window stays in
the record as the steady-state capability bound.
The reference publishes no numbers (SURVEY.md §6; BASELINE.json
"published": {}) and this framework has no chip measurement on the
installed stack yet, so ``vs_baseline`` is 1.0 in every record until the
ledger (ROADMAP A1) supplies a measured denominator.
When the main measurement is fp32 on a real accelerator, a second record
for bf16 (BASELINE.json config #4) is printed to *stderr* — visible in the
driver's recorded tail without breaking the one-stdout-line contract.

Measures the jitted SPMD train step with device-resident data (compile time
and input pipeline excluded — steady-state chip throughput, the
samples/sec/chip metric BASELINE.json names).  Runs on the backend
``JAX_PLATFORMS`` selects; every mode that touches devices says which on
stderr (``device: platform=… device_kind=…``) before it measures.  A sweep
PARENT never initialises a backend — its children own the devices.

``--sweep N1,N2,...`` is the scaling-readiness harness (BASELINE.json's
>=90%-linear north star): one subprocess per device count, each on its own
mesh, reporting per-N samples/sec/chip plus the efficiency-vs-smallest-N
ratio.  On a single-chip/CPU host it runs virtual CPU meshes — a
dispatch+collective-overhead trend, NOT a hardware scaling number; on a pod
it is the real measurement, one command.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ddp_tpu.data import synthetic
from ddp_tpu.models import get_model
from ddp_tpu.obs.live import PEAK_TFLOPS_BF16_PASS, model_mfu
from ddp_tpu.optim import SGDConfig, triangular_lr
from ddp_tpu.parallel import mesh as mesh_lib
from ddp_tpu.parallel.mesh import scan_unroll
from ddp_tpu.train import make_train_step, shard_batch
from ddp_tpu.train.step import init_train_state
from ddp_tpu.utils.platform import (cpu_device_env, device_line,
                                    enable_compile_cache)

# FLOP model + peak table: single home in ddp_tpu/obs/live.py, so the LIVE
# MFU the trainer emits every --log_every steps and the offline bench MFU
# can never disagree on the denominator.


def make_mesh(*args, **kwargs):
    """parallel.make_mesh, announced: every mode that builds a mesh says on
    stderr where it is about to run (stdout stays the one JSON line)."""
    mesh = mesh_lib.make_mesh(*args, **kwargs)
    print(device_line(mesh), file=sys.stderr, flush=True)
    return mesh


def _parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="vgg")
    p.add_argument("--batch_size", default=512, type=int)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--no_bf16", action="store_true",
                   help="Skip the secondary bf16 stderr record")
    p.add_argument("--primary_only", action="store_true",
                   help="Skip the secondary other-dispatch-flavor record "
                        "(sweep children use this: each extra flavor is "
                        "another serial XLA compile)")
    p.add_argument("--steps", default=50, type=int)
    p.add_argument("--warmup", default=10, type=int)
    p.add_argument("--repeats", default=5, type=int,
                   help="Timed windows; the MEDIAN is the headline (a "
                        "best-window headline flatters on a noisy day) "
                        "and every window lands in window_ms_per_step "
                        "with best/spread fields, so the noise is visible "
                        "in the record itself")
    p.add_argument("--mesh_shape", default=None, metavar="D,M[,S]",
                   help="(data x model[ x stage]) mesh for the "
                        "steady-state step bench (parallel/tp/, "
                        "parallel/pp/): --batch_size is per DATA shard; "
                        "the tp plan comes from the model's TP_RECIPE; a "
                        "third entry S>1 times the pipelined step "
                        "(--pp_micro micro-batches, 1F1B) and records "
                        "the measured-vs-predicted bubble fraction")
    p.add_argument("--tp_sweep", default=None, metavar="M1,M2,...",
                   help="Tensor-parallel sweep: one child per model-axis "
                        "size M over the same device total (data axis = "
                        "total/M), at FIXED GLOBAL BATCH --batch_size — "
                        "records ms/step + MFU per mesh shape (the "
                        "model-axis cost curve; chip paste in RUNBOOK "
                        "section 10).  Uses --sweep_platform like --sweep")
    p.add_argument("--pp_sweep", default=None, metavar="S1,S2,...",
                   help="Pipeline-stage sweep: one child per stage count "
                        "S over the same device total (data axis = "
                        "total/S, model axis 1), at FIXED GLOBAL BATCH "
                        "--batch_size x --pp_micro — records ms/step "
                        "plus the MEASURED pipeline-bubble fraction next "
                        "to the static (S-1)/(A+S-1) prediction per "
                        "shape (record: BENCH_r15.json; chip paste in "
                        "RUNBOOK section 21).  S=1 runs the plain "
                        "grad-accum step as the bubble-free baseline.  "
                        "Uses --sweep_platform like --sweep")
    p.add_argument("--pp_micro", default=4, type=int, metavar="A",
                   help="Micro-batches per optimizer step for the "
                        "pipelined bench paths (default 4): the 1F1B "
                        "schedule's A — bubble prediction is "
                        "(S-1)/(A+S-1)")
    p.add_argument("--auto_plan", default=None, metavar="PLAN.json",
                   help="Steady-state step bench under a searched "
                        "sharding plan (python -m ddp_tpu.parallel.tp "
                        "--search --out PLAN.json): the doc drives the "
                        "mesh shape, layout recipe and ZeRO choice; "
                        "--batch_size stays per DATA shard")
    p.add_argument("--autoplan_bench", action="store_true",
                   help="Hand recipe vs searched auto plan, MEASURED "
                        "(ISSUE 17 acceptance; record: BENCH_r13.json): "
                        "per --autoplan_models model, run the cost-model "
                        "search over the device total, then one bench "
                        "child per configuration at FIXED GLOBAL BATCH "
                        "--batch_size — the hand TP_RECIPE at model axis "
                        "4 (pure DP when the model has no recipe) vs the "
                        "searched plan via --auto_plan.  Headline: the "
                        "worst-case hand/auto ms/step speedup (>= 1.0 "
                        "means the search matched or beat every hand "
                        "configuration).  Needs --calib (the fitted "
                        "coefficients); uses --sweep_platform like "
                        "--sweep")
    p.add_argument("--autoplan_models", default="deepnn,vgg",
                   metavar="M1,M2,...",
                   help="--autoplan_bench model list (default "
                        "deepnn,vgg: one model WITH a hand TP_RECIPE to "
                        "beat, one without — the search must also learn "
                        "when NOT to shard)")
    p.add_argument("--calib", default=None, metavar="CALIB.json",
                   help="(--autoplan_bench) calibrated-coefficient "
                        "source: a bench.py --calibrate_cost record (or "
                        "a prior auto-plan JSON)")
    p.add_argument("--ckpt_bench", action="store_true",
                   help="Checkpoint-path bench (ISSUE 6): save + restore "
                        "wall time and PEAK HOST RSS for the gathered (v1) "
                        "vs sharded (v2, train/ckpt_shard.py) formats at "
                        "each --ckpt_sizes model size.  One child process "
                        "per (size, format, phase) so ru_maxrss cleanly "
                        "attributes each phase's peak; saves run on a "
                        "(2,4) 8-virtual-device mesh, restores reshard "
                        "onto (2,2)x4 (the elastic-resume path).  Record: "
                        "BENCH_r08.json; chip paste in RUNBOOK section 11")
    p.add_argument("--ckpt_sizes", default="32,128", metavar="MB1,MB2,...",
                   help="--ckpt_bench checkpoint payload sizes in MiB "
                        "(params + momentum, fp32; default 32,128)")
    p.add_argument("--ckpt_bench_child", default=None,
                   choices=["save", "restore"],
                   help="(internal) --ckpt_bench child phase")
    p.add_argument("--ckpt_format", default="gathered",
                   choices=["gathered", "sharded"],
                   help="(--ckpt_bench child) checkpoint layout under test")
    p.add_argument("--ckpt_size_mb", default=32, type=int,
                   help="(--ckpt_bench child) payload size in MiB")
    p.add_argument("--num_devices", default=None, type=int,
                   help="Mesh size (default: all visible devices)")
    p.add_argument("--calibrate_cost", action="store_true",
                   help="Calibrate the static cost model (ddp_tpu/"
                        "analysis/costmodel.py): fit per-op-class time "
                        "coefficients (s/FLOP for conv and dot, s/byte "
                        "for elementwise traffic and collective payload) "
                        "from short measured probes — the ops/"
                        "conv_probe.py methodology: best-of jitted "
                        "dependency-linked chains, marginal "
                        "differencing — then price every analysis-"
                        "registry program's static cost table through "
                        "them and print predicted ms/step next to a "
                        "measured ms/step for the data-parallel train "
                        "step.  Audits the analysis registry's model "
                        "(deepnn unless --model overrides); on a CPU "
                        "host set XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=8 for the full (2,4)x8 registry")
    p.add_argument("--ledger_spill", default=None, metavar="SPILL",
                   help="(--calibrate_cost only) also join this span "
                        "spill (--trace_spill output of a traced run) "
                        "against the freshly fitted predictions into the "
                        "efficiency ledger (obs/ledger.py) and embed it "
                        "in the JSON record — predictions scaled by the "
                        "mesh's device count (virtual-mesh shard "
                        "serialization)")
    p.add_argument("--guard_overhead", action="store_true",
                   help="Round 12: price the step-level fault domain on "
                        "the steady-state step loop — ms/step with the "
                        "drift audit off, at --drift K=50, K=10, and with "
                        "the spike guard's host-side window check on.  "
                        "The audit's synchronous host verdict read (a "
                        "2*L*4-byte psum pair + device_get every K steps) "
                        "is the cost being measured; acceptance is < 1% "
                        "ms/step at K=50.  Record: BENCH_r10.json")
    p.add_argument("--mem_ledger", action="store_true",
                   help="Round 14: the memory twin of the efficiency "
                        "ledger (obs/memledger.py) — per-program MEASURED "
                        "committed device bytes vs the liveness model's "
                        "resident-set prediction, one pinned-mesh "
                        "subprocess per program, with the static "
                        "orderings (TP < 1-D, ZeRO < non-ZeRO) asserted "
                        "on the measured numbers.  Record: BENCH_r14.json")
    p.add_argument("--mem_ledger_child", default=None, metavar="PROGRAM",
                   help="(internal) measure one named program's memory in "
                        "THIS process and print the JSON record — the "
                        "--mem_ledger parent spawns one child per program "
                        "so XLA compile arenas never cross-pollute "
                        "measurements")
    p.add_argument("--mem_programs", default=None, metavar="P1,P2,...",
                   help="--mem_ledger program list override (default: "
                        "obs/memledger.py DEFAULT_PROGRAMS)")
    p.add_argument("--inspect_overhead", action="store_true",
                   help="Round 14: price an ENABLED-BUT-IDLE live "
                        "introspection plane (--inspect_port) on the "
                        "steady-state step loop: bound HTTP server + the "
                        "per-step probe (periodic .prom rewrite + unarmed "
                        "profile trigger) vs the bare loop, round-robin "
                        "windows.  Acceptance: < 1% ms/step.  Record: "
                        "BENCH_r14.json")
    p.add_argument("--batch_sweep", default=None, metavar="B1,B2,...",
                   help="MFU-vs-per-chip-batch sweep (VERDICT r5 next #1): "
                        "one subprocess per (batch, flavor) cell on the "
                        "SAME mesh, reporting median-based samples/sec/"
                        "chip + mfu per cell.  BN-stats/pool/DMA work that "
                        "does not grow with the batch is amortised by a "
                        "larger one (size on the chip: not measured); "
                        "the batch knob is "
                        "the reference's own (multigpu.py:259).  Pod/chip "
                        "recording: --batch_sweep 256,512,1024,2048")
    p.add_argument("--batch_sweep_flavors",
                   default="fp32_step,fp32_scan,bf16_step,bf16_scan",
                   metavar="F1,F2,...",
                   help="Cells per batch size: comma list from {fp32,bf16}"
                        "_{step,scan} (default: all four — precision x "
                        "dispatch flavor; CI smoke narrows this to one "
                        "to keep the serial-compile cost bounded)")
    p.add_argument("--sweep", default=None, metavar="N1,N2,...",
                   help="Scaling harness: one subprocess per device count "
                        "(virtual CPU meshes unless --sweep_platform real), "
                        "reporting per-N samples/sec/chip + efficiency")
    p.add_argument("--sweep_platform", default="cpu", choices=["cpu", "real"],
                   help="cpu: each sweep child forces an N-device virtual "
                        "CPU mesh (dispatch-overhead trend, no hardware "
                        "needed); real: children use the visible devices "
                        "(the actual scaling measurement on a pod)")
    p.add_argument("--shard_update", action="store_true",
                   help="Bench the ZeRO-1-style weight-update-sharded step "
                        "(reduce-scatter + sharded SGD + all-gather, "
                        "train/zero.py) instead of the replicated-update "
                        "step; composes with --sweep so the one-command pod "
                        "measurement covers the collective pattern that "
                        "matters at scale")
    p.add_argument("--dispatch", default="step", choices=["step", "scan"],
                   help="step (default): one dispatch per step — JAX async "
                        "dispatch pipelines these; scan: the whole window "
                        "as one jitted lax.scan (the resident-epoch "
                        "mode's dispatch pattern)")
    p.add_argument("--profile_dir", default=None,
                   help="Capture a jax.profiler trace of one extra "
                        "(untimed) window of the SELECTED --dispatch "
                        "flavor (the per-op breakdown; analyze with "
                        "python -m ddp_tpu.utils.profiling)")
    p.add_argument("--dump_hlo", default=None, metavar="PATH",
                   help="Write the compiled train step's optimized HLO "
                        "text — the file ddp_tpu.utils.profiling --hlo "
                        "consumes to disambiguate conv fusions, from the "
                        "SAME program the trace/timing ran (fusion "
                        "numbering is not stable across programs)")
    p.add_argument("--pipeline", action="store_true",
                   help="Time the HOST side only: loader materialisation + "
                        "augmentation, no device in the loop — isolates "
                        "input-pipeline throughput from H2D bandwidth "
                        "for the host-fed-vs-resident gap attribution")
    p.add_argument("--stream_attr", action="store_true",
                   help="Streaming-gap attribution (VERDICT r5 weak #5): "
                        "measure host-augment, H2D upload, and the device "
                        "step each in ISOLATION at the training shape, "
                        "then the end-to-end streaming epoch through the "
                        "real Trainer + prefetch engine, and decompose "
                        "the wall time by the pipeline model (wall == "
                        "slowest stage when perfectly overlapped; the "
                        "excess is dispatch gap).  Composes with "
                        "--prefetch_depth/--prefetch_workers for "
                        "before/after overlap measurements and --bf16")
    p.add_argument("--prefetch_depth", default=2, type=int, metavar="D",
                   help="Streaming engine in-flight depth for --e2e/"
                        "--stream_attr (0 = unpipelined reference shape; "
                        "default 2 = the CLI default)")
    p.add_argument("--prefetch_workers", default=4, type=int, metavar="W",
                   help="Streaming engine host workers for --e2e/"
                        "--stream_attr (default 4 = the CLI default)")
    p.add_argument("--e2e", action="store_true",
                   help="Time full Trainer epochs (input pipeline + "
                        "augmentation + H2D + step) instead of the "
                        "device-resident steady-state step")
    p.add_argument("--resident", action="store_true",
                   help="With --e2e: HBM-resident dataset + one lax.scan "
                        "per epoch (on-device augmentation) instead of "
                        "host-fed per-step batches")
    p.add_argument("--e2e_steps", default=16, type=int,
                   help="With --e2e: steps per epoch (dataset size = "
                        "batch x chips x this; 98 reproduces the real "
                        "CIFAR-10 epoch length and amortises the "
                        "per-epoch dispatch the 16-step default "
                        "overstates)")
    p.add_argument("--serve", action="store_true",
                   help="Load-generate against the serving stack "
                        "(ddp_tpu/serve/): closed-loop capacity probe, "
                        "then an open-loop offered-load sweep recording "
                        "p50/p90/p99 latency + achieved throughput per "
                        "point and locating the saturation knee — the "
                        "latency-vs-load curve a capacity plan reads")
    p.add_argument("--fleet", default=1, type=int, metavar="N",
                   help="With --serve: drive N engine replicas behind "
                        "the fault-tolerant router (serve/fleet.py) "
                        "instead of one bare engine+batcher — the "
                        "knee-vs-N scaling record (default 1)")
    p.add_argument("--serve_loads", default="auto", metavar="R1,R2,...",
                   help="Offered loads (requests/sec) for the open-loop "
                        "sweep; 'auto' derives 4 points bracketing the "
                        "measured closed-loop capacity (0.4/0.7/1.0/"
                        "1.3x) so the knee is inside the sweep by "
                        "construction")
    p.add_argument("--serve_secs", default=4.0, type=float,
                   help="Seconds per load point (default 4)")
    p.add_argument("--serve_buckets", default="1,8,32,128",
                   help="Engine padded-batch bucket set (compiled once "
                        "at startup; default 1,8,32,128)")
    p.add_argument("--serve_max_wait_ms", default=5.0, type=float,
                   help="Batch-forming wait budget (default 5 ms)")
    p.add_argument("--serve_queue_depth", default=256, type=int,
                   help="Admission queue bound (default 256)")
    p.add_argument("--serve_conc", default=8, type=int,
                   help="Closed-loop concurrent clients (default 8)")
    p.add_argument("--serve_rows", default=1, type=int,
                   help="Image rows per request (default 1 — the "
                        "single-user online shape)")
    p.add_argument("--snapshot_path", default=None,
                   help="With --serve: serve this trained checkpoint "
                        "(head path or directory) instead of fresh-init "
                        "weights — the full lineage-load path bench")
    p.add_argument("--generate", action="store_true",
                   help="With --serve: bench GENERATIVE decoding (the "
                        "tinylm KV-cache engine + token-level continuous "
                        "batcher) instead of the classifier stack — "
                        "tokens/sec and TTFT vs concurrent streams")
    p.add_argument("--gen_streams", default="1,2,4,8",
                   metavar="S1,S2,...",
                   help="With --generate: concurrent client-stream "
                        "counts to sweep (default 1,2,4,8; each point "
                        "runs --serve_secs seconds)")
    p.add_argument("--gen_prompt_len", default=8, type=int,
                   help="With --generate: prompt tokens per stream "
                        "(default 8)")
    p.add_argument("--gen_new_tokens", default=16, type=int,
                   help="With --generate: tokens generated per stream "
                        "(default 16)")
    p.add_argument("--gen_slots", default=8, type=int,
                   help="With --generate: KV-cache slots (the decode "
                        "batch width; default 8)")
    p.add_argument("--gen_prefill_buckets", default="16,64",
                   help="With --generate: padded prompt buckets "
                        "(default 16,64)")
    p.add_argument("--chaos", action="store_true",
                   help="Run the chaos campaign (tools/chaos_campaign.py): "
                        "the DDP_TPU_FAULT drill matrix under "
                        "python -m ddp_tpu.supervise, scored per drill on "
                        "restarts used, time-to-recover, and final-state "
                        "bit-parity vs an undisturbed control.  Record: "
                        "CHAOS_r01.json (NOT a BENCH_* headline — "
                        "bench_trend ignores CHAOS_* files)")
    p.add_argument("--chaos_out", default="CHAOS_r01.json",
                   help="--chaos scorecard path (default CHAOS_r01.json)")
    p.add_argument("--chaos_drills", default=None, metavar="D1,D2,...",
                   help="--chaos drill subset (default: the full matrix; "
                        "CI smoke uses sigterm_step,watchdog_stall)")
    return p.parse_args()


def main() -> None:
    args = _parse_args()
    enable_compile_cache()
    if args.dump_hlo and (args.sweep or args.pipeline or args.e2e
                          or args.batch_sweep or args.stream_attr
                          or args.serve or args.tp_sweep or args.pp_sweep
                          or args.ckpt_bench or args.ckpt_bench_child
                          or args.calibrate_cost or args.guard_overhead
                          or args.autoplan_bench or args.mem_ledger
                          or args.mem_ledger_child or args.inspect_overhead):
        raise SystemExit("--dump_hlo only applies to the steady-state step "
                         "bench (it dumps the timed step/scan program); it "
                         "has no program to dump in --sweep/--batch_sweep/"
                         "--pipeline/--e2e/--stream_attr/--serve/--tp_sweep/"
                         "--ckpt_bench modes")
    if args.chaos:
        _bench_chaos(args)
        return
    if args.ckpt_bench_child:
        _bench_ckpt_child(args)
        return
    if args.ckpt_bench:
        _bench_ckpt(args)
        return
    if args.calibrate_cost:
        _bench_calibrate_cost(args)
        return
    if args.autoplan_bench:
        _bench_autoplan(args)
        return
    if args.guard_overhead:
        _bench_guard_overhead(args)
        return
    if args.mem_ledger_child:
        _bench_mem_ledger_child(args)
        return
    if args.mem_ledger:
        _bench_mem_ledger(args)
        return
    if args.inspect_overhead:
        _bench_inspect_overhead(args)
        return
    if args.serve:
        if args.generate:
            _bench_generate(args)
        else:
            _bench_serve(args)
        return
    if args.tp_sweep:
        _bench_tp_sweep(args)
        return
    if args.pp_sweep:
        _bench_pp_sweep(args)
        return
    if args.batch_sweep:
        _bench_batch_sweep(args)
        return
    if args.sweep:
        _bench_sweep(args)
        return
    if args.pipeline:
        _bench_pipeline(args)
        return
    if args.stream_attr:
        _bench_stream_attr(args)
        return
    if args.e2e:
        _bench_e2e(args)
        return

    recs = _bench_step(args, bf16=args.bf16, extras=not args.primary_only)
    print(json.dumps(recs[0]))
    for rec in recs[1:]:
        print(json.dumps(rec), file=sys.stderr)
    # Secondary bf16 record (driver runs fp32 only; without this the bf16
    # capability is invisible to BENCH_r*.json tails).  Real accelerators
    # only — CPU-mesh tests/sweeps stay single-measurement and fast.
    if not args.bf16 and not args.no_bf16 and \
            args.profile_dir is None and jax.default_backend() != "cpu":
        print(json.dumps(_bench_step(args, bf16=True, extras=False)[0]),
              file=sys.stderr)


def _bench_chaos(args) -> None:
    """The chaos campaign, as a bench mode: a subprocess around
    tools/chaos_campaign.py (each drill spawns its own supervised
    training children with a pinned CPU-mesh environment — the tool
    owns that env, not this process).  Propagates the campaign's
    pass/fail exit."""
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "chaos_campaign.py")
    cmd = [sys.executable, tool, "--out", args.chaos_out]
    if args.chaos_drills:
        cmd += ["--drills", args.chaos_drills]
    rc = subprocess.call(cmd)
    if rc != 0:
        raise SystemExit(rc)


def _bench_step(args, *, bf16: bool, extras: bool = True) -> list:
    """Steady-state train-step throughput on the requested mesh.  Returns
    records, primary first.  ``--dispatch step`` (the default) issues
    one dispatch per step, pipelined by JAX async dispatch;
    ``--dispatch scan`` issues the window as ONE jitted ``lax.scan`` (the
    resident-epoch mode's dispatch pattern).  With ``extras``, the other
    flavor is also measured and reported (stderr)."""
    plan = None
    # getattr: callers hand-build Namespaces without the tp flag
    # (tests/test_round3_fixes.py's precedent for late-added knobs).
    mesh_shape = getattr(args, "mesh_shape", None)
    auto_doc = None
    if getattr(args, "auto_plan", None):
        # A searched plan doc drives mesh shape, recipe AND the ZeRO
        # choice — the same contract as the CLI's --auto_plan.
        from ddp_tpu.parallel.tp.autoplan import read_plan_doc
        auto_doc = read_plan_doc(args.auto_plan)
        if auto_doc["model"] != args.model:
            raise SystemExit(f"--auto_plan was searched for "
                             f"{auto_doc['model']!r}, not {args.model!r}")
        dims = tuple(int(v) for v in auto_doc["mesh_shape"])
        d_m = dims[:2]
        pp_s = dims[2] if len(dims) > 2 else 1
        mesh_shape = ",".join(map(str, dims))
        mesh = make_mesh(shape=dims)
        if auto_doc.get("zero"):
            args.shard_update = True
    elif mesh_shape:
        try:
            dims = tuple(int(x) for x in mesh_shape.split(","))
            if len(dims) not in (2, 3) or min(dims) < 1:
                raise ValueError(mesh_shape)
        except ValueError:
            raise SystemExit(f"--mesh_shape wants 'D,M' or 'D,M,S' (e.g. "
                             f"2,4 or 2,1,2), got {mesh_shape!r}")
        d_m = dims[:2]
        pp_s = dims[2] if len(dims) > 2 else 1
        mesh = make_mesh(shape=dims)
    else:
        pp_s = 1
        mesh = make_mesh(args.num_devices)
    if pp_s > 1:
        if args.shard_update:
            raise SystemExit("--shard_update does not compose with a "
                             "staged mesh: the pipeline update is already "
                             "per-stage (each stage owns only its own "
                             "params/momentum)")
        if args.dispatch == "scan":
            raise SystemExit("--dispatch scan cannot wrap the pipeline "
                             "step (the 1F1B schedule is a host-driven op "
                             "loop, not one jittable program); use "
                             "--dispatch step with a staged --mesh_shape")
        if getattr(args, "dump_hlo", None):
            raise SystemExit("--dump_hlo has no single program to dump "
                             "under a staged mesh (one jitted program per "
                             "stage x role); audit them with python -m "
                             "ddp_tpu.analysis --mesh-shape D,M,S instead")
    n_chips = mesh.devices.size
    model = get_model(args.model)
    params, stats = model.init(jax.random.key(0))
    if auto_doc is not None:
        from ddp_tpu.parallel.tp.autoplan import plan_from_doc
        plan = plan_from_doc(auto_doc, jax.device_get(params), stats)
    elif mesh_shape:
        from ddp_tpu.parallel.tp.plan import plan_for_model
        plan = plan_for_model(args.model, jax.device_get(params), stats,
                              model_size=d_m[1])
    schedule = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                                 steps_per_epoch=98)
    compute_dtype = jnp.bfloat16 if bf16 else None
    pp_plan = None
    if pp_s > 1:
        from ddp_tpu.obs.tracer import get_tracer
        from ddp_tpu.parallel.pp import plan_stages
        from ddp_tpu.parallel.pp.schedule import make_pp_step, place_state
        pp_plan = plan_stages(args.model, pp_s, model_size=d_m[1],
                              params=jax.device_get(params),
                              batch_stats=stats)
        # tracer: the first call per micro-count A is per-op timed, which
        # is what fills step_fn.bubble (the measured-vs-predicted record).
        step_fn = make_pp_step(args.model, SGDConfig(), schedule, mesh,
                               pp_plan, compute_dtype=compute_dtype,
                               tp_plan=plan, tracer=get_tracer())
        state = place_state(init_train_state(params, stats), mesh, pp_plan,
                            tp_plan=plan)
    elif args.shard_update:
        from ddp_tpu.train.step import TrainState
        from ddp_tpu.train.zero import init_opt_shard
        step_fn = make_train_step(model, SGDConfig(), schedule, mesh,
                                  compute_dtype=compute_dtype, plan=plan,
                                  shard_update=True)
        state = TrainState(params, stats,
                           init_opt_shard(params, mesh, plan=plan),
                           jnp.zeros((), jnp.int32))
    else:
        step_fn = make_train_step(model, SGDConfig(), schedule, mesh,
                                  compute_dtype=compute_dtype, plan=plan)
        state = init_train_state(params, stats)
    if plan is not None and pp_s == 1:
        from ddp_tpu.parallel.tp.plan import state_shardings
        state = jax.device_put(
            state, state_shardings(plan, mesh, zero=args.shard_update))

    from ddp_tpu.parallel.mesh import data_axis_size
    global_batch = args.batch_size * data_axis_size(mesh)
    if pp_s > 1:
        from ddp_tpu.parallel.pp.schedule import pp_shard_fn
        pp_a = max(int(getattr(args, "pp_micro", 4)), 1)
        ds, _ = synthetic(n_train=global_batch * pp_a, n_test=1)
        imgs = (ds.images.astype(np.float32) / 255.0).reshape(
            (pp_a, global_batch) + ds.images.shape[1:])
        batch = pp_shard_fn(pp_plan)(
            {"image": imgs,
             "label": ds.labels.reshape(pp_a, global_batch)}, mesh)
    else:
        pp_a = 1
        ds, _ = synthetic(n_train=global_batch, n_test=1)
        batch = shard_batch({"image": ds.images.astype(np.float32) / 255.0,
                             "label": ds.labels}, mesh)
    rng = jax.random.key(0)

    def time_windows(run_window) -> list:
        """Per-repeat wall times of one window, each ending in
        ``block_until_ready`` on the last loss.  ALL windows are returned,
        not just the best: the per-window spread is the bench contract's
        variance evidence."""
        dts = []
        for _ in range(max(args.repeats, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(run_window())
            dts.append(time.perf_counter() - t0)
        return dts

    def record(tag: str, dts: list, extra: dict = None) -> dict:
        dt = statistics.median(dts)  # the headline window: conservative
        #               by construction (VERDICT r5 weak #1); min(dts) is
        #               the steady-state capability bound and stays in the
        #               record as best_window_ms_per_step
        sps_chip = global_batch * pp_a * args.steps / dt / n_chips
        axes_tag = "data x model x stage" if pp_s > 1 else "data x model"
        micro_tag = f"{pp_a} micro-batches/step, " if pp_s > 1 else ""
        mesh_tag = ((f"{'auto-plan ' if auto_doc is not None else ''}"
                     f"mesh {mesh_shape} ({axes_tag}), {micro_tag}")
                    if mesh_shape else "")
        rec = {
            "metric": f"{args.model} train samples/sec/chip "
                      f"(batch {args.batch_size}/chip, "
                      f"{'bf16' if bf16 else 'fp32'}, {n_chips} chip(s), "
                      f"{mesh_tag}"
                      f"{'zero-sharded update, ' if args.shard_update else ''}"
                      f"{tag})",
            "value": round(sps_chip, 2),
            "unit": "samples/sec/chip",
            # No measured denominator exists on this stack (ROADMAP A1);
            # when one does, compare MATCHING modes only.
            "vs_baseline": 1.0,
            # Absolute-efficiency context so the driver tail self-
            # interprets across rounds.  Named for what it is: WALL time
            # per step (the window includes host dispatch), an upper
            # bound on device-busy.  == median_ms_per_step (the headline
            # window).
            "wall_ms_per_step": round(dt / args.steps * 1000.0, 3),
            # Variance-honest contract: every window's ms/step plus
            # median/best/spread.  Reading rule: a large spread_pct marks
            # a noisy measurement — compare median_ms_per_step across
            # rounds before calling a headline delta a regression;
            # best_window is the capability bound a quiet run reaches.
            "window_ms_per_step": [round(d / args.steps * 1000.0, 3)
                                   for d in dts],
            "median_ms_per_step": round(
                statistics.median(dts) / args.steps * 1000.0, 3),
            "best_window_ms_per_step": round(
                min(dts) / args.steps * 1000.0, 3),
            "window_spread_pct": round(
                (max(dts) - min(dts)) / min(dts) * 100.0, 1),
        }
        kind = jax.devices()[0].device_kind
        mfu = model_mfu(sps_chip, args.model, kind)
        if mfu is not None:  # device kind in the peak table only
            rec["mfu"] = round(mfu, 4)
            rec["mfu_peak_tflops"] = PEAK_TFLOPS_BF16_PASS[kind]
        if extra:
            rec.update(extra)
        return rec

    def step_window():
        nonlocal state
        for _ in range(args.steps):
            state, loss = step_fn(state, batch, rng)
        return loss

    # At least one warmup step always runs (it also triggers compilation).
    for _ in range(max(args.warmup, 1)):
        state, loss = step_fn(state, batch, rng)
    float(loss)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scan_window_fn(state):
        def body(st, _):
            st, loss = step_fn(st, batch, rng)
            return st, loss
        # scan_unroll: XLA:CPU compiles conv-in-while-loop to a naive
        # fallback (~30x; parallel/mesh.py) — unroll short CPU-mesh windows
        # (driver-contract tests, --sweep_platform cpu); TPU stays rolled.
        state, losses = jax.lax.scan(body, state, None, length=args.steps,
                                     unroll=scan_unroll(mesh, args.steps))
        return state, losses[-1]

    def scan_window():
        nonlocal state
        state, loss = scan_window_fn(state)
        return loss

    if getattr(args, "dump_hlo", None) and bf16 == args.bf16:
        # Dump the program of the SELECTED dispatch flavor (the one the
        # trace/timing runs — the flag's whole point is same-program
        # fusion numbering), and only on the PRIMARY precision pass: the
        # secondary bf16 stderr pass re-enters this function and would
        # silently overwrite the file with the other precision's HLO.
        lowered = (scan_window_fn.lower(state) if args.dispatch == "scan"
                   else step_fn.lower(state, batch, rng))
        with open(args.dump_hlo, "w") as f:
            f.write(lowered.compile().as_text())

    step_tag = f"{args.steps}-step window, per-step dispatch"
    scan_tag = f"{args.steps}-step scan dispatch (resident-epoch mode)"
    # Record what program SHAPE the scan flavor timed (ADVICE r5): on CPU
    # meshes scan_unroll fully unrolls windows <= 32 steps, a different
    # program from the rolled loop earlier rounds measured — without this
    # marker, cross-round CPU scan-flavor comparisons silently compare
    # rolled against unrolled.  scan_unroll=1 means rolled; N means N
    # bodies inlined per loop iteration (== steps here: fully unrolled).
    _su = scan_unroll(mesh, args.steps)
    scan_extra = {"scan_unroll": args.steps if _su is True else int(_su),
                  "scan_rolled": _su is not True and int(_su) < args.steps}
    primary_is_step = args.dispatch == "step"
    if pp_s > 1:
        # The pipelined step has exactly one dispatch flavor (the host-
        # driven 1F1B op loop); its record carries the bubble accounting
        # the warmup's per-op timed pass measured.
        pp_extra = {"pp": dict(step_fn.bubble or {})}
        return [record(f"{args.steps}-step window, 1F1B pipeline dispatch",
                       time_windows(step_window), extra=pp_extra)]
    if not primary_is_step or (extras and args.profile_dir is None):
        float(scan_window())  # compile the scanned program when needed
    primary = step_window if primary_is_step else scan_window
    if args.profile_dir:
        # One traced (untimed) window of the SELECTED flavor — tracing
        # skews wall-clock, so it never sets dt.
        jax.profiler.start_trace(args.profile_dir)
        float(primary())
        jax.profiler.stop_trace()
    recs = [record(step_tag if primary_is_step else scan_tag,
                   time_windows(primary),
                   extra=None if primary_is_step else scan_extra)]
    if extras and args.profile_dir is None:
        other = scan_window if primary_is_step else step_window
        recs.append(record(scan_tag if primary_is_step else step_tag,
                           time_windows(other),
                           extra=scan_extra if primary_is_step else None))
    return recs


def _sweep_total(args, mode: str) -> int:
    """A sweep's device total, from ``--num_devices``.  The parent never
    asks a backend: a parent that has touched JAX holds the chips, and
    its children, one per mesh shape, could not get them."""
    if not args.num_devices:
        raise SystemExit(
            f"{mode} needs --num_devices N (the device total its children "
            "split): the sweep parent does not initialise a backend")
    return args.num_devices


def _run_child(child: list, env: dict, label: str) -> dict:
    """Run a bench subprocess and return its (first valid) bench-record
    JSON line — the shared child contract of the sweep modes (ADVICE r2:
    stray stdout chatter degrades to a clear error, not a json crash)."""
    out = subprocess.run(child, env=env, capture_output=True, text=True)
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-2000:])
        raise SystemExit(f"{label} failed rc={out.returncode}")
    # The parent touches no backend, so where the cell ran is the
    # child's to say: pass its start-up line on.
    for line in out.stderr.splitlines():
        if line.startswith("device: "):
            print(f"[{label}] {line}", file=sys.stderr)
    for line in out.stdout.strip().splitlines():
        try:
            cand = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(cand, dict) and "value" in cand:
            return cand
    sys.stderr.write(out.stdout[-2000:])
    raise SystemExit(f"{label}: no bench-record JSON line on stdout")


def _bench_batch_sweep(args) -> None:
    """MFU-vs-per-chip-batch curve (VERDICT r5 next #1): per (batch,
    precision x dispatch flavor) cell, one subprocess on the same mesh —
    each cell is a fresh XLA program, and a child per cell keeps the
    serial compiles isolated exactly like --sweep's children.  Emits ONE
    JSON line whose ``batch_sweep`` dict holds median-based
    samples/sec/chip (+ mfu on device kinds with a measured peak) per
    cell; the headline ``value`` is the best cell mfu when available
    (the curve's whole point: does a larger batch amortise the
    batch-invariant BN-stats/pool/DMA work above the batch-512 MFU?),
    else the best cell samples/sec/chip."""
    batches = [int(x) for x in args.batch_sweep.split(",")]
    flavors = [f.strip() for f in args.batch_sweep_flavors.split(",") if f]
    valid = {"fp32_step", "fp32_scan", "bf16_step", "bf16_scan"}
    if not set(flavors) <= valid:
        raise SystemExit(f"--batch_sweep_flavors: unknown flavor(s) "
                         f"{sorted(set(flavors) - valid)}; pick from "
                         f"{sorted(valid)}")
    table: dict = {}
    for b in batches:
        table[str(b)] = {}
        for flavor in flavors:
            prec, disp = flavor.split("_")
            child = [sys.executable, os.path.abspath(__file__),
                     "--model", args.model, "--batch_size", str(b),
                     "--steps", str(args.steps),
                     "--warmup", str(args.warmup),
                     "--repeats", str(args.repeats),
                     "--no_bf16", "--primary_only", "--dispatch", disp]
            child += ["--bf16"] if prec == "bf16" else []
            child += ["--shard_update"] if args.shard_update else []
            if args.num_devices:
                child += ["--num_devices", str(args.num_devices)]
            rec = _run_child(child, dict(os.environ),
                             f"batch-sweep cell batch={b} {flavor}")
            cell = {"samples_per_sec_per_chip": rec["value"],
                    "median_ms_per_step": rec["median_ms_per_step"],
                    "best_window_ms_per_step":
                        rec["best_window_ms_per_step"],
                    "window_spread_pct": rec["window_spread_pct"]}
            if "mfu" in rec:
                cell["mfu"] = rec["mfu"]
            table[str(b)][flavor] = cell
    cells = [(b, f, c) for b, fl in table.items() for f, c in fl.items()]
    has_mfu = all("mfu" in c for _, _, c in cells)
    peak = max(cells, key=lambda x: x[2].get("mfu",
                                             x[2]["samples_per_sec_per_chip"]))
    print(json.dumps({
        "metric": f"{args.model} MFU-vs-batch sweep (per-chip batches "
                  f"{batches}, flavors {flavors}"
                  f"{', zero-sharded update' if args.shard_update else ''})",
        "value": (peak[2]["mfu"] if has_mfu
                  else peak[2]["samples_per_sec_per_chip"]),
        "unit": (f"peak mfu over sweep (at batch {peak[0]}, {peak[1]})"
                 if has_mfu else
                 f"peak samples/sec/chip over sweep (at batch {peak[0]}, "
                 f"{peak[1]}; no measured MXU peak for this device kind)"),
        "vs_baseline": 1.0,
        "batch_sweep": table,
    }))


def _bench_stream_attr(args) -> None:
    """Streaming-gap attribution: the table decomposing the host-fed
    streaming path's wall time into host-augment / H2D / device-step /
    dispatch-gap, plus the
    end-to-end streaming epoch through the real Trainer with the prefetch
    engine's own occupancy counters (consumer wait ~ 0 == the input
    pipeline is hidden).

    Since round 7 the record also carries the span TRACER'S account of
    the timed streaming epochs themselves (obs/tracer.py — the same
    instrumentation a production run spills): a ``phase_ms`` median
    block per phase, so BENCH_r0N.json trajectories stay attributable
    across rounds.  The three ``attribute_streaming`` STAGE inputs stay
    isolated measurements ON PURPOSE: the pipeline-floor model needs
    each stage's uncontended sequential cost, and the in-run spans
    measure something else — h2d/dispatch spans are async-dispatch
    *enqueue* times (~0 exactly when the link is the wall), and
    host_augment span walls inflate under worker contention (4 workers
    sharing cores time ~4x the sequential cost).  Spans explain the run
    you ran; the isolated stages bound the run you could have.

    Pipeline model: perfectly overlapped, wall/step == max(stage); the
    excess is serialization the engine failed to hide.  On a real TPU the
    same run under --profile_dir gives the device-idle cross-check
    (utils/profiling.py:device_busy_ms_per_step)."""
    import contextlib
    import io

    from ddp_tpu.data import PrefetchStats, TrainLoader
    from ddp_tpu.obs.aggregate import phase_medians
    from ddp_tpu.obs.tracer import SpanTracer
    from ddp_tpu.train import Trainer
    from ddp_tpu.utils.profiling import attribute_streaming

    mesh = make_mesh(args.num_devices)
    n_chips = mesh.devices.size
    model = get_model(args.model)
    params, stats = model.init(jax.random.key(0))
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    steps = args.e2e_steps
    n_train = args.batch_size * n_chips * steps
    train_ds, _ = synthetic(n_train=n_train)
    loader = TrainLoader(train_ds, args.batch_size, n_chips, augment=True)
    repeats = max(args.repeats, 1)

    def _t(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    def median_epoch_s(run_epoch) -> float:
        return statistics.median([_t(run_epoch) for _ in range(repeats)])

    # Isolated stage — host augment+materialise, SEQUENTIAL (the
    # pipeline-floor model needs the stage's uncontended per-step cost;
    # the real run's host_augment spans land in phase_ms instead).
    loader.set_epoch(0)
    for _ in loader:  # warm allocator/rng pools
        pass

    def host_epoch():
        for k in range(len(loader)):
            loader.materialize(k)

    host_ms = median_epoch_s(host_epoch) / steps * 1e3

    # Isolated stage — H2D upload alone: pre-materialised batches,
    # BLOCKING put (block_until_ready is what captures the actual
    # transfer; the tracer's h2d span is only the enqueue).
    host_batches = [loader.materialize(k) for k in range(len(loader))]

    def h2d_epoch():
        for hb in host_batches:
            jax.block_until_ready(shard_batch(hb, mesh))

    jax.block_until_ready(shard_batch(host_batches[0], mesh))  # warm path
    h2d_ms = median_epoch_s(h2d_epoch) / steps * 1e3
    del host_batches

    # Isolated stage — device step alone (resident batch, steady state):
    # the other number the tracer cannot give (its dispatch span is
    # enqueue time under async dispatch).
    schedule = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                                 steps_per_epoch=98)
    step_fn = make_train_step(model, SGDConfig(), schedule, mesh,
                              compute_dtype=compute_dtype)
    # Fresh buffers: the jitted step DONATES its state, and params/stats
    # must survive for the streaming Trainer below.
    state = init_train_state(jax.tree_util.tree_map(jnp.copy, params),
                             jax.tree_util.tree_map(jnp.copy, stats))
    dev_batch = shard_batch(loader.materialize(0), mesh)
    rng = jax.random.key(0)
    for _ in range(max(args.warmup, 1)):
        state, loss = step_fn(state, dev_batch, rng)
    float(loss)

    def step_epoch():
        nonlocal state
        for _ in range(steps):
            state, loss = step_fn(state, dev_batch, rng)
        float(loss)

    step_ms = median_epoch_s(step_epoch) / steps * 1e3
    del state, dev_batch

    # The real streaming path end to end (Trainer + prefetch), traced:
    # host/h2d stage costs and the phase_ms block come from these spans.
    pstats = PrefetchStats()
    # Ring sized to the whole run (warmup + timed + profile epochs, ~6
    # spans/step) so phase_ms medians cover the FULL timed window — a
    # default-sized ring would silently keep only the tail (the no-
    # silent-caps rule the bench record follows).
    tracer = SpanTracer(ring=max(4096, steps * (repeats + 4) * 8))
    trainer = Trainer(model, loader, params, stats, mesh=mesh,
                      lr_schedule=schedule, sgd_config=SGDConfig(),
                      save_every=10**9, snapshot_path=None,
                      compute_dtype=compute_dtype,
                      prefetch_depth=args.prefetch_depth,
                      prefetch_workers=args.prefetch_workers,
                      prefetch_stats=pstats, tracer=tracer)
    with contextlib.redirect_stdout(io.StringIO()):
        trainer.train(2)  # compiles: the step once per state type (--e2e)
        trainer.prefetch_stats = pstats = PrefetchStats()  # timed window
        t_window = tracer.now()  # spans before this are warmup
        dts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            trainer.train(1)  # train() restarts at epoch 0: 1 timed epoch
            trainer.flush_losses()
            dts.append(time.perf_counter() - t0)
        phase_ms = phase_medians(tracer.spans_since(t_window))
        if args.profile_dir:
            # One traced (untimed) streaming epoch — the device-idle
            # cross-check RUNBOOK §6 describes (wall - busy from
            # utils/profiling.py:device_busy_ms_per_step == the idle this
            # mode attributes).  Tracing skews wall clock, so it never
            # contributes to dts (or to phase_ms, read before it).
            jax.profiler.start_trace(args.profile_dir)
            trainer.train(1)
            trainer.flush_losses()
            jax.profiler.stop_trace()
    wall_ms = statistics.median(dts) / steps * 1e3
    attr = attribute_streaming(host_ms, h2d_ms, step_ms, wall_ms)
    print(json.dumps({
        "metric": f"{args.model} streaming overlap attribution (batch "
                  f"{args.batch_size}/chip, "
                  f"{'bf16' if args.bf16 else 'fp32'}, {n_chips} chip(s), "
                  f"depth {args.prefetch_depth}, workers "
                  f"{args.prefetch_workers}, {steps}-step epochs)",
        "value": attr["overlap_efficiency"],
        "unit": "pipeline overlap efficiency (slowest isolated stage / "
                "streaming wall, per step; phase_ms = tracer spans of "
                "the timed run)",
        "vs_baseline": 1.0,
        "attribution_ms_per_step": attr,
        "phase_ms": {k: round(v, 3) for k, v in sorted(phase_ms.items())},
        "prefetch": {"depth": args.prefetch_depth,
                     "workers": args.prefetch_workers,
                     **pstats.per_step_ms()},
        "window_epoch_s": [round(d, 3) for d in dts],
    }))


def _bench_serve(args) -> None:
    """Serving latency/throughput vs offered load (ddp_tpu/serve/).

    Two measurements around one in-process engine + dynamic batcher (the
    HTTP layer is deliberately out of the loop: stdlib JSON parsing
    would dominate on a CPU box and the queue/batch/forward pipeline is
    the part this framework owns):

    1. CLOSED loop — ``--serve_conc`` clients submitting back-to-back:
       the capacity probe (max sustainable req/s at this request shape).
    2. OPEN loop — fixed-rate arrivals at each ``--serve_loads`` point
       (quasi-open: a bounded submitter pool, so at saturation arrivals
       backlog instead of spawning unbounded threads — standard load-gen
       practice), recording p50/p90/p99 latency, achieved throughput,
       and shed count per point.

    The saturation KNEE is the last offered point the stack still serves
    at >=95% of the offered rate with nothing shed; the headline value is
    the achieved throughput there.  'auto' loads bracket the measured
    capacity (0.4/0.7/1.0/1.3x) so the knee is inside the sweep by
    construction — and the compiled-executable count is asserted against
    the resolved bucket-set size in the record itself (the bounded-
    compile contract, ddp_tpu/serve/engine.py).
    """
    import threading

    from ddp_tpu.serve import (DynamicBatcher, LocalReplica, QueueFull,
                               Router, ServeEngine)
    from ddp_tpu.serve.batcher import percentiles

    mesh = make_mesh(args.num_devices)
    model = get_model(args.model)
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    buckets = [int(b) for b in args.serve_buckets.split(",") if b]
    fleet_n = max(int(args.fleet), 1)

    def make_engine() -> "ServeEngine":
        if args.snapshot_path:
            return ServeEngine.from_checkpoint(
                args.snapshot_path, args.model, mesh=mesh, buckets=buckets,
                compute_dtype=compute_dtype)
        params, stats = model.init(jax.random.key(0))
        return ServeEngine(model, params, stats, mesh, buckets=buckets,
                           compute_dtype=compute_dtype)

    t0 = time.perf_counter()
    engines = [make_engine() for _ in range(fleet_n)]
    engine = engines[0]
    compiled = 0
    for eng in engines:
        c = eng.warm()
        assert c <= len(eng.buckets), \
            f"compile bound broken: {c} > {len(eng.buckets)}"
        compiled += c
    warm_s = time.perf_counter() - t0
    if not 1 <= args.serve_rows <= engine.max_rows:
        # Fail HERE with the real reason: inside the load loops the same
        # admission error would kill every client thread and surface as
        # a ZeroDivisionError from a measured capacity of 0.
        raise SystemExit(
            f"--serve_rows {args.serve_rows} does not fit the engine's "
            f"buckets (largest {engine.max_rows}); every request would "
            "be rejected at admission")
    batchers = [DynamicBatcher(eng, max_wait_ms=args.serve_max_wait_ms,
                               queue_depth=args.serve_queue_depth).start()
                for eng in engines]
    router = None
    if fleet_n > 1:
        # Fleet mode: the same load loops drive the router's submit —
        # QueueFull below also catches the router's shed subclasses, so
        # shed accounting is transport-identical to single-engine mode.
        replicas = [LocalReplica(f"r{i}", eng, b)
                    for i, (eng, b) in enumerate(zip(engines, batchers))]
        router = Router(replicas).start()
        submit = router.submit
    else:
        submit = batchers[0].submit
    rng = np.random.default_rng(0)
    req = rng.integers(0, 256,
                       (args.serve_rows, 32, 32, 3)).astype(np.uint8)

    def closed_loop(conc: int, secs: float) -> dict:
        stop = time.perf_counter() + secs
        lat: list = []
        timeouts = [0]
        lock = threading.Lock()

        def client():
            # A timed-out request must not kill the client thread —
            # a silently-dead client stops offering load and the record
            # would understate capacity with no sign anything went wrong.
            while time.perf_counter() < stop:
                t = time.perf_counter()
                try:
                    submit(req, timeout=30)
                except TimeoutError:
                    with lock:
                        timeouts[0] += 1
                    continue
                dt = (time.perf_counter() - t) * 1e3
                with lock:
                    lat.append(dt)

        threads = [threading.Thread(target=client) for _ in range(conc)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
        return {"clients": conc, "requests": len(lat),
                "throughput_rps": round(len(lat) / wall, 2),
                "timed_out": timeouts[0],
                "latency_ms": {k: (round(v, 3) if v is not None else None)
                               for k, v in percentiles(lat).items()}}

    def open_loop(rate: float, secs: float) -> dict:
        n = max(int(rate * secs), 8)
        base = time.perf_counter() + 0.05
        arrivals = [base + i / rate for i in range(n)]
        lat: list = []
        shed = 0
        timed_out = 0
        counter = iter(range(n))
        lock = threading.Lock()

        def client():
            nonlocal shed, timed_out
            while True:
                with lock:
                    i = next(counter, None)
                if i is None:
                    return
                delay = arrivals[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                t = time.perf_counter()
                try:
                    submit(req, timeout=30)
                except QueueFull:
                    with lock:
                        shed += 1
                    continue
                except TimeoutError:  # counted, never a dead client
                    with lock:
                        timed_out += 1
                    continue
                dt = (time.perf_counter() - t) * 1e3
                with lock:
                    lat.append(dt)

        pool = [threading.Thread(target=client)
                for _ in range(min(128, n))]
        t_start = time.perf_counter()
        for t in pool:
            t.start()
        for t in pool:
            t.join()
        wall = max(time.perf_counter() - t_start - 0.05, 1e-9)
        return {"offered_rps": round(rate, 2), "requests": n,
                "achieved_rps": round(len(lat) / wall, 2),
                "shed": shed,
                "shed_rate": round(shed / n, 4),
                "timed_out": timed_out,
                "latency_ms": {k: (round(v, 3) if v is not None else None)
                               for k, v in percentiles(lat).items()}}

    closed = closed_loop(args.serve_conc, args.serve_secs)
    capacity = closed["throughput_rps"]
    if capacity <= 0:
        raise SystemExit(
            "closed-loop capacity probe served 0 requests in "
            f"{args.serve_secs}s (all timed out?); no load sweep to run "
            "— raise --serve_secs or check the engine")
    if args.serve_loads == "auto":
        # Wide bracket: dynamic batching serves ABOVE the closed-loop
        # probe (bigger formed batches amortise dispatch), so the sweep
        # must reach well past it for the knee to be interior.
        loads = [round(capacity * f, 2)
                 for f in (0.4, 0.7, 1.0, 1.5, 2.25)]
    else:
        loads = [float(x) for x in args.serve_loads.split(",")]
    open_points = [open_loop(r, args.serve_secs) for r in sorted(loads)]

    knee = None
    for pt in open_points:  # ascending offered load
        if pt["shed"] == 0 and pt["timed_out"] == 0 and \
                pt["achieved_rps"] >= 0.95 * pt["offered_rps"]:
            knee = pt
    rows_per_req = args.serve_rows
    # The unit must say what the number IS: when no sweep point
    # qualifies as the knee (every point shed or degraded — e.g. an
    # explicit --serve_loads entirely past saturation), the headline is
    # the most-saturated point's throughput, and calling that a knee
    # would poison cross-round BENCH comparisons.
    print(json.dumps({
        "metric": f"{args.model} serve latency/throughput vs offered load "
                  f"(fleet of {fleet_n}, "
                  f"batch buckets {list(engine.buckets)}, "
                  f"{rows_per_req} row(s)/request, "
                  f"{'bf16' if args.bf16 else 'fp32'}, "
                  f"{mesh.devices.size} chip(s), max_wait "
                  f"{args.serve_max_wait_ms} ms)",
        "value": (knee or open_points[-1])["achieved_rps"],
        "unit": ("req/s at the saturation knee (last offered point "
                 "served >=95% with nothing shed)" if knee is not None
                 else "req/s at the MOST-SATURATED sweep point (no knee "
                      "inside the sweep: every offered point shed or "
                      "degraded; not comparable to knee records)"),
        "vs_baseline": 1.0,
        "serve": {
            "fleet": fleet_n,
            "closed_loop": closed,
            "open_loop": open_points,
            "knee_offered_rps": (knee or {}).get("offered_rps"),
            "samples_per_sec_at_knee": round(
                (knee or open_points[-1])["achieved_rps"] * rows_per_req,
                2),
            "compiled_executables": compiled,
            "bucket_set_size": len(engine.buckets),
            "warm_compile_s": round(warm_s, 2),
            "engine": engine.stats(),
            "batcher": batchers[0].stats(),
            "router": router.stats() if router is not None else None,
        },
    }))
    if router is not None:
        router.close()
    for b in batchers:
        b.drain(timeout=10.0)


def _bench_generate(args) -> None:
    """Generative serving throughput: tokens/sec and TTFT vs concurrent
    streams (ddp_tpu/serve/kvcache.py + token_batcher.py).

    Each sweep point runs S closed-loop clients for ``--serve_secs``
    seconds; every client loops full streams (prompt -> prefill ->
    ``--gen_new_tokens`` decode steps).  Because the decode program
    advances EVERY live slot per step at a fixed [slots] shape, aggregate
    tokens/sec should rise with S until the slot count saturates — the
    continuous-batching payoff the curve makes visible.  The headline is
    tokens/sec at the largest stream count (higher is better); TTFT
    percentiles per point price what co-batching costs the first token.
    """
    import threading

    from ddp_tpu.models import transformer as tfm
    from ddp_tpu.serve.batcher import percentiles
    from ddp_tpu.serve.kvcache import KVCacheEngine
    from ddp_tpu.serve.token_batcher import TokenBatcher

    mesh = make_mesh(args.num_devices)
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    prefill_buckets = [int(b) for b in
                       args.gen_prefill_buckets.split(",") if b]
    t0 = time.perf_counter()
    if args.snapshot_path:
        engine = KVCacheEngine.from_checkpoint(
            args.snapshot_path, tfm.LM_NAME, mesh=mesh,
            slots=args.gen_slots, prompt_buckets=prefill_buckets,
            compute_dtype=compute_dtype)
    else:
        params, _ = get_model(tfm.LM_NAME).init(jax.random.key(0))
        engine = KVCacheEngine(tfm, params, mesh, slots=args.gen_slots,
                               prompt_buckets=prefill_buckets,
                               compute_dtype=compute_dtype)
    compiled = engine.warm()
    assert compiled <= engine.compile_bound, \
        f"compile bound broken: {compiled} > {engine.compile_bound}"
    warm_s = time.perf_counter() - t0
    batcher = TokenBatcher(engine, max_new_tokens=args.gen_new_tokens,
                           queue_depth=args.serve_queue_depth).start()
    rng = np.random.default_rng(0)
    n_prompt = max(1, min(int(args.gen_prompt_len), engine.max_prompt))

    def point(streams: int, secs: float) -> dict:
        stop = time.perf_counter() + secs
        lock = threading.Lock()
        tokens = [0]
        ttfts: list = []
        stream_lat: list = []
        completed = [0]

        def client(seed: int):
            r = np.random.default_rng(seed)
            while time.perf_counter() < stop:
                prompt = r.integers(0, tfm.VOCAB, n_prompt).tolist()
                t = time.perf_counter()
                try:
                    out = batcher.generate(
                        prompt, max_new_tokens=args.gen_new_tokens,
                        timeout=60)
                except TimeoutError:
                    continue  # counted absent: a dead point shows 0 t/s
                dt = (time.perf_counter() - t) * 1e3
                with lock:
                    tokens[0] += len(out["tokens"])
                    ttfts.append(out["ttft_ms"])
                    stream_lat.append(dt)
                    completed[0] += 1

        threads = [threading.Thread(target=client, args=(1000 + i,))
                   for i in range(streams)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
        return {
            "streams": streams,
            "completed_streams": completed[0],
            "tokens": tokens[0],
            "tokens_per_sec": round(tokens[0] / wall, 2),
            "ttft_ms": {k: (round(v, 3) if v is not None else None)
                        for k, v in percentiles(ttfts).items()},
            "stream_latency_ms": {
                k: (round(v, 3) if v is not None else None)
                for k, v in percentiles(stream_lat).items()},
        }

    streams = sorted({max(1, int(s))
                      for s in args.gen_streams.split(",") if s})
    curve = [point(s, args.serve_secs) for s in streams]
    head = curve[-1]
    print(json.dumps({
        "metric": f"{tfm.LM_NAME} generative decode tokens/sec vs "
                  f"concurrent streams ({engine.slots} KV slots, prompt "
                  f"{n_prompt}, {args.gen_new_tokens} new tokens/stream, "
                  f"prompt buckets {list(engine.prompt_buckets)}, "
                  f"{'bf16' if args.bf16 else 'fp32'}, "
                  f"{mesh.devices.size} chip(s))",
        "value": head["tokens_per_sec"],
        "unit": f"tokens/s at {head['streams']} concurrent streams "
                "(continuous token-level batching; higher is better)",
        "vs_baseline": 1.0,
        "generate": {
            "curve": curve,
            "slots": engine.slots,
            "compiled_executables": compiled,
            "compile_bound": engine.compile_bound,
            "warm_compile_s": round(warm_s, 2),
            "checkpoint": args.snapshot_path,
            "engine": engine.stats(),
            "batcher": batcher.stats(),
        },
    }))
    batcher.drain(timeout=10.0)


def _bench_sweep(args) -> None:
    """Per-device-count throughput sweep (BASELINE.json north star:
    >=90% linear scaling).  Emits one JSON line: per-N samples/sec/chip
    and the max-N/min-N per-chip efficiency ratio."""
    counts = sorted(int(x) for x in args.sweep.split(","))
    per_n: dict = {}
    for n in counts:
        env = dict(os.environ)
        child = [sys.executable, os.path.abspath(__file__),
                 "--model", args.model, "--batch_size", str(args.batch_size),
                 "--steps", str(args.steps), "--warmup", str(args.warmup),
                 "--repeats", str(args.repeats), "--num_devices", str(n),
                 "--no_bf16", "--primary_only",  # one program per child:
                 # the secondary dispatch-flavor window would double each
                 # child's (serial, CPU-bound) compile cost for no signal
                 "--dispatch", args.dispatch]
        child += ["--bf16"] if args.bf16 else []
        # Composed execution strategies ride through to the children, so
        # the one-command pod measurement covers the collective patterns
        # that matter at scale (ZeRO reduce-scatter/all-gather; the
        # resident scan-per-epoch e2e path), not just the plain step.
        child += ["--shard_update"] if args.shard_update else []
        if args.e2e or args.resident:
            child += ["--e2e", "--e2e_steps", str(args.e2e_steps)]
            child += ["--resident"] if args.resident else []
        if args.sweep_platform == "cpu":
            env = cpu_device_env(n, env)
        per_n[n] = _run_child(child, env, f"sweep child n={n}")["value"]
    eff = per_n[counts[-1]] / per_n[counts[0]] if per_n[counts[0]] else 0.0
    mode = ("zero-sharded update, " if args.shard_update else "") + \
           ("HBM-resident e2e, " if args.resident
            else "host-fed e2e, " if args.e2e else "")
    print(json.dumps({
        "metric": f"{args.model} DP scaling sweep "
                  f"({args.sweep_platform} mesh, {mode}batch "
                  f"{args.batch_size}/chip, devices {counts})",
        "value": round(eff, 4),
        "unit": f"per-chip efficiency at {counts[-1]} vs {counts[0]} devices",
        "vs_baseline": 1.0,
        "samples_per_sec_per_chip": {str(n): per_n[n] for n in counts},
    }))


def _bench_tp_sweep(args) -> None:
    """Tensor-parallel mesh-shape sweep at FIXED GLOBAL BATCH: one child
    per model-axis size M over the same device total (data axis =
    total/M), recording ms/step and MFU per mesh shape — the measured
    cost of trading data-parallel width for model-parallel width (the
    row-psum collectives + thinner per-shard matmuls).  Emits ONE JSON
    line whose ``tp_sweep`` dict is keyed by mesh shape ("8x1", "4x2",
    "2x4"); committed CPU-box record: BENCH_r07.json (chip paste in
    RUNBOOK section 10).  m=1 children run the REAL tp code path on a
    (N,1) mesh, so the m>1 deltas are collective cost, not plumbing."""
    ms = sorted(int(x) for x in args.tp_sweep.split(","))
    total = _sweep_total(args, "--tp_sweep")
    global_batch = args.batch_size
    per: dict = {}
    for m in ms:
        if total % m:
            raise SystemExit(f"--tp_sweep: model axis {m} does not divide "
                             f"the device total {total}")
        d = total // m
        if global_batch % d:
            raise SystemExit(f"--tp_sweep: global batch {global_batch} not "
                             f"divisible by the {d}-way data axis at m={m}")
        env = dict(os.environ)
        child = [sys.executable, os.path.abspath(__file__),
                 "--model", args.model,
                 "--batch_size", str(global_batch // d),
                 "--steps", str(args.steps), "--warmup", str(args.warmup),
                 "--repeats", str(args.repeats),
                 "--mesh_shape", f"{d},{m}",
                 "--no_bf16", "--primary_only", "--dispatch", args.dispatch]
        child += ["--bf16"] if args.bf16 else []
        child += ["--shard_update"] if args.shard_update else []
        if args.sweep_platform == "cpu":
            env = cpu_device_env(total, env)
        rec = _run_child(child, env, f"tp sweep child m={m}")
        per[f"{d}x{m}"] = {
            "ms_per_step": rec["median_ms_per_step"],
            "best_window_ms_per_step": rec["best_window_ms_per_step"],
            "samples_per_sec_per_chip": rec["value"],
            "mfu": rec.get("mfu"),
        }
    shapes = [f"{total // m}x{m}" for m in ms]
    base_ms = per[shapes[0]]["ms_per_step"]
    last_ms = per[shapes[-1]]["ms_per_step"]
    print(json.dumps({
        "metric": f"{args.model} tensor-parallel mesh sweep "
                  f"({args.sweep_platform} mesh, global batch "
                  f"{global_batch}, {total} devices, "
                  f"{'bf16' if args.bf16 else 'fp32'}, "
                  f"{'zero-sharded update, ' if args.shard_update else ''}"
                  f"shapes {shapes})",
        "value": round(base_ms / last_ms, 4) if last_ms else 0.0,
        "unit": f"ms/step ratio, {shapes[0]} vs {shapes[-1]} (data x model)",
        "vs_baseline": 1.0,
        "tp_sweep": per,
    }))


def _bench_pp_sweep(args) -> None:
    """Pipeline-stage sweep at FIXED GLOBAL BATCH: one child per stage
    count S over the same device total (data axis = total/S, model axis
    1), each stepping --pp_micro micro-batches through the 1F1B
    schedule, recording ms/step, samples/sec/chip AND the pipeline
    bubble — the MEASURED idle fraction (per-op timed critical path,
    parallel/pp/schedule.py) next to the static (S-1)/(A+S-1) prediction
    — per mesh shape.  S=1 runs the plain single-dispatch step on the
    same devices as the bubble-free baseline.  Emits ONE JSON line whose
    ``pp_sweep`` dict is keyed by mesh shape ("8x1x1", "4x1x2",
    "2x1x4"); committed CPU-box record: BENCH_r15.json (chip paste in
    RUNBOOK section 21)."""
    ss = sorted(int(x) for x in args.pp_sweep.split(","))
    total = _sweep_total(args, "--pp_sweep")
    global_batch = args.batch_size
    a = max(int(args.pp_micro), 1)
    per: dict = {}
    for s in ss:
        if total % s:
            raise SystemExit(f"--pp_sweep: stage count {s} does not "
                             f"divide the device total {total}")
        d = total // s
        if global_batch % d:
            raise SystemExit(f"--pp_sweep: global batch {global_batch} "
                             f"not divisible by the {d}-way data axis "
                             f"at s={s}")
        env = dict(os.environ)
        child = [sys.executable, os.path.abspath(__file__),
                 "--model", args.model,
                 "--batch_size", str(global_batch // d),
                 "--steps", str(args.steps), "--warmup", str(args.warmup),
                 "--repeats", str(args.repeats),
                 "--mesh_shape", f"{d},1,{s}",
                 "--pp_micro", str(a),
                 "--no_bf16", "--primary_only", "--dispatch", "step"]
        child += ["--bf16"] if args.bf16 else []
        if args.sweep_platform == "cpu":
            env = cpu_device_env(total, env)
        rec = _run_child(child, env, f"pp sweep child s={s}")
        per[f"{d}x1x{s}"] = {
            "ms_per_step": rec["median_ms_per_step"],
            "best_window_ms_per_step": rec["best_window_ms_per_step"],
            "samples_per_sec_per_chip": rec["value"],
            "pp": rec.get("pp"),
        }
    shapes = [f"{total // s}x1x{s}" for s in ss]
    deepest = per[shapes[-1]].get("pp") or {}
    print(json.dumps({
        "metric": f"{args.model} pipeline-stage mesh sweep "
                  f"({args.sweep_platform} mesh, global batch "
                  f"{global_batch} x {a} micro-batches/step, {total} "
                  f"devices, {'bf16' if args.bf16 else 'fp32'}, 1F1B, "
                  f"shapes {shapes})",
        "value": round(deepest.get("bubble_measured", 0.0), 4),
        "unit": (f"measured bubble fraction at {shapes[-1]} "
                 f"(static prediction "
                 f"{round(deepest.get('bubble_predicted', 0.0), 4)})"),
        "vs_baseline": 1.0,
        "pp_sweep": per,
    }))


def _bench_autoplan(args) -> None:
    """Hand recipe vs searched auto plan, MEASURED (the ISSUE 17
    acceptance gate; committed record: BENCH_r13.json).  Per model: run
    the cost-model search (parallel/tp/autoplan.py) over the device
    total, then measure BOTH configurations as bench children at FIXED
    GLOBAL BATCH — the hand baseline (the model's TP_RECIPE at model
    axis 4, or pure DP when it has none) and the searched plan through
    the real ``--auto_plan`` load path.  The headline is the WORST-case
    hand/auto ms/step ratio across models (higher better; >= 1.0 = the
    search matched or beat every hand configuration), and each model's
    block records predicted-vs-measured for the chosen plan next to the
    calibration record's own residual, so "within the calibration error
    band" is checkable from the record alone."""
    import tempfile

    from ddp_tpu.analysis.search import coefficients_from
    from ddp_tpu.parallel.tp.autoplan import (read_plan_doc, recipe_summary,
                                              search_space_for)
    if not args.calib:
        raise SystemExit("--autoplan_bench needs --calib CALIB.json (run "
                         "bench.py --calibrate_cost first; its record "
                         "carries the fitted coefficients)")
    with open(args.calib, "r", encoding="utf-8") as fh:
        calib = json.load(fh)
    coeffs = coefficients_from(calib)
    total = _sweep_total(args, "--autoplan_bench")
    global_batch = args.batch_size
    models = [m.strip() for m in args.autoplan_models.split(",") if m.strip()]
    tmpdir = tempfile.mkdtemp(prefix="autoplan_bench_")
    # The known virtual-mesh factor: a CPU mesh serializes its shards, so
    # measured ~= n_dev x the per-shard prediction (the ledger's
    # pred_scale; obs/ledger.py module docstring).
    pred_scale = total if args.sweep_platform == "cpu" else 1
    env = dict(os.environ)
    if args.sweep_platform == "cpu":
        env = cpu_device_env(total, env)
    per: dict = {}
    for model_name in models:
        plan_path = os.path.join(tmpdir, f"{model_name}.autoplan.json")
        search_child_s = _search_plan_child(model_name, total, args.calib,
                                            global_batch, plan_path)
        doc = read_plan_doc(plan_path)
        d_auto, m_auto = (int(v) for v in doc["mesh_shape"])
        common = [sys.executable, os.path.abspath(__file__),
                  "--model", model_name,
                  "--steps", str(args.steps), "--warmup", str(args.warmup),
                  "--repeats", str(args.repeats),
                  "--no_bf16", "--primary_only",
                  "--dispatch", args.dispatch]
        space = search_space_for(model_name)
        if space.layers and total % 4 == 0:
            d_hand, m_hand = total // 4, 4
            hand_child = common + ["--mesh_shape", f"{d_hand},{m_hand}",
                                   "--batch_size",
                                   str(global_batch // d_hand)]
            hand_cfg = f"{d_hand}x{m_hand} TP_RECIPE"
        else:
            d_hand, m_hand = total, 1
            hand_child = common + ["--num_devices", str(total),
                                   "--batch_size",
                                   str(global_batch // total)]
            hand_cfg = f"dp{total}"
        if global_batch % d_hand or global_batch % d_auto:
            raise SystemExit(
                f"--autoplan_bench: global batch {global_batch} must "
                f"divide both data axes (hand {d_hand}, auto {d_auto})")
        auto_child = common + ["--auto_plan", plan_path,
                               "--batch_size", str(global_batch // d_auto)]
        hand = _run_child(hand_child, env, f"autoplan hand {model_name}")
        # When the search CHOOSES THE HAND LAYOUT ITSELF — a trivial
        # (d,1) plan against the pure-DP hand config, the same data
        # axis, ZeRO off — the two children run the same partitioning
        # (a model axis of size 1 is degenerate; the trivial plan
        # resolves to the plain DP step builders, tests/test_autoplan
        # .py pins it), so the layout delta is zero by identity.
        # Timing the same program twice minutes apart would report box
        # drift as a layout effect — an early run of this harness
        # measured a 6% "regression" between two identical dp8
        # programs.  Measure once and record the coincidence; a TP
        # coincidence still runs both children (the plan-doc load path
        # differs from --mesh_shape, so it stays worth timing).
        same_layout = ((d_auto, m_auto) == (d_hand, m_hand)
                       and m_hand == 1 and not doc.get("zero")
                       and not doc["recipe"])
        auto = hand if same_layout else _run_child(
            auto_child, env, f"autoplan auto {model_name}")
        hand_ms = float(hand["median_ms_per_step"])
        auto_ms = float(auto["median_ms_per_step"])
        pred_ms = float(doc["predicted_ms_per_step"]) * pred_scale
        per[model_name] = {
            "hand": {"config": hand_cfg,
                     "mesh": f"{d_hand}x{m_hand}",
                     "ms_per_step": hand_ms,
                     "best_window_ms_per_step":
                         hand["best_window_ms_per_step"],
                     "samples_per_sec_per_chip": hand["value"]},
            "auto": {"mesh": f"{d_auto}x{m_auto}",
                     "recipe": recipe_summary(doc["recipe"], space),
                     "zero": bool(doc.get("zero")),
                     "same_layout_as_hand": same_layout,
                     "ms_per_step": auto_ms,
                     "best_window_ms_per_step":
                         auto["best_window_ms_per_step"],
                     "samples_per_sec_per_chip": auto["value"],
                     "predicted_ms_per_step": round(pred_ms, 3),
                     "gap_pct": round((auto_ms - pred_ms) / pred_ms
                                      * 100.0, 1) if pred_ms else None,
                     # The whole search CHILD's wall: interpreter
                     # start and the jax import included, not the
                     # search alone.
                     "search_child_s": round(search_child_s, 2),
                     # The exact plan the auto child loaded.
                     "plan_doc": doc,
                     "candidates_considered":
                         doc["search"]["candidates_considered"]},
            # Best timed window on each side: the capability bound a
            # clean window reaches.  The median is also recorded, but on
            # a shared box its noise floor (one stalled window) dwarfs
            # real layout deltas — BENCH_r13's first cut "lost" 28% on
            # two IDENTICAL dp8 programs by comparing medians.
            "speedup": (round(float(hand["best_window_ms_per_step"])
                              / float(auto["best_window_ms_per_step"]), 4)
                        if auto.get("best_window_ms_per_step") else None),
            "speedup_median": round(hand_ms / auto_ms, 4)
                if auto_ms else None,
        }
    # The calibration record's own residual on the program it measured —
    # the error band the auto plan's gap_pct is judged against.
    calib_gap = None
    calib_meas = calib.get("measured_ms_per_step")
    calib_preds = calib.get("predicted_ms_per_step")
    if not isinstance(calib_preds, dict):
        # --calib was another coefficient carrier (a plan doc): no
        # calibrate residual to report.
        calib_preds = {}
    calib_prog = _pick_calib_program(calib_preds)
    if isinstance(calib_meas, dict):
        calib_meas = calib_meas.get(calib_prog)
    # The calibrate record measured on ITS OWN mesh size (its
    # "n_devices" field; the "@dp8" program name is registry naming,
    # not a device count), so its residual gets its own scale.
    calib_n = int(calib.get("n_devices") or 0)
    if calib_meas and calib_prog and calib_n:
        cp = float(calib_preds[calib_prog]) * \
            (calib_n if args.sweep_platform == "cpu" else 1)
        if cp:
            calib_gap = round((float(calib_meas) - cp) / cp * 100.0, 1)
    worst = min(p["speedup"] for p in per.values()
                if p["speedup"] is not None)
    print(json.dumps({
        "metric": f"auto-plan vs hand-recipe train step "
                  f"({args.sweep_platform} mesh, {total} devices, "
                  f"global batch {global_batch}, fp32, models "
                  f"{models})",
        "value": worst,
        "unit": "speedup, hand best-window ms/step over auto (worst "
                "model; >=1 = auto matched or beat every hand config)",
        "vs_baseline": 1.0,
        "autoplan_bench": per,
        "pred_scale": pred_scale,
        "calibration_gap_pct": calib_gap,
        "coefficients": coeffs,
    }))


def _search_plan_child(model_name: str, total: int, calib_path: str,
                       global_batch: int, plan_path: str) -> float:
    """Write the searched plan for ``model_name`` over ``total`` devices
    to ``plan_path`` and return the child's wall seconds.  The search
    traces the real step builders, which creates arrays — so it runs as a
    one-device CPU child (meshes are abstract and pricing needs no
    device), leaving the chips to the measuring children.  The doc is a
    function of its inputs alone: the candidates are the plain
    ``make_train_step`` programs, which hold no ``lax.scan``, so
    ``scan_unroll``'s platform branch is not in what gets priced
    (tests/test_autoplan.py pins the child's doc byte-equal to the
    in-process ``search_plan``)."""
    t0 = time.perf_counter()
    rc = subprocess.call(
        [sys.executable, "-m", "ddp_tpu.parallel.tp", "--search",
         "--model", model_name, "--devices", str(total),
         "--calib", os.path.abspath(calib_path),
         "--global_batch", str(global_batch),
         "--out", plan_path],
        env=cpu_device_env(1), stdout=sys.stderr,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if rc != 0:
        raise SystemExit(f"--autoplan_bench: search for {model_name} "
                         f"failed rc={rc}")
    return time.perf_counter() - t0


def _pick_calib_program(predicted: dict):
    """The calibrate record's measured program: it measures the plain
    data-parallel train step (``train_step@dp<N>``)."""
    for name in sorted(predicted):
        if name.startswith("train_step@dp"):
            return name
    return None


def _ckpt_synth_tree(size_mb: int, *, with_arrays: bool = True):
    """Synthetic checkpoint pytree of ~``size_mb`` MiB total (params plus
    a same-sized momentum mirror): alternating column/row model-sharded
    (1024, 2048) fp32 matrices with replicated biases — the layout the tp
    planner emits, at a controllable size so the checkpoint path is
    measured at >= 2 model sizes without needing a model that large.
    Returns ``(host_tree_or_None, spec_tree)``; extents divide every mesh
    the bench uses (model axis 4 at save, 2 at restore)."""
    from jax.sharding import PartitionSpec as P
    n = max(1, int(size_mb) // 16)  # one 8 MiB matrix each in params+mom
    host, specs = {}, {}
    for i in range(n):
        col = i % 2 == 0
        specs[f"layer{i}"] = {
            "w": P(None, "model") if col else P("model", None),
            "b": P(),
        }
        if with_arrays:
            host[f"layer{i}"] = {
                "w": np.full((1024, 2048), float(i + 1), np.float32),
                "b": np.full((2048 if col else 1024,), float(i), np.float32),
            }
    return (host if with_arrays else None), specs


def _bench_ckpt_child(args) -> None:
    """One --ckpt_bench measurement in isolation: this process builds the
    placed (model-sharded) state, runs exactly ONE phase (save | restore)
    in exactly ONE format, and reports wall time plus ru_maxrss before and
    after — the peak-RSS delta is attributable to that phase alone."""
    import resource

    from jax.sharding import NamedSharding
    from ddp_tpu.optim.sgd import SGDState
    from ddp_tpu.parallel.mesh import replicated_sharding
    from ddp_tpu.train.checkpoint import save_checkpoint
    from ddp_tpu.train.ckpt_shard import (HostBytesProbe, load_for_mesh,
                                          save_checkpoint_sharded)

    def peak_kb() -> int:
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)

    d, m = (int(x) for x in (args.mesh_shape or "2,4").split(","))
    mesh = make_mesh(shape=(d, m))
    rec = {"value": 0.0, "phase": args.ckpt_bench_child,
           "format": args.ckpt_format, "size_mb": int(args.ckpt_size_mb),
           "mesh": f"{d}x{m}"}
    if args.ckpt_bench_child == "save":
        host, spec_tree = _ckpt_synth_tree(args.ckpt_size_mb)
        place = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            host, spec_tree)
        mom = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(np.zeros(a.shape, a.dtype),
                                        NamedSharding(mesh, s)),
            host, spec_tree)
        del host
        jax.block_until_ready((place, mom))
        rec["rss_peak_before_kb"] = peak_kb()
        t0 = time.perf_counter()
        if args.ckpt_format == "sharded":
            save_checkpoint_sharded(args.snapshot_path, place, {},
                                    SGDState(mom), 0, 0, mesh=mesh)
        else:
            # The trainer's gathered path: all-gather the model-sharded
            # leaves to replicated, then the canonical single-file write.
            rep = replicated_sharding(mesh)
            g_p = jax.device_put(place, rep)
            g_m = jax.device_put(mom, rep)
            jax.block_until_ready((g_p, g_m))
            save_checkpoint(args.snapshot_path, g_p, {}, SGDState(g_m),
                            0, 0)
        rec["wall_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        rec["rss_peak_after_kb"] = peak_kb()
    else:
        _, spec_tree = _ckpt_synth_tree(args.ckpt_size_mb,
                                        with_arrays=False)
        probe = HostBytesProbe()
        rec["rss_peak_before_kb"] = peak_kb()
        t0 = time.perf_counter()
        ck = load_for_mesh(args.snapshot_path, mesh,
                           param_specs=spec_tree, probe=probe)
        jax.block_until_ready((ck.params, ck.opt_state.momentum_buf))
        rec["wall_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        rec["rss_peak_after_kb"] = peak_kb()
        rec["engine_peak_staging_mb"] = round(probe.peak / 2**20, 2)
    print(json.dumps(rec))


def _bench_ckpt(args) -> None:
    """Gathered-vs-sharded checkpoint bench (ISSUE 6): per payload size
    and format, ONE child saves on a (2,4) 8-virtual-device mesh and a
    SECOND child restores that file resharded onto a (2,2) 4-device mesh
    (the elastic-resume direction).  Per-child ru_maxrss deltas make the
    save path's peak host memory a measured number: the gathered save
    all-gathers the model-sharded leaves (8 replicated device copies plus
    whole-model npz staging, O(model)); the sharded save streams one
    model-slot at a time (O(model/m)).  Headline value: gathered-vs-
    sharded save-path RSS-delta ratio at the LARGEST size (> 1 means the
    sharded save peaks lower).  Record: BENCH_r08.json."""
    import tempfile

    sizes = sorted(int(s) for s in args.ckpt_sizes.split(","))
    per: dict = {}
    with tempfile.TemporaryDirectory() as td:
        for size in sizes:
            per_size: dict = {}
            for fmt in ("gathered", "sharded"):
                path = os.path.join(td, f"ck_{fmt}_{size}.pt")
                cell: dict = {}
                for phase, shape, ndev in (("save", "2,4", 8),
                                           ("restore", "2,2", 4)):
                    child = [sys.executable, os.path.abspath(__file__),
                             "--ckpt_bench_child", phase,
                             "--ckpt_format", fmt,
                             "--ckpt_size_mb", str(size),
                             "--mesh_shape", shape,
                             "--snapshot_path", path]
                    out = _run_child(child,
                                     cpu_device_env(ndev, dict(os.environ)),
                                     f"ckpt bench {fmt} {phase} {size}MB")
                    delta_mb = round(
                        (out["rss_peak_after_kb"]
                         - out["rss_peak_before_kb"]) / 1024, 1)
                    cell[f"{phase}_ms"] = out["wall_ms"]
                    cell[f"{phase}_rss_peak_delta_mb"] = delta_mb
                    if "engine_peak_staging_mb" in out:
                        cell["restore_engine_peak_staging_mb"] = \
                            out["engine_peak_staging_mb"]
                per_size[fmt] = cell
            per[f"{size}MB"] = per_size
    big = per[f"{sizes[-1]}MB"]
    s_delta = big["sharded"]["save_rss_peak_delta_mb"]
    g_delta = big["gathered"]["save_rss_peak_delta_mb"]
    print(json.dumps({
        "metric": f"checkpoint save-path peak host RSS, gathered vs "
                  f"sharded (sizes {sizes} MiB; save on (2,4)x8 cpu mesh, "
                  f"restore resharded onto (2,2)x4 — elastic resume)",
        "value": round(g_delta / max(s_delta, 1.0), 2),
        "unit": f"gathered/sharded save RSS-delta ratio at {sizes[-1]}MiB "
                "(> 1: sharded peaks lower; sharded delta floored at "
                "1 MiB — it can sit below the RSS noise floor)",
        "vs_baseline": 1.0,
        "ckpt_bench": per,
    }))


def _bench_pipeline(args) -> None:
    """Host-side input pipeline in isolation: per-epoch batch
    materialisation + crop/flip augmentation at the training batch size,
    no device involved.  Comparing this rate to the host-fed --e2e number
    attributes the gap: if this is >> e2e, the bottleneck is the H2D
    link, not the pipeline."""
    from ddp_tpu.data import TrainLoader
    n_chips = args.num_devices or 1
    n_train = args.batch_size * n_chips * 16
    train_ds, _ = synthetic(n_train=n_train)
    loader = TrainLoader(train_ds, args.batch_size, n_chips, augment=True)
    # Warm epoch (allocator, rng pools), then best-of-repeats timed epochs.
    for b in loader:
        pass
    dt = float("inf")
    for _ in range(max(args.repeats, 1)):
        loader.set_epoch(1)
        t0 = time.perf_counter()
        n = 0
        for b in loader:
            n += len(b["label"])
        dt = min(dt, time.perf_counter() - t0)
    print(json.dumps({
        "metric": f"host input pipeline samples/sec (materialise+augment, "
                  f"batch {args.batch_size}, no device)",
        "value": round(n / dt, 2),
        "unit": "samples/sec",
        "vs_baseline": 1.0,
    }))


def _bench_e2e(args) -> None:
    """End-to-end epoch throughput through the real Trainer (loader +
    augmentation + prefetch + H2D + jitted step)."""
    import contextlib
    import io

    from ddp_tpu.obs.aggregate import phase_medians
    from ddp_tpu.obs.tracer import SpanTracer
    from ddp_tpu.train import Trainer

    mesh = make_mesh(args.num_devices)
    n_chips = mesh.devices.size
    model = get_model(args.model)
    params, stats = model.init(jax.random.key(0))
    n_train = args.batch_size * n_chips * args.e2e_steps
    train_ds, _ = synthetic(n_train=n_train)
    from ddp_tpu.data import TrainLoader
    loader = TrainLoader(train_ds, args.batch_size, n_chips,
                         augment=not args.resident)
    schedule = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                                 steps_per_epoch=98)
    # Ring sized to the whole run so phase_ms medians cover the full
    # timed window (see _bench_stream_attr's sizing note).
    tracer = SpanTracer(ring=max(4096, args.e2e_steps * 5 * 8))
    trainer = Trainer(model, loader, params, stats, mesh=mesh,
                      lr_schedule=schedule, sgd_config=SGDConfig(),
                      save_every=10**9, snapshot_path=None,
                      resident=args.resident, device_augment=args.resident,
                      shard_update=args.shard_update,
                      compute_dtype=jnp.bfloat16 if args.bf16 else None,
                      prefetch_depth=args.prefetch_depth,
                      prefetch_workers=args.prefetch_workers,
                      tracer=tracer)
    # The warm-up must leave nothing to compile: every executable JAX
    # prepares (built or read back from the cache) is counted, and the
    # count inside the timed window goes into the record.
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    with contextlib.redirect_stdout(io.StringIO()):
        # Two warm-up epochs.  The first runs from the freshly
        # initialised state, which is not yet placed on the mesh; the
        # state it returns carries the mesh sharding in its type, and the
        # resident epoch program is traced and compiled once more for
        # that type at the second epoch (the per-step program does the
        # same at its second step; ROADMAP, "Left open by PR 21").
        trainer.train(2)
        warm_compiles = len(compiles)
        t_window = tracer.now()
        t0 = time.perf_counter()
        trainer.train(3)  # train() restarts at epoch 0: 3 timed epochs
        dt = time.perf_counter() - t0
    # Tracer-derived per-phase medians over the timed window — the block
    # that makes BENCH_r0N.json e2e trajectories attributable across
    # rounds (which stage moved, not just the headline).
    phase_ms = {k: round(v, 3) for k, v in sorted(
        phase_medians(tracer.spans_since(t_window)).items())}
    samples = n_train * 3
    sps_chip = samples / dt / n_chips
    feed_mode = ("HBM-resident data" if args.resident
                 else f"host-fed, prefetch depth {args.prefetch_depth}")
    print(json.dumps({
        "metric": f"{args.model} e2e train samples/sec/chip "
                  f"(batch {args.batch_size}/chip, "
                  f"{'bf16' if args.bf16 else 'fp32'}, {n_chips} chip(s), "
                  f"{feed_mode}, "
                  f"{'zero-sharded update, ' if args.shard_update else ''}"
                  f"{args.e2e_steps}-step epochs, incl. input pipeline)",
        "value": round(sps_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": 1.0,
        "phase_ms": phase_ms,
        "compiles_in_warmup": warm_compiles,
        "compiles_in_timed_window": len(compiles) - warm_compiles,
    }))


def _bench_guard_overhead(args) -> None:
    """Price the round-12 fault domain on the steady-state step loop.

    Four configurations over the same jitted DP step and device-resident
    batch: drift audit off (the baseline), audit every 50 steps, audit
    every 10 steps, and the spike guard's host-side median/MAD window
    check over the window's losses (the guard itself rides the trainer's
    existing deferred flush, so what is timed here — one stacked
    device_get plus the rolling-window math — upper-bounds its real
    marginal cost).  The audit's cost is one jitted fingerprint program
    (two psums over ``data``, 2*L*4-byte payload) plus a synchronous
    host read of the [L] verdict vector every K steps.

    Headline value: % ms/step overhead of the K=50 audit vs the baseline
    median (acceptance: < 1%).  Record: BENCH_r10.json."""
    from ddp_tpu.resilience.drift import DriftAuditor
    from ddp_tpu.resilience.guard import StepHealthGuard
    mesh = make_mesh(args.num_devices)
    n_chips = mesh.devices.size
    model = get_model(args.model)
    params, stats = model.init(jax.random.key(0))
    schedule = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                                 steps_per_epoch=98)
    step_fn = make_train_step(model, SGDConfig(), schedule, mesh)
    state = init_train_state(params, stats)
    from ddp_tpu.parallel.mesh import data_axis_size
    global_batch = args.batch_size * data_axis_size(mesh)
    ds, _ = synthetic(n_train=global_batch, n_test=1)
    batch = shard_batch({"image": ds.images.astype(np.float32) / 255.0,
                         "label": ds.labels}, mesh)
    rng = jax.random.key(0)
    auditor = DriftAuditor(mesh, state.params, every=1, action="abort")
    n_leaves = len(jax.tree_util.tree_leaves(state.params))

    def window(audit_every: int = 0, guard: StepHealthGuard = None):
        nonlocal state
        losses = []
        for i in range(1, args.steps + 1):
            state, loss = step_fn(state, batch, rng)
            if guard is not None:
                losses.append(loss)
            if audit_every and i % audit_every == 0:
                auditor.audit(state.params, i)
        if guard is not None:
            # The trainer's flush shape: ONE stacked host read, then the
            # rolling-window check over the whole stretch.
            stacked = np.asarray(jax.device_get(jnp.stack(losses)),
                                 np.float64)
            guard.check(stacked, epoch=0, start_step=0)
        return loss

    # Warm every program before any timed window: the step, the audit's
    # fingerprint jit, and the loss stack.
    for _ in range(max(args.warmup, 1)):
        state, loss = step_fn(state, batch, rng)
    auditor.audit(state.params, 1)
    float(loss)

    def make_guard() -> StepHealthGuard:
        # skip on spike: a measurement run must never raise out of the
        # timed window; the cost of the decision path is identical.
        return StepHealthGuard("abort", window=64, spike_factor=2.0,
                               spike_action="skip")

    # Windows run ROUND-ROBIN across configurations: CPU boxes drift
    # (frequency/cache warming over a multi-minute run), and measuring
    # each config in its own contiguous block folds that drift into the
    # config deltas — observed as a "negative overhead" for whichever
    # config happened to run last.
    configs = [("audit_off", {}),
               ("audit_k50", {"audit_every": 50}),
               ("audit_k10", {"audit_every": 10}),
               ("guard_on", {})]
    dts: dict = {name: [] for name, _ in configs}
    for _ in range(max(args.repeats, 1)):
        for name, kw in configs:
            guard = make_guard() if name == "guard_on" else None
            t0 = time.perf_counter()
            loss = window(guard=guard, **kw)
            float(loss)
            dts[name].append(time.perf_counter() - t0)
    per = {}
    for name, _ in configs:
        d = dts[name]
        per[name] = {
            "median_ms_per_step": round(
                statistics.median(d) / args.steps * 1000.0, 4),
            "best_window_ms_per_step": round(
                min(d) / args.steps * 1000.0, 4),
            "window_ms_per_step": [round(x / args.steps * 1000.0, 4)
                                   for x in d],
        }
    base = per["audit_off"]["median_ms_per_step"]
    for k in ("audit_k50", "audit_k10", "guard_on"):
        per[k]["overhead_pct_vs_off"] = round(
            (per[k]["median_ms_per_step"] - base) / base * 100.0, 2)

    # The window deltas bound the overhead from above but sit inside the
    # box's timing noise — so ALSO price one audit call directly (the
    # fingerprint program + the synchronous host verdict read) and derive
    # the amortised per-step cost: audit_ms / K / step_ms.  This is the
    # deterministic number the acceptance gate reads.
    a_dts = []
    for _ in range(max(args.repeats, 1) * 4):
        t0 = time.perf_counter()
        auditor.audit(state.params, 1)
        a_dts.append(time.perf_counter() - t0)
    audit_call_ms = round(statistics.median(a_dts) * 1000.0, 4)
    derived = {f"k{K}": round(audit_call_ms / K / base * 100.0, 4)
               for K in (50, 10)}
    print(json.dumps({
        "metric": f"{args.model} step-level fault-domain overhead "
                  f"(batch {args.batch_size}/chip, fp32, {n_chips} "
                  f"chip(s), {args.steps}-step round-robin windows: "
                  f"drift audit off/K=50/K=10 + spike-guard window "
                  f"check; one audit call priced directly)",
        "value": derived["k50"],
        "unit": "% ms/step of the K=50 drift audit, derived as "
                "audit_call_ms / 50 / audit-off median ms/step "
                "(acceptance: < 1%); window deltas recorded alongside "
                "as the in-noise upper bound",
        "vs_baseline": 1.0,
        "guard_overhead": per,
        "audit_call_ms": audit_call_ms,
        "derived_audit_overhead_pct": derived,
        "audit_payload_bytes": 2 * n_leaves * 4,
        "audit_n_leaves": n_leaves,
    }))


def _bench_mem_ledger(args) -> None:
    """Measured-vs-predicted per-program device memory (obs/memledger.py)
    — the memory twin of the time-cost efficiency ledger.

    The parent computes the liveness predictions in-process (abstract
    eval only, no compile) and spawns ONE pinned-mesh subprocess per
    program to measure it: a shared process would let one program's XLA
    compile arena and cached executables pollute the next program's
    watermark (measured: the TP step's compile arena alone outweighs the
    ~100 MB its sharding saves).  The join asserts the static orderings
    (TP < 1-D, ZeRO < non-ZeRO) on MEASURED bytes — the acceptance
    criterion that makes the liveness numbers trustworthy as auto-plan
    pruning input."""
    from ddp_tpu.obs import memledger
    if args.mesh_shape:
        d, m = (int(x) for x in args.mesh_shape.split(","))
    else:
        d, m = 4, 2  # the budget table's searched shape (BUDGETS.json)
    names = (args.mem_programs.split(",") if args.mem_programs
             else list(memledger.DEFAULT_PROGRAMS))
    pred = memledger.predict(args.model, (d, m), names)
    measured = []
    for name in names:
        child = [sys.executable, os.path.abspath(__file__),
                 "--mem_ledger_child", name, "--model", args.model,
                 "--mesh_shape", f"{d},{m}"]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count"
                             f"={d * m}")
        measured.append(_run_child(child, env, f"mem_ledger[{name}]"))
    rows = memledger.join(pred, measured)
    orderings = memledger.check_orderings(
        {r["program"]: r["measured_bytes"] for r in rows})
    print(memledger.format_ledger(rows, orderings), file=sys.stderr)
    gaps = [abs(r["gap_pct"]) for r in rows if r["gap_pct"] is not None]
    print(json.dumps({
        "metric": f"{args.model} measured-vs-predicted per-program device "
                  f"memory (committed post-step bytes vs liveness "
                  f"resident-set prediction, cpu mesh {d}x{m}, one pinned "
                  f"subprocess per program)",
        "value": round(statistics.median(gaps), 1) if gaps else 0.0,
        "unit": "% median absolute measured-vs-predicted resident-bytes "
                "gap across programs (lower = the liveness model tracks "
                "reality closer); static orderings TP < 1-D and ZeRO < "
                "non-ZeRO asserted on MEASURED bytes",
        "vs_baseline": 1.0,
        "mem_gap_pct": {r["program"]: r["gap_pct"] for r in rows},
        "mem_ledger": rows,
        "orderings": orderings,
    }))
    bad = [o for o in orderings if not o["ok"]]
    if bad:
        raise SystemExit(
            "mem_ledger: measured bytes violate the static ordering(s): "
            + "; ".join(f"{o['smaller']} !< {o['larger']}" for o in bad))


def _bench_mem_ledger_child(args) -> None:
    """One program's measurement, in THIS (pinned-mesh) process — prints
    the memledger record as the bench-child JSON line."""
    from ddp_tpu.obs import memledger
    d, m = ((int(x) for x in args.mesh_shape.split(","))
            if args.mesh_shape else (4, 2))
    print(json.dumps(memledger.measure_in_process(
        args.mem_ledger_child, args.model, (int(d), int(m)))))


def _bench_inspect_overhead(args) -> None:
    """Price an enabled-but-IDLE introspection plane on the step loop.

    Two configurations, round-robin windows (same drift discipline as
    _bench_guard_overhead): the bare jitted step loop, and the same loop
    with everything ``--inspect_port`` adds when nobody is scraping — a
    bound loopback HTTP server on its daemon thread, the per-step probe
    composing the periodic .prom rewrite (one crash-atomic file replace
    per --log_every=50 steps) and the unarmed profile trigger (one lock
    check per step).  Headline: % ms/step overhead (acceptance < 1%)."""
    import tempfile

    from ddp_tpu.obs.inspect import (InspectServer, ProfileTrigger,
                                     PromFileWriter)
    from ddp_tpu.obs.registry import MetricsRegistry
    from ddp_tpu.obs.tracer import SpanTracer
    mesh = make_mesh(args.num_devices)
    n_chips = mesh.devices.size
    model = get_model(args.model)
    params, stats = model.init(jax.random.key(0))
    schedule = functools.partial(triangular_lr, base_lr=0.4, num_epochs=20,
                                 steps_per_epoch=98)
    step_fn = make_train_step(model, SGDConfig(), schedule, mesh)
    state = init_train_state(params, stats)
    from ddp_tpu.parallel.mesh import data_axis_size
    global_batch = args.batch_size * data_axis_size(mesh)
    ds, _ = synthetic(n_train=global_batch, n_test=1)
    batch = shard_batch({"image": ds.images.astype(np.float32) / 255.0,
                         "label": ds.labels}, mesh)
    rng = jax.random.key(0)
    for _ in range(max(args.warmup, 1)):
        state, loss = step_fn(state, batch, rng)
    float(loss)

    counter = [0]
    registry = MetricsRegistry()
    registry.counter("ddp_bench_steps_total",
                     "Bench loop steps").set_function(
                         lambda: float(counter[0]))
    tracer = SpanTracer(spill_path=None, ring=1024, host=0)
    with tempfile.TemporaryDirectory() as tmp:
        writer = PromFileWriter(registry, os.path.join(tmp, "m.prom"),
                                every=50)
        trigger = ProfileTrigger(tracer, tmp, profiler_available=False)
        server = InspectServer(0, registry=registry, tracer=tracer,
                               health=lambda: {"step": counter[0]},
                               profile=trigger)
        try:
            def window(probe: bool):
                nonlocal state
                for _ in range(args.steps):
                    state, loss = step_fn(state, batch, rng)
                    if probe:
                        counter[0] += 1
                        writer.step(counter[0])
                        trigger.step(counter[0])
                return loss

            window(True)  # warm the probe path (first .prom write)
            dts: dict = {"inspect_off": [], "inspect_on": []}
            for _ in range(max(args.repeats, 1)):
                for name in ("inspect_off", "inspect_on"):
                    t0 = time.perf_counter()
                    loss = window(probe=(name == "inspect_on"))
                    float(loss)
                    dts[name].append(time.perf_counter() - t0)
        finally:
            server.close()
            tracer.close()
    per = {name: {
        "median_ms_per_step": round(
            statistics.median(d) / args.steps * 1000.0, 4),
        "best_window_ms_per_step": round(
            min(d) / args.steps * 1000.0, 4),
        "window_ms_per_step": [round(x / args.steps * 1000.0, 4)
                               for x in d],
    } for name, d in dts.items()}
    base = per["inspect_off"]["median_ms_per_step"]
    overhead = round((per["inspect_on"]["median_ms_per_step"] - base)
                     / base * 100.0, 2)
    per["inspect_on"]["overhead_pct_vs_off"] = overhead
    print(json.dumps({
        "metric": f"{args.model} idle introspection-plane overhead "
                  f"(batch {args.batch_size}/chip, fp32, {n_chips} "
                  f"chip(s), {args.steps}-step round-robin windows: bare "
                  f"loop vs bound idle server + per-step probe)",
        "value": max(overhead, 0.0),
        "unit": "% ms/step of --inspect_port enabled-but-idle vs off "
                "(median windows; acceptance: < 1%; negative medians "
                "clamp to 0 — the delta is inside timing noise)",
        "vs_baseline": 1.0,
        "inspect_overhead": per,
    }))


def _bench_calibrate_cost(args) -> None:
    """Fit per-op-class time coefficients from short measured probes and
    price the analysis registry's static cost table through them.

    Probes follow ops/conv_probe.py exactly: each op class is timed as a
    jitted UNROLLED chain of dependency-linked calls (the ``+ acc*1e-30``
    link forces serial execution without changing the math) at two chain
    lengths, and the reported per-call time is the MARGINAL
    ``(t_long - t_short) / (N_LONG - N_SHORT)`` — dispatch/sync overhead
    cancels.  Four coefficients: s/FLOP for conv and for dot (the
    compute-bound classes), s/byte for elementwise memory traffic (the
    cost model's bytes-touched convention: operands + result), and
    s/payload-byte for collectives.

    The prediction is the ADDITIVE no-overlap model
    ``conv_flops*c_conv + dot_flops*c_dot + bytes*c_byte +
    collective_payload*c_coll`` — an upper bound a fused/overlapped
    program beats, meant for ranking programs and catching
    order-of-magnitude cost-table regressions, not as a roofline.
    Measured ms/step (same marginal methodology over the real jitted
    step at a shorter window — each call is a full train step) is
    reported next to the prediction for the data-parallel train step.
    The prediction prices ONE shard's body (the cost model's unit); on
    a virtual CPU mesh the shards SERIALIZE on the host, so measured
    ~= n_dev x predicted there — on a real pod, where shards run in
    parallel, the two are directly comparable.  One JSON line on
    stdout."""
    from jax.sharding import PartitionSpec as P

    from ddp_tpu.analysis.costmodel import program_cost
    from ddp_tpu.analysis.jaxpr_audit import trace_jaxpr
    from ddp_tpu.analysis.programs import (DEFAULT_MODEL, build_context,
                                           build_programs)
    from ddp_tpu.ops.conv_probe import (N_LONG, N_SHORT, best_of,
                                        conv_flops)
    from ddp_tpu.ops.layers import conv2d

    repeats = max(1, min(args.repeats, 4))

    def fit(make_chain, chain_args, work_per_call):
        t_s = best_of(make_chain(N_SHORT), chain_args, repeats)
        t_l = best_of(make_chain(N_LONG), chain_args, repeats)
        marginal = max((t_l - t_s) / (N_LONG - N_SHORT), 1e-12)
        return marginal / work_per_call

    # conv: deepnn-interior-ish SAME 3x3 shape (16x16x64 -> 64).
    xc = jnp.ones((8, 16, 16, 64), jnp.float32)
    wc = jnp.ones((3, 3, 64, 64), jnp.float32)

    def conv_chain(n):
        def win(x, w):
            acc = jnp.zeros((), x.dtype)
            for _ in range(n):
                acc = jnp.mean(conv2d(x, w + acc * 1e-30))
            return acc
        return jax.jit(win)

    c_conv = fit(conv_chain, (xc, wc), conv_flops(8, 16, 64, 64))

    # dot: square matmul, 2*K^3 FLOPs/call.
    k = 256
    xd = jnp.ones((k, k), jnp.float32)
    wd = jnp.ones((k, k), jnp.float32)

    def dot_chain(n):
        def win(x, w):
            acc = jnp.zeros((), x.dtype)
            for _ in range(n):
                acc = jnp.mean(x @ (w + acc * 1e-30))
            return acc
        return jax.jit(win)

    c_dot = fit(dot_chain, (xd, wd), 2.0 * k * k * k)

    # elementwise bytes: one mul (read 4 MiB + write 4 MiB) + one mean
    # (read 4 MiB) per link = 3 * size * itemsize bytes-touched/call,
    # matching the cost model's operands-plus-result convention.
    ve = jnp.ones((1 << 20,), jnp.float32)

    def ew_chain(n):
        def win(v):
            acc = jnp.zeros((), v.dtype)
            for _ in range(n):
                acc = jnp.mean(v * (1.0 + acc * 1e-30))
            return acc
        return jax.jit(win)

    c_byte = fit(ew_chain, (ve,), 3.0 * ve.size * 4)

    # collective: psum over the mesh's first axis inside shard_map; the
    # cost model charges a collective its PER-SHARD operand bytes, so
    # that is the work unit here too.  The link's add/mean traffic rides
    # along (the coefficient slightly upper-bounds pure transport).
    mesh = make_mesh(args.num_devices)
    axis = mesh.axis_names[0]
    vc = jnp.ones((mesh.devices.size * (1 << 16),), jnp.float32)
    shard_bytes = vc.size * 4 // mesh.devices.size

    def coll_chain(n):
        def body(v):
            acc = jnp.zeros((), v.dtype)
            for _ in range(n):
                acc = jnp.mean(jax.lax.psum(v + acc * 1e-30, axis))
            return acc
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                                     out_specs=P()))

    c_coll = fit(coll_chain, (vc,), shard_bytes)

    # Price the registry.  The bench-level default model is vgg, but the
    # analysis registry (and BUDGETS.json) defaults to deepnn — follow
    # the registry unless the user explicitly picked something else.
    model_name = DEFAULT_MODEL if args.model == "vgg" else args.model
    n_dev = jax.device_count()
    m = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    ctx = build_context(model_name, mesh_2d=(n_dev // m, m))
    progs = build_programs(ctx)
    predicted = {}
    for prog in progs:
        cost = program_cost(trace_jaxpr(prog.fn, prog.args))
        pred_s = (cost.by_class["conv"] * c_conv
                  + cost.by_class["dot"] * c_dot
                  + cost.bytes * c_byte
                  + cost.collective_payload_bytes * c_coll)
        predicted[prog.name] = round(pred_s * 1e3, 3)

    # Measured ms/step for the flagship data-parallel train step: the
    # same marginal differencing, at a shorter window (each call is a
    # full train step, not a microsecond kernel).  Each timed window
    # starts from freshly materialised zero buffers so donation on a
    # real accelerator cannot invalidate reused args.
    meas_name = "train_step@dp8"
    prog = next(p for p in progs if p.name == meas_name)
    w_short, w_long = 2, 8

    def mat(x):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            return jax.random.key(0)
        return jnp.zeros(x.shape, x.dtype)

    def window(n):
        state, batch, rng = jax.tree_util.tree_map(mat, prog.args)
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = prog.fn(state, batch, rng)
            state = out[0]
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    window(1)  # compile + warm
    t_s = min(window(w_short) for _ in range(repeats))
    t_l = min(window(w_long) for _ in range(repeats))
    measured_ms = max(t_l - t_s, 0.0) / (w_long - w_short) * 1e3

    record = {
        "metric": f"{model_name} cost-model calibration: predicted vs "
                  f"measured ms/step ({n_dev}-device "
                  f"{jax.default_backend()} mesh)",
        "value": predicted.get(meas_name),
        "unit": "ms/step",
        "vs_baseline": 1.0,
        "measured_ms_per_step": {meas_name: round(measured_ms, 3)},
        "predicted_ms_per_step": predicted,
        # The mesh size the measurement ran on — the virtual-mesh
        # serialization factor consumers (obs/ledger.py pred_scale,
        # --autoplan_bench's calibration_gap_pct) need; the "@dp8" in
        # the program NAME is the registry's fixed naming, not this.
        "n_devices": n_dev,
        "note": "prediction prices one shard's body; a virtual CPU "
                "mesh serializes shards, so expect measured ~= "
                f"{n_dev} x predicted there",
        "coefficients": {
            "conv_s_per_flop": c_conv,
            "dot_s_per_flop": c_dot,
            "elementwise_s_per_byte": c_byte,
            "collective_s_per_payload_byte": c_coll,
        },
    }
    if getattr(args, "ledger_spill", None):
        # The efficiency ledger: measured spans vs these predictions,
        # per phase, with the mesh's serialization factor applied.
        from ddp_tpu.obs.export import read_spill
        from ddp_tpu.obs.ledger import build_ledger
        try:
            spans = read_spill([args.ledger_spill])
            record["ledger"] = build_ledger(spans, record,
                                            pred_scale=float(n_dev))
        except (OSError, ValueError) as e:
            record["ledger_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


if __name__ == "__main__":
    main()
