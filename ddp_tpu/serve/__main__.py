"""``python -m ddp_tpu.serve`` — stand up a model server on a checkpoint.

Loads the newest verifiable checkpoint (the trainer's own lineage walk),
AOT-compiles one eval forward per padded batch bucket, and serves
``/predict`` / ``/healthz`` / ``/stats`` / ``/metrics`` (Prometheus
text exposition) over a stdlib threaded HTTP
server fronted by the dynamic micro-batcher.  SIGTERM/SIGINT drain
gracefully through the resilience preemption guard: admission stops
(503 + draining healthz), accepted requests finish, the span spill is
flushed, exit 0.  A second signal kills immediately (the guard's
standard escape hatch).

Usage:
    python multigpu.py 5 1 --snapshot_path ck.pt        # train
    python -m ddp_tpu.serve --snapshot_path ck.pt --port 8100
    curl -s localhost:8100/healthz
    curl -s -X POST localhost:8100/predict -d '{"instances": [[[..]]]}'
    python -m ddp_tpu.obs serve_spill.jsonl             # telemetry
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Optional

import jax.numpy as jnp

from ..utils.platform import device_line, enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m ddp_tpu.serve",
        description=__doc__.splitlines()[0])
    p.add_argument("--snapshot_path", default="checkpoint.pt",
                   help="Checkpoint head path or directory (the trainer's "
                        "--snapshot_path); the newest VERIFIABLE snapshot "
                        "is loaded via resilience.lineage (default: "
                        "checkpoint.pt)")
    p.add_argument("--model", default="vgg",
                   choices=["vgg", "deepnn", "resnet18", "transformer",
                            "tinylm"],
                   help="Model architecture the checkpoint was trained "
                        "with (default: vgg — the reference's model); "
                        "tinylm + --generate serves token streams")
    p.add_argument("--host", default="127.0.0.1",
                   help="Bind address (default 127.0.0.1; 0.0.0.0 to "
                        "expose)")
    p.add_argument("--port", default=8100, type=int,
                   help="Listen port (default 8100; 0 picks a free port "
                        "and prints it)")
    p.add_argument("--buckets", default="1,8,32,128",
                   help="Padded batch buckets, comma-separated; each is "
                        "rounded up to a mesh-size multiple and compiled "
                        "ONCE at startup — the whole executable set, "
                        "bounded and known (default 1,8,32,128)")
    p.add_argument("--max_batch", default=None, type=int,
                   help="Batch-former row target (default: the largest "
                        "bucket)")
    p.add_argument("--max_wait_ms", default=5.0, type=float,
                   help="Batch-forming wait budget from the oldest queued "
                        "request (default 5 ms): a lone request never "
                        "waits longer; a busy queue never waits at all")
    p.add_argument("--queue_depth", default=256, type=int,
                   help="Admission queue bound; a full queue sheds with "
                        "503 instead of queueing into unbounded latency "
                        "(default 256 requests)")
    p.add_argument("--generate", action="store_true",
                   help="Generative decoding mode: front the tinylm "
                        "decoder (models/transformer.py) with a KV-cache "
                        "engine + token-level continuous batcher and "
                        "serve POST /generate; /predict routes stay on "
                        "classifier servers only")
    p.add_argument("--slots", default=8, type=int,
                   help="Generative only: concurrent KV-cache streams "
                        "per replica (rounded up to a data-mesh "
                        "multiple; default 8)")
    p.add_argument("--prefill_buckets", default="16,64",
                   help="Generative only: padded prompt-length buckets, "
                        "comma-separated; prefill + cache-write compile "
                        "once per bucket (default 16,64)")
    p.add_argument("--max_new_tokens", default=32, type=int,
                   help="Generative only: per-request generation cap "
                        "(requests may ask for fewer; default 32)")
    p.add_argument("--fleet", default=0, type=int, metavar="N",
                   help="Serve N in-process engine replicas behind the "
                        "fault-tolerant router (health-driven ejection, "
                        "retry budgets, circuit breakers) instead of one "
                        "bare engine; 0 = single-engine mode (default)")
    p.add_argument("--swap_poll_s", default=0.0, type=float,
                   help="Fleet only: poll the checkpoint lineage every "
                        "this many seconds and hot-swap newly published "
                        "verifiable snapshots into rotation with zero "
                        "downtime (0 disables the watcher; default 0)")
    p.add_argument("--bf16", action="store_true",
                   help="Serve in bfloat16 compute (match the flag the "
                        "checkpoint was trained with for parity)")
    p.add_argument("--num_devices", default=None, type=int,
                   help="Mesh size override (default: all visible "
                        "devices); formed batches shard across the same "
                        "data axis training uses")
    p.add_argument("--trace_spill", default=None,
                   metavar="PATH",
                   help="Span spill (queue_wait/batch_form/pad/h2d/"
                        "forward/d2h), analyzable with python -m "
                        "ddp_tpu.obs exactly like a training spill; '' "
                        "keeps tracing in-memory only (default: "
                        "serve_spill.jsonl next to --snapshot_path, the "
                        "run's output dir)")
    p.add_argument("--obs_off", action="store_true",
                   help="Telemetry kill-switch (the training CLI's "
                        "contract: no spans, no spill, zero overhead)")
    return p


def main(argv: Optional[list] = None) -> int:
    args = build_parser().parse_args(argv)

    from ..obs.registry import MetricsRegistry
    from ..obs.tracer import NullTracer, SpanTracer, set_tracer
    from ..parallel.mesh import make_mesh
    from ..resilience.faults import install_serve_faults
    from ..resilience.preemption import PreemptionGuard
    from .batcher import DynamicBatcher
    from .engine import ServeEngine
    from .fleet import ServeFleet
    from .http import ServeHTTPServer

    # Unset --trace_spill defaults next to the checkpoint head (the
    # run's output dir), not the CWD; '' stays the explicit kill value.
    from ..obs.tracer import default_spill_path
    trace_spill = args.trace_spill
    if trace_spill is None:
        trace_spill = default_spill_path(args.snapshot_path,
                                         "serve_spill.jsonl")
    if args.obs_off:
        tracer = NullTracer()
    else:
        tracer = SpanTracer(spill_path=trace_spill or None,
                            ring=65536, host=0)
    enable_compile_cache()
    mesh = make_mesh(args.num_devices)
    print(device_line(mesh), file=sys.stderr, flush=True)
    registry = MetricsRegistry()  # one /metrics surface per process
    buckets = [int(b) for b in args.buckets.split(",") if b]
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    try:
        set_tracer(tracer)
        print(f"loading newest verifiable checkpoint under "
              f"{args.snapshot_path!r} ...", file=sys.stderr)
        fleet = engine = batcher = None
        prefill_buckets = [int(b) for b in args.prefill_buckets.split(",")
                           if b]
        if args.fleet >= 1:
            t0 = time.monotonic()
            fleet = ServeFleet(
                args.snapshot_path, args.model, mesh=mesh,
                n_replicas=args.fleet, buckets=buckets,
                compute_dtype=compute_dtype, max_batch=args.max_batch,
                max_wait_ms=args.max_wait_ms,
                queue_depth=args.queue_depth, tracer=tracer,
                registry=registry, generate=args.generate,
                slots=args.slots, prompt_buckets=prefill_buckets,
                max_new_tokens=args.max_new_tokens)
            install_serve_faults(fleet)
            fleet.start(poll_s=args.swap_poll_s)
            print(f"warmed {args.fleet} replica(s) in "
                  f"{time.monotonic() - t0:.1f}s (checkpoint step "
                  f"{fleet.health()['checkpoint_step']}; hot-swap watcher "
                  f"{'every %.1fs' % args.swap_poll_s if args.swap_poll_s > 0 else 'off'})",
                  file=sys.stderr)
            httpd = ServeHTTPServer((args.host, args.port), fleet=fleet)
        elif args.generate:
            from .kvcache import KVCacheEngine
            from .token_batcher import TokenBatcher
            engine = KVCacheEngine.from_checkpoint(
                args.snapshot_path, args.model, mesh=mesh,
                slots=args.slots, prompt_buckets=prefill_buckets,
                compute_dtype=compute_dtype, tracer=tracer,
                registry=registry)
            t0 = time.monotonic()
            compiled = engine.warm()
            print(f"compiled {compiled} executable(s) (bound "
                  f"{engine.compile_bound}: prefill+write per prompt "
                  f"bucket {list(engine.prompt_buckets)} + 1 decode) in "
                  f"{time.monotonic() - t0:.1f}s (checkpoint "
                  f"{engine.checkpoint_file!r}, step "
                  f"{engine.checkpoint_step}); no stream pays a compile",
                  file=sys.stderr)
            batcher = TokenBatcher(engine,
                                   max_new_tokens=args.max_new_tokens,
                                   queue_depth=args.queue_depth,
                                   tracer=tracer,
                                   registry=registry).start()
            httpd = ServeHTTPServer((args.host, args.port), engine, batcher)
        else:
            engine = ServeEngine.from_checkpoint(
                args.snapshot_path, args.model, mesh=mesh, buckets=buckets,
                compute_dtype=compute_dtype, tracer=tracer,
                registry=registry)
            t0 = time.monotonic()
            compiled = engine.warm()
            print(f"compiled {compiled} bucket executable(s) "
                  f"{list(engine.buckets)} in {time.monotonic() - t0:.1f}s "
                  f"(checkpoint {engine.checkpoint_file!r}, epoch "
                  f"{engine.checkpoint_epoch}); no request pays a compile",
                  file=sys.stderr)
            batcher = DynamicBatcher(engine, max_batch=args.max_batch,
                                     max_wait_ms=args.max_wait_ms,
                                     queue_depth=args.queue_depth,
                                     tracer=tracer,
                                     registry=registry).start()
            httpd = ServeHTTPServer((args.host, args.port), engine, batcher)
        listener = threading.Thread(target=httpd.serve_forever,
                                    daemon=True, name="serve-http")
        listener.start()
        # Graceful drain on SIGTERM/SIGINT — the same resilience guard
        # the trainer uses for preemption (main-thread only; under a
        # non-main-thread embedder, stop via drain()/close()+close()).
        guard = (PreemptionGuard().install()
                 if threading.current_thread() is threading.main_thread()
                 else None)
        host, port = httpd.server_address[:2]
        what = (f"{args.model} fleet of {args.fleet}" if fleet is not None
                else args.model)
        routes = ("/generate" if args.generate else "/predict")
        print(f"serving {what} on http://{host}:{port} "
              f"({routes} /healthz /stats /metrics); SIGTERM drains "
              "gracefully", flush=True)
        try:
            while guard is None or not guard.noticed():
                time.sleep(0.2)
        except KeyboardInterrupt:
            pass  # second Ctrl-C during shutdown lands here; drain anyway
        print("draining: admission stopped, serving accepted requests ...",
              file=sys.stderr)
        if fleet is not None:
            drained = fleet.close(timeout=30.0)
        else:
            drained = batcher.drain(timeout=30.0)
        # Idempotent listener teardown: a second SIGTERM racing this
        # shutdown may have already closed it — close() absorbs that.
        httpd.close()
        if guard is not None:
            guard.uninstall()
        stats = (fleet.stats() if fleet is not None else
                 {"engine": engine.stats(), "batcher": batcher.stats()})
        print(json.dumps(stats), file=sys.stderr)
        print(f"drained={'clean' if drained else 'FORCED'}; bye",
              file=sys.stderr)
        return 0 if drained else 1
    finally:
        set_tracer(NullTracer())
        tracer.flush(fsync=True)
        tracer.close()


if __name__ == "__main__":
    raise SystemExit(main())
