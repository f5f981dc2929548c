"""The batch sweep of PERF.md: one cell at several batches a chip, each in
a process of its own (a peak counter never falls within one), the rest
of the cell as its files say: the configuration's peak learning rate is
the same at every batch.  Prints one JSON line a batch, with
``memory_peak_gb`` as the result line counts it (live arrays plus the
runtime's reservation for the program's temporaries).

    python benchmark/tools/batch_sweep.py --workload vgg_train_resident_1chip \\
        --batches 512,1024,2048,3072,4096 --seconds 5

The parent never touches JAX: each child is the one process on the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def child(args) -> int:
    from benchmark import run as bench_run
    from benchmark.runners import train
    resolved = bench_run.resolve(args.workload, args.rehearse)
    resolved["mix"] = dict(resolved["mix"], batch_per_chip=args.batch)
    with contextlib.redirect_stdout(sys.stderr):
        result = train.run(resolved, args, bench_run.process_age_s)
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="512,1024,2048,3072,4096")
    ap.add_argument("--batch", type=int, default=None,
                    help="(child) the one batch to run")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace, args.trace_dir = 0, None
    if args.batch is not None:
        return child(args)
    for b in args.batches.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--batch", b, "--seconds", str(args.seconds),
               "--seed", str(args.seed)]
        if args.rehearse:
            cmd.append("--rehearse")
        proc = subprocess.run(cmd, capture_output=True, text=True)
        detail = [ln for ln in proc.stderr.splitlines()
                  if ln.startswith("benchmark-detail: ")]
        row = {"batch_per_chip": int(b), "rc": proc.returncode}
        if proc.returncode == 0 and detail:
            d = json.loads(detail[-1][len("benchmark-detail: "):])
            row.update(
                samples_per_s_per_chip=d["rate_per_chip"],
                memory_peak_gb=d["memory_peak_bytes"] / 1e9,
                setup_s=d["setup_s"], epochs=d["epochs"],
                window_s=d["window_s"], checks=d["checks"])
        else:
            row["stderr_tail"] = proc.stderr[-1500:]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
