"""Run one benchmark cell once and print its result line.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is resolved by name, through data only: its entry in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``) and
a job mix (``traffic/<traffic>.json``); the mix names the runner
(``runners/<runner>.py``); the cell's per-layer metrics are the
``per_layer`` entries of ``BENCHMARK.json`` that list it (or list no
cell), each read by ``layer_metrics/<name>.py``.  Nothing here, and
nothing below it, branches on a cell's or a model's name: a later PR
adds files and entries and edits none (README.md).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``).  Everything the program prints goes to standard error.
Anything but a TPU with the chips the cell asks for is an error (exit 2,
no result line), unless ``--rehearse``: the same code at the tiny preset
of ``tests/tiny/``, on any backend, ``"correct": false``, and no metric
that only a chip can give.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record
    of its start (10 ms ticks), so that interpreter start-up and imports
    count as set-up."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _IMPORTED_AT


_IMPORTED_AT = time.monotonic()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlay(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def resolve(workload: str, rehearse: bool, root: str = ROOT) -> dict:
    """Everything that defines one cell, gathered from the data files."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has: "
                         + ", ".join(sorted(cells)))
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.join(root, spec["paths"][0])
    mix = load_json(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))
    if rehearse:
        tiny = load_json(os.path.join(bench_dir, "tests", "tiny",
                                      mix["runner"] + ".json"))
        config = overlay(config, tiny.get("config", {}))
        mix = overlay(mix, tiny.get("traffic", {}))

    def in_cell(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell, "config": config, "mix": mix,
        "end_to_end": [m for m in spec["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in spec["per_layer"] if in_cell(m)],
        "peaks": load_json(os.path.join(bench_dir, "peaks.json")),
        "bench_dir": bench_dir,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset, any backend, correct=false")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's files here (default: a "
                         "temporary directory, removed)")
    args = ap.parse_args(argv)

    resolved = resolve(args.workload, args.rehearse)
    runner = importlib.import_module(
        "benchmark.runners." + resolved["mix"]["runner"])
    # The program prints epoch headers on standard output; the result
    # line must be the last line there, so its prints go to stderr.
    with contextlib.redirect_stdout(sys.stderr):
        result = runner.run(resolved, args, process_age_s)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
