"""Look at a profiler trace by hand, and cut the small recorded trace the
reduction is tested against.

    python benchmark/tools/record_trace.py <trace_dir> [--out small.json]
        [--ms 40] [--devices 1]

Prints the planes and lines with their event counts, the 40 operations
with the most self time, and what
``trace_reduce.reduce`` makes of the whole.  With ``--out``, writes the
first ``--ms`` milliseconds of operations of the first ``--devices``
device planes (and the benchmark's own host annotations) as JSON in the
form ``trace_reduce`` takes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import trace_reduce as tr
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--out", default=None)
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()

    trace = tr.load_newest(args.trace_dir)
    for plane in trace["planes"]:
        print("PLANE", plane["name"])
        for line in plane["lines"]:
            evs = line["events"]
            span = ((max(e[1] + e[2] for e in evs) - min(e[1] for e in evs))
                    / 1e6 if evs else 0.0)
            print(f"  LINE {line['name']!r}: {len(evs)} events over "
                  f"{span:.2f} ms; first: {evs[0][:3] if evs else None}")
    reduced = tr.reduce(trace)
    if reduced is None:
        print("no device operations in this trace")
        return 1
    print("REDUCED", json.dumps({k: v for k, v in reduced.items()
                                 if k not in ("ops", "conv_ops")}, indent=1))
    print("CONV seconds", tr.conv_seconds(reduced))
    for name, secs in list(reduced["ops"].items())[:40]:
        print(f"  {secs * 1e3:10.3f} ms  {tr.short_label(name)}")

    if args.out:
        small = {"planes": []}
        kept_devices = 0
        for plane in trace["planes"]:
            is_dev = plane["name"].startswith(tr.DEVICE_PLANE_PREFIX)
            if is_dev and kept_devices >= args.devices:
                continue
            kept_devices += is_dev
            lines = []
            for line in plane["lines"]:
                if is_dev and line["name"] != tr.OP_LINE:
                    continue
                evs = line["events"]
                lo = min(e[1] for e in evs)
                keep = [e for e in evs if not is_dev
                        or e[1] + e[2] <= lo + args.ms * 1e6]
                lines.append({"name": line["name"], "events": keep})
            small["planes"].append({"name": plane["name"], "lines": lines})
        with open(args.out, "w") as f:
            json.dump(small, f, separators=(",", ":"))
        print("wrote", args.out, os.path.getsize(args.out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
