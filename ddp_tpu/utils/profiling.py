"""Per-op summary of a jax.profiler trace — the analysis behind a
roofline table, as a reusable tool.

The reference's only timing is two ``time.time()`` calls around training
(singlegpu.py:232-234); this framework additionally captures XLA traces
(``--profile_dir`` on the CLI, ``bench.py --profile_dir``) and this module
turns a captured trace into the numbers that matter on TPU: device-busy
time per step and the top ops by total device time, aggregated from the
``.xplane.pb`` the profiler writes.

Parsing uses the tensorflow-bundled xplane proto when available (the
heavyweight tensorboard profile plugin in this image is version-skewed
against its own pywrap helpers, so events are aggregated here directly);
set ``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` if the fast-proto
runtime rejects the generated module.

Usage:
    python -m ddp_tpu.utils.profiling /tmp/prof [--steps 20] [--top 20]
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
from typing import Dict, List, Optional, Tuple


def _load_xspaces(trace_dir: str) -> list:
    """All .xplane.pb files of the newest capture session (multi-host
    traces write one file per host; sessions are timestamped dirs)."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:  # pragma: no cover - tf is baked into the image
        raise RuntimeError(
            "xplane parsing needs the tensorflow-bundled xplane proto; "
            f"import failed: {e}")
    sessions = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not sessions:
        raise FileNotFoundError(
            f"no capture sessions under {trace_dir}/plugins/profile/ — "
            "pass the directory given to jax.profiler.start_trace/"
            "--profile_dir")
    spaces = []
    for path in sorted(glob.glob(os.path.join(sessions[-1],
                                              "*.xplane.pb"))):
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        spaces.append(xs)
    if not spaces:
        raise FileNotFoundError(f"no .xplane.pb in {sessions[-1]}")
    return spaces


def device_op_summary(trace_dir: str, steps: int = 1,
                      device_plane: Optional[str] = None
                      ) -> Dict[str, List[Tuple[str, float, float]]]:
    """Aggregate per-op device time from a trace.

    Returns ``{"<plane>/<line>": [(op_name, total_ms, ms_per_step), ...]}``
    for EVERY device plane with events (one per chip; multi-host captures
    contribute one file per host), ops sorted by total time descending —
    nothing is silently dropped on multi-chip traces.  ``device_plane``
    restricts to one plane by exact name; ``steps`` divides totals into
    per-step cost (the number of steps captured in the trace).
    """
    planes = [p for xs in _load_xspaces(trace_dir) for p in xs.planes
              if (p.name == device_plane if device_plane
                  else ("/device:" in p.name
                        and any(len(ln.events) for ln in p.lines)))]
    if not planes:
        raise ValueError(f"no matching device plane with events in "
                         f"{trace_dir}")
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in planes:
        for line in plane.lines:
            totals: collections.Counter = collections.Counter()
            for ev in line.events:
                totals[plane.event_metadata[ev.metadata_id].name] += \
                    ev.duration_ps
            out[f"{plane.name}/{line.name}"] = [
                (name, ps / 1e9, ps / 1e9 / max(steps, 1))
                for name, ps in totals.most_common()]
    return out


# Op-name → phase rules for categorize().  Order matters: first match wins.
# Derived from reading the optimized HLO of the VGG train step on v5e:
# conv work appears as
# %convolution OR as kOutput fusions carrying a
# ``convolution_algorithm_config`` — multiply_reduce_fusion (dgrad conv +
# fused dγ/dβ epilogue), multiply_subtract_fusion (wgrad conv fused with
# the SGD update), and (XLA names these inconsistently) plain
# ``fusion.N`` (the forward convs + their BN-stats epilogues land here),
# which ONLY an HLO dump can disambiguate from elementwise fusions —
# hence conv_ops below.  Max-pool backward is select-and-scatter;
# copy/slice-start are async DMA.
_CATEGORY_RULES = (
    ("conv dgrad (+BN-bwd epilogue)", ("multiply_reduce_fusion",)),
    ("conv wgrad (+SGD update)", ("multiply_subtract_fusion",)),
    ("convolution (unfused)", ("convolution",)),
    ("pool backward", ("select_and_scatter", "select-and-scatter")),
    ("pool / reduce-window", ("reduce_window", "reduce-window")),
    ("collectives", ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")),
    ("async copies/DMA", ("copy-start", "copy-done", "slice-start",
                          "slice-done", "dynamic-update-slice-start")),
    ("layout copies / bitcasts", ("copy", "bitcast", "transpose")),
    ("elementwise/reduction fusions", ("fusion",)),
)


def conv_fusions_from_hlo(hlo_text: str) -> Dict[str, str]:
    """Map fusion-op names that are really CONVOLUTIONS to a conv
    sub-kind.  The discriminator is ``convolution_algorithm_config`` in
    the backend_config — present exactly on conv emitters (a bare
    ``window_config`` appears on many unrelated TPU ops, including
    copies, and over-matches).  Feed the text from
    ``jitted.lower(...).compile().as_text()`` of the SAME program the
    trace captured — trace op names alone cannot distinguish a kOutput
    conv fusion named ``fusion.164`` from an elementwise one."""
    import re
    out: Dict[str, str] = {}
    for m in re.finditer(
            r"%(\S+) = [^\n]*convolution_algorithm_config", hlo_text):
        name = m.group(1)
        if "multiply_reduce" in name:
            kind = "conv dgrad (+BN-bwd epilogue)"
        elif "multiply_subtract" in name:
            kind = "conv wgrad (+SGD update)"
        else:
            kind = "conv (fused, kind per HLO)"
        out[name] = kind
    return out


def categorize(ops: List[Tuple[str, float, float]],
               conv_ops: Optional[Dict[str, str]] = None
               ) -> List[Tuple[str, float, float]]:
    """Fold a per-op list into phase buckets (same (name, total_ms,
    ms_per_step) tuples, sorted by total).  ``conv_ops`` (from
    :func:`conv_fusions_from_hlo`) reclassifies ambiguous ``fusion.N``
    names that are conv fusions.  Unmatched ops land in 'other'."""
    buckets: collections.Counter = collections.Counter()
    per: collections.Counter = collections.Counter()
    for name, tot, step_ms in ops:
        # Trace op names can be FULL definition lines ("%fusion.2 = (...)
        # fusion(%copy-done.57, ...)"); classify on the op's own name only
        # or operand names pollute the buckets (a fusion consuming
        # %copy-done.57 is not a copy).
        bare = name.lstrip("%").split(" = ")[0].split("(")[0].strip()
        if conv_ops and bare in conv_ops:
            label = conv_ops[bare]
        else:
            low = bare.lower()
            for label, keys in _CATEGORY_RULES:
                if any(k in low for k in keys):
                    break
            else:
                label = "other"
        buckets[label] += tot
        per[label] += step_ms
    return [(label, buckets[label], per[label])
            for label, _ in buckets.most_common()]


def device_busy_ms_per_step(trace_dir: str, steps: int = 1
                            ) -> Dict[str, float]:
    """Total device-busy ms/step per device plane line of a trace — the
    denominator of the streaming-gap attribution: for a profiled streaming
    run, ``wall_ms_per_step - max(busy line)`` is device IDLE per step,
    i.e. time the chip sat waiting on the input pipeline / dispatch
    (exactly how the round-4 resident-vs-step gap was attributed)."""
    return {line: sum(t for _, t, _ in ops) / max(steps, 1)
            for line, ops in device_op_summary(trace_dir,
                                               steps=steps).items()}


def attribute_streaming(host_ms: float, h2d_ms: float, step_ms: float,
                        wall_ms: float) -> Dict[str, float]:
    """Pipeline-model decomposition of a streaming run's per-step wall time
    (the streaming-gap table).

    Inputs are the three stages measured in ISOLATION at the same shape
    (sequential host materialise+augment, blocking H2D upload,
    steady-state device step — the pipeline-floor model needs each
    stage's uncontended cost; the same run's tracer spans ship alongside
    as the record's ``phase_ms`` block, bench.py --stream_attr) plus the
    measured end-to-end streaming wall time per step.  In a perfectly overlapped pipeline the
    wall time equals the SLOWEST stage (the others hide behind it);
    everything above that floor is serialization the overlap engine
    failed to hide — dispatch gap.  Returns the stage costs, the
    bottleneck stage name, the pipeline floor, ``dispatch_gap_ms`` and
    ``overlap_efficiency`` (floor / wall; 1.0 = every non-bottleneck
    stage fully hidden).

    Edge discipline (measurement noise can put wall *below* the floor —
    e.g. a floor stage timed on a colder cache than the real run): the
    gap is CLAMPED at 0 and efficiency capped at 1.0, so a noisy sample
    reads as "fully overlapped", never as a negative gap a trend
    consumer would mis-sum; ``wall_ms <= 0`` (no steps ran) reports zero
    efficiency and zero gap rather than dividing by it.
    """
    stages = {"host_augment_ms": host_ms, "h2d_ms": h2d_ms,
              "device_step_ms": step_ms}
    bottleneck = max(stages, key=lambda k: stages[k])
    floor = stages[bottleneck]
    return {
        **{k: round(v, 3) for k, v in stages.items()},
        "streaming_wall_ms": round(wall_ms, 3),
        "bottleneck": bottleneck,
        "pipeline_floor_ms": round(floor, 3),
        "dispatch_gap_ms": round(max(wall_ms - floor, 0.0), 3)
        if wall_ms > 0 else 0.0,
        "overlap_efficiency": round(min(floor / wall_ms, 1.0), 4)
        if wall_ms > 0 else 0.0,
    }


def print_summary(trace_dir: str, steps: int = 1, top: int = 20,
                  by_category: bool = False,
                  hlo_path: Optional[str] = None) -> None:
    summary = device_op_summary(trace_dir, steps=steps)
    conv_ops = None
    if hlo_path:
        with open(hlo_path) as f:
            conv_ops = conv_fusions_from_hlo(f.read())
    for line_name, ops in summary.items():
        if not ops:
            continue
        total_ms = sum(t for _, t, _ in ops)
        print(f"--- {line_name}: {len(ops)} distinct ops, "
              f"{total_ms:.2f} ms total, {total_ms / max(steps, 1):.3f} "
              "ms/step")
        rows = categorize(ops, conv_ops) if by_category else ops[:top]
        for name, tot, per in rows:
            print(f"  {per:8.3f} ms/step  {name[:100]}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace_dir")
    p.add_argument("--steps", type=int, default=1,
                   help="Steps captured in the trace (divides totals)")
    p.add_argument("--top", type=int, default=20)
    p.add_argument("--by_category", action="store_true",
                   help="Fold ops into phase buckets (conv fwd/dgrad/"
                        "wgrad incl. their fused epilogues, pool, "
                        "collectives, DMA, elementwise) instead of "
                        "listing the top ops — the one-look roofline "
                        "attribution")
    p.add_argument("--hlo", default=None,
                   help="Optimized-HLO text file (from jitted.lower()."
                        "compile().as_text()) used to reclassify "
                        "ambiguous fusion.N names that are really conv "
                        "fusions — without it those land in the "
                        "elementwise bucket.  MUST come from the same "
                        "compiled program the trace captured: fusion "
                        "numbering is not stable across programs")
    args = p.parse_args()
    print_summary(args.trace_dir, steps=args.steps, top=args.top,
                  by_category=args.by_category, hlo_path=args.hlo)


if __name__ == "__main__":
    main()
