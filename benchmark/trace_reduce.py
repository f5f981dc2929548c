"""From a profiler trace to numbers: the reduction every per-layer
reader of a device metric shares (the yardstick; a PR that claims a gain
may not edit it).

A trace is read with ``jax.profiler.ProfileData`` alone and brought into
plain Python first:

    {"planes": [{"name": str, "lines": [{"name": str, "events":
        [[name, start_ns, duration_ns], ...]}]}]}

(on a TPU an operation's event is named by its whole HLO instruction and
carries no category)

so that the arithmetic below runs the same on a trace from the chip and
on the small recorded one under ``testdata/`` (``tests/test_trace_reduce
.py``; ``tools/record_trace.py`` wrote it).

What the arithmetic is:

- *busy* is the UNION of the intervals in which an operation runs on a
  device, never their sum: an operation inside a loop lies inside the
  loop's own event, and two overlapping operations are not two times;
- an operation's *self time* is its duration less what its children on
  the same line cover, so that a loop does not take the credit for its
  body in the table of operations;
- an *idle gap* is a stretch of the traced window in which nothing runs
  on the device; it is named after the host span that covers most of it
  (the program's own spans, moved onto the profiler's clock through one
  annotation that both clocks saw);
- a *collective* is an operation whose name starts with one of
  ``COLLECTIVE_PREFIXES``; its *exposed* part is the part of its interval
  in which no other operation runs on that device.
"""
from __future__ import annotations

import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
MIN_GAP_NS = 20_000
TOP = 10

Interval = Tuple[float, float]


# -- reading ----------------------------------------------------------------

def load_xplane(path: str, host_prefix: str = "bench:") -> dict:
    """Device planes whole; of the host planes only the events this
    benchmark annotated (``bench:...``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if device or ev.name.startswith(host_prefix):
                    events.append([ev.name, ev.start_ns, ev.duration_ns])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_newest(trace_dir: str) -> dict:
    """The newest capture under a ``jax.profiler.start_trace`` directory
    (one ``.xplane.pb`` a host), its planes put together."""
    sessions = sorted(glob.glob(os.path.join(trace_dir, "plugins",
                                             "profile", "*")))
    if not sessions:
        return {"planes": []}
    planes: List[dict] = []
    for path in sorted(glob.glob(os.path.join(sessions[-1],
                                              "*.xplane.pb"))):
        planes += load_xplane(path)["planes"]
    return {"planes": planes}


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def length(merged: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(merged: List[Interval], holes: List[Interval]
             ) -> List[Interval]:
    """``merged`` less ``holes`` (both unions)."""
    out: List[Interval] = []
    for s, e in merged:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: List[list]) -> List[float]:
    """Each event's duration less what its direct children cover (events
    of one line nest like calls; order of ``events`` is kept)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [float(ev[2]) for ev in events]
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            pe = events[parent][1] + events[parent][2]
            selfs[parent] -= max(0.0, min(e, pe) - s)
        stack.append(i)
    return [max(0.0, v) for v in selfs]


# -- the trace's parts --------------------------------------------------------

def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIX)]


def op_events(plane: dict) -> List[list]:
    """The operations of one device: its ``XLA Ops`` line(s)."""
    return [ev for line in plane["lines"] if line["name"] == OP_LINE
            for ev in line["events"]]


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def find_event(trace: dict, name: str) -> Optional[list]:
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0] == name:
                    return ev
    return None


# -- the reduction ------------------------------------------------------------

def reduce(trace: dict, host_spans: Optional[List[dict]] = None,
           sync_name: Optional[str] = None,
           sync_host_s: Optional[float] = None,
           hlo_texts: Iterable[str] = ()) -> Optional[dict]:
    """Everything the device readers use, or None where no operation ran
    on a device.  The traced window runs from the first operation's start
    to the last one's end over all devices; per-device numbers are
    averaged over the devices."""
    per_device = [(p["name"], op_events(p)) for p in device_planes(trace)]
    per_device = [(n, evs) for n, evs in per_device if evs]
    if not per_device:
        return None
    lo = min(ev[1] for _n, evs in per_device for ev in evs)
    hi = max(ev[1] + ev[2] for _n, evs in per_device for ev in evs)
    n_dev = len(per_device)

    busy_ns = 0.0
    coll_ns = coll_exposed_ns = 0.0
    op_self: Dict[str, float] = {}
    gaps0: List[Interval] = []
    for i, (_name, evs) in enumerate(per_device):
        busy = union((ev[1], ev[1] + ev[2]) for ev in evs)
        busy_ns += length(busy)
        if i == 0:
            gaps0 = subtract([(lo, hi)], busy)
        selfs = self_times(evs)
        for ev, st in zip(evs, selfs):
            op_self[ev[0]] = op_self.get(ev[0], 0.0) + st
        coll = union((ev[1], ev[1] + ev[2]) for ev in evs
                     if is_collective(ev[0]))
        # Leaf operations only: a loop's own event covers everything.
        other = union((ev[1], ev[1] + ev[2]) for ev, st in zip(evs, selfs)
                      if not is_collective(ev[0]) and st >= 0.5 * ev[2])
        coll_ns += length(coll)
        coll_exposed_ns += length(subtract(coll, other))

    ops = sorted(op_self.items(), key=lambda kv: -kv[1])
    reduced = {
        "devices": n_dev,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "collective_s": coll_ns / n_dev / 1e9,
        "collective_exposed_s": coll_exposed_ns / n_dev / 1e9,
        # whole instruction -> self seconds a device
        "ops": {name: ns / n_dev / 1e9 for name, ns in ops},
    }
    conv_keys = conv_keys_from_hlo(hlo_texts)
    reduced["conv_ops"] = {name for name in op_self
                           if op_key(name) in conv_keys}
    gaps = label_gaps(gaps0, lo, host_spans, trace, sync_name, sync_host_s)
    reduced["breakdown"] = {
        "device_ops": [[short_label(name), ns / n_dev / 1e9]
                       for name, ns in ops[:TOP]],
        "idle_gaps": gaps[:TOP],
    }
    return reduced


def label_gaps(gaps: List[Interval], lo: float,
               host_spans: Optional[List[dict]], trace: dict,
               sync_name: Optional[str], sync_host_s: Optional[float]
               ) -> List[list]:
    """Idle gaps of the first device, summed under the host span that
    covers most of each: ``[[label, seconds], ...]``, longest first.  A
    gap no span covers is ``host:unattributed``; gaps under ``MIN_GAP_NS``
    (the device's own turn-around between operations) are summed under
    ``device:between_ops``."""
    sync = find_event(trace, sync_name) if sync_name else None
    spans: List[Tuple[float, float, str]] = []
    if sync is not None and host_spans and sync_host_s is not None:
        # host seconds -> trace ns: the annotation's start on both clocks.
        for sp in host_spans:
            if sp.get("overlap"):
                continue
            s = sync[1] + (sp["start_s"] - sync_host_s) * 1e9
            spans.append((s, s + sp["dur_s"] * 1e9, sp["phase"]))
    totals: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < MIN_GAP_NS:
            label = "device:between_ops"
        else:
            best, best_cover = "host:unattributed", 0.0
            for ss, se, phase in spans:
                cover = min(e, se) - max(s, ss)
                if cover > best_cover:
                    best, best_cover = "host:" + phase, cover
            label = best
        totals[label] = totals.get(label, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])]


_CALLS = re.compile(r"calls=%([\w.\-]+)")
_SHAPE = re.compile(r"\b(?:bf16|f32|f16|f64|u8|s8|u16|s16|u32|s32|pred)"
                    r"\[[\d,]*\]")


def op_key(text: str) -> Tuple[str, Optional[str]]:
    """What names an operation the same way in a trace event (the whole
    HLO instruction, operands with their shapes) and in the compiled
    module's text (operands by name): the instruction's name and result
    type, and the computation a fusion calls.  The result type holds the
    batch, so the programs of a cell's two shapes do not collide."""
    text = text.strip()
    calls = _CALLS.search(text)
    head = text.split(" fusion(")[0] if calls else text.split("(")[0]
    return head, calls.group(1) if calls else None


def conv_keys_from_hlo(hlo_texts: Iterable[str]) -> set:
    """The keys (:func:`op_key`) of the instructions that hold a
    convolution, from the compiled modules' text: a bare ``convolution``,
    or a fusion whose computation holds one (or calls one that does).
    The profiler gives a TPU operation no category, and a fusion's kind
    does not tell either (``kOutput`` also takes the pooling windows), so
    the compiled text is the one place that says."""
    keys = set()
    for text in hlo_texts:
        comps: Dict[str, List[str]] = {}
        cur = None
        for line in text.splitlines():
            if line and not line[0].isspace():
                m = re.match(r"(?:ENTRY )?%?([\w.\-]+) ", line)
                cur = m.group(1) if m and line.rstrip().endswith("{") else None
                if cur:
                    comps[cur] = []
            elif cur:
                comps[cur].append(line)
        has_conv = {c: any(" convolution(" in ln for ln in lines)
                    for c, lines in comps.items()}
        changed = True
        while changed:  # a computation that calls one that holds one
            changed = False
            for c, lines in comps.items():
                if not has_conv[c] and any(
                        has_conv.get(m.group(1)) for ln in lines
                        for m in [_CALLS.search(ln)] if m):
                    has_conv[c] = changed = True
        for lines in comps.values():
            for ln in lines:
                m = _CALLS.search(ln)
                if " convolution(" in ln or (" fusion(" in ln and m
                                             and has_conv.get(m.group(1))):
                    keys.add(op_key(ln.split(", metadata=")[0]))
    return keys


def conv_seconds(reduced: dict) -> float:
    """Self time a device of the operations that hold a convolution
    (``reduce(..., hlo_texts=...)`` marked them)."""
    return sum(secs for name, secs in reduced["ops"].items()
               if name in reduced["conv_ops"])


def result_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """An instruction's result array(s) as ``(type with shape, dimensions)``,
    from its text as a trace event or a line of the compiled module
    carries it."""
    head = op_key(text)[0].split(" = ", 1)[-1]
    return [(shape, tuple(int(d) for d in
                          shape[shape.index("[") + 1:-1].split(",") if d))
            for shape in _SHAPE.findall(head)]


def table_seconds(reduced: dict, rows: int, row_elems: int) -> float:
    """Self time a device of the operations that write a whole copy of a
    table resident in HBM (a relayout, a reshape that moves bytes): those
    whose result has the table's ``rows`` as its first dimension and at
    least ``row_elems`` elements a row.  An operation that only reads the
    table (the row gather) writes a batch, and no activation has the
    table's row count."""
    def whole_table(dims: Tuple[int, ...]) -> bool:
        return (len(dims) > 1 and dims[0] == rows
                and math.prod(dims[1:]) >= row_elems)

    return sum(secs for name, secs in reduced["ops"].items()
               if any(whole_table(dims)
                      for _shape, dims in result_shapes(name)))


def short_label(text: str) -> str:
    """An operation's name for the breakdown: ``fusion.506 kOutput
    bf16[3072,8,8,512]`` from the whole instruction — its name, a
    fusion's kind, and the largest of its result's shapes."""
    name = text.split(" = ")[0].lstrip("%").strip()
    kind = re.search(r"kind=(\w+)", text)
    shapes = result_shapes(text)
    parts = [name] + ([kind.group(1)] if kind else [])
    if shapes:
        parts.append(max(shapes, key=lambda sd: math.prod(sd[1]))[0])
    return " ".join(parts)
