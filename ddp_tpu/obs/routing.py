"""Routing counters of an expert model, host side.

The device side counts inside the step: per expert layer the model's
state pytree carries ``assignments`` (to each expert held here),
``dropped`` (assignments that found no room), ``live_tiles`` (row tiles
that held a row: the tiles the experts' products visited) and
``buffer_tiles`` (row tiles the buffer offered), integer and cumulative
(models/moe.py; train/step.py sums integer state over replicas).
The Trainer hands a copy of those leaves to :meth:`RoutingCounters.update`
where it flushes an epoch's losses, so no step pays a device read.

Exported through the run's registry (obs/registry.py):

    ddp_moe_assignments_total{layer,expert}   counter
    ddp_moe_dropped_total{layer}              counter
    ddp_moe_load_max_over_mean{layer}         gauge: the busiest held
        expert's assignments over the mean, since the run began
    ddp_moe_live_tiles_total{layer}           counter
    ddp_moe_live_tile_share{layer}            gauge: live tiles over the
        tiles the buffer offered (a step's buffer on every replica), since
        the run began: the share of the buffer the products multiply

``expert`` is the index among the experts held here (the router's id is
``experts_held[0]`` more).  ``python -m ddp_tpu.obs --prom FILE`` prints
them from a run's ``.prom`` file, and below them the losses of a model
with more than one prediction depth (``ddp_lm_loss{depth}``, a gauge the
Trainer sets where it flushes losses: :func:`format_lm_loss`).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

FAMILIES = ("ddp_moe_assignments_total", "ddp_moe_dropped_total",
            "ddp_moe_load_max_over_mean", "ddp_moe_live_tiles_total",
            "ddp_moe_live_tile_share")
# The scalar counters of a layer's state beside ``assignments``; a state
# written before PR 38 has the first alone.
_SCALARS = ("dropped", "live_tiles", "buffer_tiles")


def _u32(x) -> np.ndarray:
    return np.asarray(x).astype(np.uint32)


class RoutingCounters:
    """Cumulative device counts in, increments out.  The device's
    counters are 32 bits wide and wrap; the difference of two readings
    modulo 2**32 is right as long as one epoch adds less than that."""

    def __init__(self, registry=None, baseline: Optional[dict] = None):
        self.totals: Dict[str, dict] = {}
        self._last = baseline or {}
        self._assign = self._dropped = self._load = None
        self._live = self._share = None
        if registry is not None:
            self._assign = registry.counter(
                FAMILIES[0], "Assignments routed to an expert held here",
                ("layer", "expert"))
            self._dropped = registry.counter(
                FAMILIES[1], "Assignments to a held expert that found no "
                "room (must read 0)", ("layer",))
            self._load = registry.gauge(
                FAMILIES[2], "Busiest held expert's assignments over the "
                "mean, since the run began", ("layer",))
            self._live = registry.counter(
                FAMILIES[3], "Row tiles that held a row: the tiles the "
                "experts' products visited", ("layer",))
            self._share = registry.gauge(
                FAMILIES[4], "Live row tiles over the tiles the buffer "
                "offered, since the run began", ("layer",))

    def update(self, counters: dict) -> None:
        """``counters``: layer -> {"assignments": int[E], "dropped": int,
        "live_tiles": int, "buffer_tiles": int}, as read from the
        device."""
        for layer, now in counters.items():
            last = self._last.get(layer, {})
            d_assign = (_u32(now["assignments"])
                        - _u32(last.get("assignments", 0))).astype(np.int64)
            delta = {name: int((_u32(now.get(name, 0))
                                - _u32(last.get(name, 0))).astype(np.int64))
                     for name in _SCALARS}
            self._last[layer] = now
            tot = self.totals.setdefault(
                layer, {"assignments": np.zeros_like(d_assign),
                        **dict.fromkeys(_SCALARS, 0)})
            tot["assignments"] = tot["assignments"] + d_assign
            for name in _SCALARS:
                tot[name] += delta[name]
            if self._assign is not None:
                for j, n in enumerate(d_assign):
                    self._assign.labels(layer=layer, expert=str(j)).inc(
                        float(n))
                self._dropped.labels(layer=layer).inc(
                    float(delta["dropped"]))
                self._load.labels(layer=layer).set(
                    load_max_over_mean(tot["assignments"]))
                self._live.labels(layer=layer).inc(
                    float(delta["live_tiles"]))
                self._share.labels(layer=layer).set(live_tile_share(tot))


def live_tile_share(totals: dict) -> float:
    """Live tiles over the tiles offered; 0.0 where none was offered."""
    offered = totals["buffer_tiles"]
    return totals["live_tiles"] / offered if offered else 0.0


def load_max_over_mean(assignments) -> float:
    a = np.asarray(assignments, np.float64)
    return float(a.max() / a.mean()) if a.size and a.sum() > 0 else 0.0


def format_routing(families: dict) -> str:
    """The routing counters of a parsed exposition
    (``registry.parse_exposition``) as a table, a layer a line."""
    def samples(name):
        return {dict(labels).get("layer"): {} for (_n, labels) in
                families.get(name, {}).get("samples", {})}

    layers = sorted(samples(FAMILIES[0]))
    if not layers:
        return "no routing counters (ddp_moe_*) in this exposition"

    def of_layer(family, layer):
        return sum(v for (_n, labels), v in
                   families.get(family, {}).get("samples", {}).items()
                   if dict(labels)["layer"] == layer)

    lines = [f"{'layer':<10} {'assigned':>10} {'dropped':>8} "
             f"{'max/mean':>8} {'live tiles':>10} {'of buffer':>9}  "
             "assignments by expert held"]
    for layer in layers:
        by_expert = sorted(
            (int(dict(labels)["expert"]), int(v)) for (_n, labels), v in
            families[FAMILIES[0]]["samples"].items()
            if dict(labels)["layer"] == layer)
        counts = [v for _j, v in by_expert]
        lines.append(f"{layer:<10} {sum(counts):>10} "
                     f"{int(of_layer(FAMILIES[1], layer)):>8} "
                     f"{load_max_over_mean(counts):>8.2f} "
                     f"{int(of_layer(FAMILIES[3], layer)):>10} "
                     f"{of_layer(FAMILIES[4], layer):>9.1%}  "
                     + " ".join(map(str, counts)))
    return "\n".join(lines)


def format_lm_loss(families: dict) -> str:
    """``ddp_lm_loss{depth}`` of a parsed exposition, a depth a line;
    empty where the run's model had one depth."""
    by_depth = sorted((int(dict(labels)["depth"]), v) for (_n, labels), v in
                      families.get("ddp_lm_loss", {}).get("samples",
                                                          {}).items())
    return "\n".join(f"ddp_lm_loss depth {d}: {v:.5f}" for d, v in by_depth)
