#!/usr/bin/env python
"""Chaos campaign: run the DDP_TPU_FAULT drill matrix under the run
supervisor (``python -m ddp_tpu.supervise``) and score every drill on
the only question that matters — did the run finish with ZERO data loss
and no operator input?

Per drill the scorecard (``CHAOS_r01.json``-style, ``--out``) records:
restarts the supervisor spent (by classified reason, read back from the
supervisor's own ``.prom`` exposition), death-to-relaunch recovery time
(the supervisor's recovery histogram sum), wall time, final-state
BIT-PARITY against an undisturbed control run of the same config — the
resumed trajectory must land on the identical bytes, anything else is
silent data loss — and the flight-recorder bundle: every abnormal exit
must leave a schema-valid ``postmortem.json`` (obs/blackbox.py) in the
drill's workdir, or the drill FAILs even if the data survived.

The matrix (one entry per injected failure mode the resilience layer
claims to survive):
  sigterm_step     mid-epoch preemption -> exit 75 -> immediate resume
  watchdog_stall   wedged rank -> watchdog exit 124 -> backoff resume
  flip_param_bit   SDC on one replica -> drift abort (exit 1) -> resume
                   from the last clean snapshot
  poison_batch     corrupted input shard -> guard spike_abort (exit 1)
                   -> resume from the last clean snapshot
  torn_data_state  preempt, then tear the emergency checkpoint's resume
                   record on disk -> degraded epoch-boundary resume
  local_wipe       preempt with ``--mirror`` on, then rm -rf the ENTIRE
                   local checkpoint directory -> supervised resume must
                   restore from the remote mirror tier alone
  kill_stage       a (2,1,2) PIPELINED run is preempted mid-schedule and
                   a whole stage plane stays dead at relaunch (the probe
                   sees 2 devices) -> the supervisor's stage-first
                   shrink re-cuts the pipeline to (2,1,1) and the
                   canonical checkpoint restores onto the collapsed 2-D
                   mesh bit-identically

Four control configs: A (64-sample synthetic, 2 steps/epoch — fast)
for most drills; B (320-sample, 10 steps/epoch, save_every=2) for
``poison_batch`` so the loss-health guard has its minimum 8-step
history before the poisoned step AND no checkpoint lands between the
poison and the abort (epoch 1 never saves under save_every=2; the
deferred loss flush kills the run at the top of epoch 2, before its
save) — the relaunch therefore resumes from clean bytes; C (A minus
``--mesh_shape``) for ``flip_param_bit``, because the drift audit
refuses the tensor-parallel plan that any ``--mesh_shape`` builds; P
(32-sample, ``--mesh_shape 2,1,2 --grad_accum 2``, 2 optimizer
steps/epoch) for ``kill_stage`` — the pipelined config whose staged
step is bit-compatible with the plain (2,1) grad-accum step it
collapses onto after the shrink.

CI runs the ``sigterm_step,watchdog_stall`` subset as the supervisor
smoke (``bench.py --chaos`` is the porcelain); the full matrix is the
release drill.  Exits nonzero when any drill fails.

Usage:
    python tools/chaos_campaign.py [--out CHAOS_r01.json]
                                   [--drills sigterm_step,...]
                                   [--workdir DIR] [--keep]
                                   [--ndev 8] [--timeout 900]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

SCHEMA = "chaos_campaign/1"

# Config A: the standard 6-step CPU drill (2 steps/epoch on 8 devices).
# Config B: 10 steps/epoch so the guard's 8-step minimum history exists
# by the poisoned step, save_every=2 so no save lands mid-divergence.
# Config C: A without --mesh_shape — the drift audit refuses any tensor-
# parallel plan (even the trivial m=1 one --mesh_shape always builds),
# so the SDC drill runs on the plain all-devices DP mesh instead.
_CONFIGS = {
    "A": ["3", "1", "--batch_size", "4", "--synthetic", "--model",
          "deepnn", "--lr", "0.05", "--synthetic_size", "64",
          "--seed", "3", "--mesh_shape", "8,1"],
    "B": ["3", "2", "--batch_size", "4", "--synthetic", "--model",
          "deepnn", "--lr", "0.05", "--synthetic_size", "320",
          "--seed", "3", "--mesh_shape", "8,1"],
    "C": ["3", "1", "--batch_size", "4", "--synthetic", "--model",
          "deepnn", "--lr", "0.05", "--synthetic_size", "64",
          "--seed", "3"],
    # Config P: the pipelined drill mesh — 2 data replicas x 2 stages on
    # 4 of the virtual devices, grad_accum=2 so the 1F1B schedule has
    # micro-batches to overlap, 2 optimizer steps/epoch (32/(4*2*2)).
    "P": ["3", "1", "--batch_size", "4", "--synthetic", "--model",
          "deepnn", "--lr", "0.05", "--synthetic_size", "32",
          "--seed", "3", "--grad_accum", "2", "--mesh_shape", "2,1,2"],
}

# name -> (config, DDP_TPU_FAULT spec or None for two-stage, extra argv)
_DRILLS = {
    "sigterm_step": ("A", "sigterm@step=2", []),
    "watchdog_stall": ("A", "stall@epoch=1,secs=600",
                       ["--watchdog_secs", "15"]),
    "flip_param_bit": ("C", "flip_param_bit@step=2,replica=1",
                       ["--drift_audit_every", "1",
                        "--drift_action", "abort"]),
    "poison_batch": ("B", "poison_batch@step=12,scale=1e4",
                     ["--guard_spike_factor", "4",
                      "--guard_action", "abort"]),
    "torn_data_state": ("A", None, []),  # two-stage, see _run_torn
    "local_wipe": ("A", None, []),       # two-stage, see _run_local_wipe
    "kill_stage": ("P", None, []),       # custom probe, see _run_kill_stage
}


def _env(ndev: int) -> dict:
    env = dict(os.environ)
    env.pop("DDP_TPU_FAULT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # Supervisor device probe: trust this count instead of paying a jax
    # import per relaunch (the campaign's mesh never actually shrinks).
    env["DDP_TPU_SUPERVISE_DEVICES"] = str(ndev)
    return env


def _child_argv(config: str, extra: List[str], workdir: str,
                snapshot: Optional[str] = None) -> List[str]:
    return ([os.path.join(_REPO, "multigpu.py")] + _CONFIGS[config][:2]
            + _CONFIGS[config][2:] + extra
            + ["--snapshot_path", snapshot or os.path.join(workdir, "ck.npz"),
               "--metrics_path", os.path.join(workdir, "metrics.jsonl")])


def _run(argv: List[str], env: dict, timeout: float,
         tag: str) -> Tuple[int, float]:
    print(f"[chaos] {tag}: {' '.join(argv)}", flush=True)
    t0 = time.monotonic()
    proc = subprocess.run(argv, env=env, timeout=timeout)
    return proc.returncode, time.monotonic() - t0


def _supervised(child: List[str], env: dict, timeout: float, tag: str,
                fault: Optional[str] = None) -> Tuple[int, float]:
    env = dict(env)
    if fault:
        env["DDP_TPU_FAULT"] = fault
    argv = [sys.executable, "-m", "ddp_tpu.supervise",
            "--backoff_base", "0.2", "--backoff_max", "5",
            "--seed", "0", "--"] + child
    return _run(argv, env, timeout, tag)


def _final_ckpt(snapshot: str):
    """The newest verifiable checkpoint of a finished run (the bytes the
    bit-parity verdict is about)."""
    from ddp_tpu.resilience.lineage import latest_verifiable
    loaded = latest_verifiable(snapshot)
    if loaded is None:
        return None
    return loaded[0]


def _params_equal(a, b) -> bool:
    import jax
    import numpy as np
    if a is None or b is None:
        return False
    la = jax.tree_util.tree_leaves_with_path(a.params)
    lb = jax.tree_util.tree_leaves_with_path(b.params)
    if len(la) != len(lb):
        return False
    for (pa, x), (pb, y) in zip(la, lb):
        if pa != pb or not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return int(a.step) == int(b.step)


def _supervisor_stats(workdir: str) -> Dict[str, object]:
    """Restarts by reason + recovery seconds, read back from the
    supervisor's own end-of-run exposition — the scorecard consumes the
    same telemetry an operator's scrape would."""
    from ddp_tpu.obs.registry import parse_exposition
    prom = os.path.join(workdir, "metrics.jsonl.supervisor.prom")
    out: Dict[str, object] = {"restarts": 0, "restart_reasons": {},
                              "recovery_seconds_sum": 0.0}
    try:
        with open(prom) as f:
            fams = parse_exposition(f.read())
    except (OSError, ValueError):
        return out
    reasons: Dict[str, int] = {}
    fam = fams.get("ddp_supervisor_restarts_total")
    if fam:
        for (sname, labels), v in fam["samples"].items():
            if sname == "ddp_supervisor_restarts_total":
                reasons[dict(labels).get("reason", "?")] = int(v)
    out["restart_reasons"] = reasons
    out["restarts"] = sum(reasons.values())
    hist = fams.get("ddp_supervisor_recovery_seconds")
    if hist:
        for (sname, _labels), v in hist["samples"].items():
            if sname == "ddp_supervisor_recovery_seconds_sum":
                out["recovery_seconds_sum"] = round(float(v), 3)
    return out


def _postmortem_check(workdir: str) -> dict:
    """Every abnormal exit must leave a schema-valid flight-recorder
    bundle next to the metrics JSONL (obs/blackbox.py) — the drill's
    autopsy.  Scored per drill: a campaign that survives the fault but
    loses the postmortem has lost the artifact trail the supervisor's
    ledger and diagnosis.json link into."""
    from ddp_tpu.obs.blackbox import validate_postmortem
    path = os.path.join(workdir, "postmortem.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return {"present": os.path.exists(path), "valid": False,
                "error": str(e)}
    try:
        validate_postmortem(doc)
    except ValueError as e:
        return {"present": True, "valid": False, "error": str(e)}
    return {"present": True, "valid": True, "reason": doc["reason"],
            "exit_status": doc["exit_status"]}


def _run_control(config: str, root: str, env: dict,
                 timeout: float) -> dict:
    workdir = os.path.join(root, f"control_{config}")
    os.makedirs(workdir, exist_ok=True)
    child = [sys.executable] + _child_argv(config, [], workdir)
    rc, wall = _run(child, env, timeout, f"control {config}")
    if rc != 0:
        raise RuntimeError(f"control {config} failed with exit {rc} — "
                           "the campaign has no baseline to score against")
    return {"config": config, "workdir": workdir,
            "wall_s": round(wall, 1)}


def _run_torn(root: str, env: dict, timeout: float) -> dict:
    """Two-stage drill (``torn_data_state`` has no env-fault wiring — it
    damages bytes already on disk): (1) a SOLO run preempted at the epoch
    boundary leaves an emergency checkpoint; (2) its resume-position
    record is torn in place; (3) the supervised relaunch must degrade to
    the epoch-boundary resume with a warning and still finish."""
    from ddp_tpu.resilience import faults
    from ddp_tpu.resilience.lineage import _resolve_head
    workdir = os.path.join(root, "torn_data_state")
    os.makedirs(workdir, exist_ok=True)
    child = _child_argv("A", [], workdir)
    stage_env = dict(env)
    stage_env["DDP_TPU_FAULT"] = "sigterm@epoch=1"
    rc, wall1 = _run([sys.executable] + child, stage_env, timeout,
                     "torn_data_state stage 1 (preempt)")
    if rc != 75:
        return {"workdir": workdir, "supervisor_exit": rc,
                "error": f"stage-1 preemption exited {rc}, wanted 75"}
    faults.torn_data_state(
        _resolve_head(os.path.join(workdir, "ck.npz")))
    rc, wall2 = _supervised(child + ["--resume"], env, timeout,
                            "torn_data_state stage 2 (resume)")
    return {"workdir": workdir, "supervisor_exit": rc,
            "wall_s": round(wall1 + wall2, 1)}


def _run_local_wipe(root: str, env: dict, timeout: float) -> dict:
    """Two-stage drill for TOTAL local-disk loss (drill six): (1) a SOLO
    mirrored run preempted mid-epoch drains its remote copy before exit
    75; (2) the entire local checkpoint DIRECTORY is removed — head,
    rotated generations, manifest, everything; (3) the supervised
    relaunch finds no local tier at all and must restore from the
    ``DirStore`` mirror alone, then finish bit-identical to the control.
    The checkpoint lives in its own subdirectory (not the workdir) so
    the wipe is a true ``rm -rf`` of the durability tier without taking
    the metrics/prom files the scorecard reads with it."""
    workdir = os.path.join(root, "local_wipe")
    ckdir = os.path.join(workdir, "ckpt")
    os.makedirs(ckdir, exist_ok=True)
    snapshot = os.path.join(ckdir, "ck.npz")
    mirror = os.path.join(workdir, "mirror")
    child = _child_argv("A", ["--mirror", mirror], workdir,
                        snapshot=snapshot)
    stage_env = dict(env)
    stage_env["DDP_TPU_FAULT"] = "sigterm@step=4"
    rc, wall1 = _run([sys.executable] + child, stage_env, timeout,
                     "local_wipe stage 1 (preempt, mirror draining)")
    if rc != 75:
        return {"workdir": workdir, "supervisor_exit": rc,
                "snapshot": snapshot,
                "fault": "sigterm@step=4 + rm -rf local ckpt dir",
                "error": f"stage-1 preemption exited {rc}, wanted 75"}
    shutil.rmtree(ckdir)  # total local-disk loss: no tier-1 bytes remain
    print(f"[chaos] local_wipe: removed {ckdir} (local tier gone; "
          f"mirror at {mirror} is the only copy)", flush=True)
    rc, wall2 = _supervised(child + ["--resume"], env, timeout,
                            "local_wipe stage 2 (resume from mirror)")
    return {"workdir": workdir, "supervisor_exit": rc,
            "snapshot": snapshot,
            "fault": "sigterm@step=4 + rm -rf local ckpt dir",
            "wall_s": round(wall1 + wall2, 1)}


def _run_kill_stage(root: str, env: dict, timeout: float) -> dict:
    """Stage-loss drill: the (2,1,2) pipelined run is SIGTERMed
    mid-schedule (exit 75, emergency checkpoint on disk), and when the
    supervisor relaunches, its device probe reports only 2 live devices
    — one whole stage plane gone for good.  The stage-first shrink
    policy must give up the stage axis ((2,1,2) -> (2,1,1), which the
    mesh layer collapses to the plain 2-D (2,1)) rather than halving the
    data axis, and the canonical checkpoint must restore onto the re-cut
    mesh and finish BIT-IDENTICAL to the undisturbed (2,1,2) control —
    the (d,m,s) == (d,m,1) parity the pp test suite pins, exercised here
    across a real kill/restart boundary."""
    workdir = os.path.join(root, "kill_stage")
    os.makedirs(workdir, exist_ok=True)
    child = _child_argv("P", [], workdir)
    drill_env = dict(env)
    # The probe seam: XLA still carves the full virtual-device set, but
    # the supervisor believes only one (d, m) plane survived.
    drill_env["DDP_TPU_SUPERVISE_DEVICES"] = "2"
    rc, wall = _supervised(child, drill_env, timeout, "kill_stage",
                           fault="sigterm@step=2")
    return {"workdir": workdir, "supervisor_exit": rc,
            "fault": "sigterm@step=2 + stage plane dead at relaunch",
            "wall_s": round(wall, 1)}


def run_campaign(drills: List[str], root: str, env: dict,
                 timeout: float) -> dict:
    configs = sorted({_DRILLS[d][0] for d in drills})
    controls = {c: _run_control(c, root, env, timeout) for c in configs}
    results: Dict[str, dict] = {}
    for name in drills:
        config, fault, extra = _DRILLS[name]
        if name == "torn_data_state":
            res = _run_torn(root, env, timeout)
        elif name == "local_wipe":
            res = _run_local_wipe(root, env, timeout)
        elif name == "kill_stage":
            res = _run_kill_stage(root, env, timeout)
        else:
            workdir = os.path.join(root, name)
            os.makedirs(workdir, exist_ok=True)
            child = _child_argv(config, extra, workdir)
            rc, wall = _supervised(child, env, timeout, name, fault=fault)
            res = {"workdir": workdir, "supervisor_exit": rc,
                   "wall_s": round(wall, 1)}
        res.setdefault(
            "fault", fault or "sigterm@epoch=1 + torn data_state record")
        res["control"] = config
        res.update(_supervisor_stats(res["workdir"]))
        snap = res.pop("snapshot", None) or os.path.join(
            res["workdir"], "ck.npz")
        bit = _params_equal(
            _final_ckpt(snap),
            _final_ckpt(os.path.join(controls[config]["workdir"],
                                     "ck.npz")))
        res["bit_identical"] = bit
        res["zero_data_loss"] = bit and res["supervisor_exit"] == 0
        # Every drill kills the child abnormally at least once, so a
        # schema-valid postmortem.json must be in the workdir (the last
        # death's bundle survives the successful relaunch untouched).
        res["postmortem"] = _postmortem_check(res["workdir"])
        res["pass"] = res["zero_data_loss"] and res["postmortem"]["valid"]
        res.pop("workdir")
        results[name] = res
        print(f"[chaos] {name}: exit={res['supervisor_exit']} "
              f"restarts={res['restarts']} {res['restart_reasons']} "
              f"recover={res['recovery_seconds_sum']}s "
              f"bit_identical={bit} "
              f"postmortem={res['postmortem'].get('reason', 'MISSING')}"
              f" -> {'PASS' if res['pass'] else 'FAIL'}", flush=True)
    for c in controls.values():
        c.pop("workdir")
    ok = all(r["pass"] for r in results.values())
    return {"schema": SCHEMA, "generated_by": "tools/chaos_campaign.py",
            "controls": controls, "drills": results,
            "verdict": "PASS" if ok else "FAIL"}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python tools/chaos_campaign.py",
        description=__doc__.splitlines()[0])
    p.add_argument("--out", default="CHAOS_r01.json",
                   help="Scorecard path (default CHAOS_r01.json)")
    p.add_argument("--drills", default=",".join(_DRILLS),
                   help="Comma-separated subset of the matrix (default: "
                        "all of " + ",".join(_DRILLS) + ")")
    p.add_argument("--workdir", default=None,
                   help="Working directory (default: a fresh tempdir)")
    p.add_argument("--keep", action="store_true",
                   help="Keep the working directory (debugging)")
    p.add_argument("--ndev", type=int, default=8,
                   help="Virtual host devices per run (default 8)")
    p.add_argument("--timeout", type=float, default=900.0,
                   help="Per-subprocess timeout in seconds (default 900)")
    args = p.parse_args(argv)
    drills = [d.strip() for d in args.drills.split(",") if d.strip()]
    unknown = [d for d in drills if d not in _DRILLS]
    if unknown:
        p.error(f"unknown drill(s) {unknown}; matrix: "
                + ",".join(_DRILLS))
    root = args.workdir or tempfile.mkdtemp(prefix="chaos_campaign_")
    os.makedirs(root, exist_ok=True)
    env = _env(args.ndev)
    try:
        card = run_campaign(drills, root, env, args.timeout)
    finally:
        if not args.keep and args.workdir is None:
            shutil.rmtree(root, ignore_errors=True)
    with open(args.out, "w") as f:
        json.dump(card, f, indent=1)
    print(f"[chaos] scorecard written to {args.out}: {card['verdict']}",
          flush=True)
    return 0 if card["verdict"] == "PASS" else 1


if __name__ == "__main__":
    raise SystemExit(main())
