"""Trainer engine — reference ``Trainer`` (singlegpu.py:85-128 /
multigpu.py:74-119), re-expressed around one jitted SPMD ``train_step``.

What carries over verbatim: the epoch header print (multigpu.py:102), the
per-batch scheduler semantics (scheduler.step() inside _run_batch,
multigpu.py:98 — here the schedule is a pure function of the step counter
inside the jitted program), ``save_every``-gated checkpointing with the
rank-0 gate (multigpu.py:117-119), and the fixed default checkpoint path
``checkpoint.pt`` (multigpu.py:111).

What's new (sanctioned deviations): per-step loss is recorded (the reference
never logs loss — SURVEY.md §5 flags this as required for loss-curve
parity), the probe batch the reference materialises and throws away each
epoch just to print the batch size (multigpu.py:101) is not fetched, and
``resume=True`` restores params/BN stats/momentum/step/epoch from the
checkpoint (the load path the reference lacks, BASELINE.json config #5).

Resilience wiring (ddp_tpu/resilience/): checkpoint lineage with manifest +
fall-back restore (``keep_checkpoints``), the ``on_nan`` loss-health policy
folded into the deferred-loss flush, the coordinated emergency checkpoint
on preemption (``preemption``), and watchdog heartbeats (``watchdog``).
Invariant the save/flush ordering buys: an epoch's losses are flushed and
health-checked BEFORE that epoch's checkpoint is written, so under
``on_nan`` abort/restore every checkpoint on disk describes a state whose
losses were verified finite — which is what makes ``on_nan=restore``'s
reload-last-good sound.

Throughput: batches are host-prepared one step ahead and handed to the
device while the previous step is still running (JAX async dispatch) — the
TPU analogue of ``pin_memory=True`` + worker prefetch (singlegpu.py:177).
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracer import get_tracer
from ..ops.losses import LM_LOSS
from ..optim.sgd import SGDConfig, SGDState
from ..parallel import dist
from ..parallel.mesh import replicated_sharding
from ..utils.metrics import MetricsLogger
from .checkpoint import save_checkpoint
from .step import TrainState, init_train_state, make_train_step


def _stack_groups(batches, accum: int):
    """Group consecutive same-shaped host batches into ``[A, B, ...]``
    stacks of up to ``accum`` for the accumulation step.  The epoch's final
    ragged batch (different B) cannot join a full-batch stack, so a shape
    change flushes the current group — it becomes its own (smaller) final
    optimizer step, mirroring drop_last=False semantics."""
    group: list = []

    def flush():
        out = {k: np.stack([b[k] for b in group]) for k in group[0]}
        group.clear()
        return out

    for b in batches:
        if group and len(b["label"]) != len(group[0]["label"]):
            yield flush()
        group.append(b)
        if len(group) == accum:
            yield flush()
    if group:
        yield flush()


def _samples(label, stacked: bool) -> int:
    """Samples in a device batch, from its labels: rows (``[B]`` or, a
    token model's, ``[B,T]``), times the depth of a stack of micro-batches
    (``[A,B,...]``)."""
    return label.shape[0] * label.shape[1] if stacked else label.shape[0]


def _counters(model_state):
    """The part of a model's state that counts: its integer leaves (a
    token model's routing counters, ``{layer: {name: leaf}}``) with the
    dicts that hold them; empty for a classifier's running statistics."""
    if not isinstance(model_state, dict):
        return {}
    out = {}
    for key, sub in model_state.items():
        if isinstance(sub, dict):
            sub = _counters(sub)
            if sub:
                out[key] = sub
        elif jnp.issubdtype(getattr(sub, "dtype", jnp.float32),
                            jnp.integer):
            out[key] = sub
    return out


def _with_new_counters(loaded, like):
    """A checkpoint's model state with the counters it lacks: an integer
    leaf that ``like`` (this program's state) has and the file does not is
    a counter added since the file was written, and starts at zero
    (MIGRATING.md).  Nothing else is filled: any other difference still
    fails where the step is traced."""
    if not isinstance(loaded, dict) or not isinstance(like, dict):
        return loaded
    out = dict(loaded)
    for key, sub in like.items():
        if isinstance(sub, dict):
            if key in loaded:
                out[key] = _with_new_counters(loaded[key], sub)
        elif key not in loaded and jnp.issubdtype(
                getattr(sub, "dtype", jnp.float32), jnp.integer):
            out[key] = jnp.zeros_like(sub)
    return out


class Trainer:
    def __init__(self, model, train_loader, params, batch_stats, *,
                 mesh, lr_schedule: Callable,
                 sgd_config: SGDConfig = SGDConfig(),
                 save_every: int = 1,
                 snapshot_path: Optional[str] = "checkpoint.pt",
                 compute_dtype=None, seed: int = 0,
                 resume: bool = False,
                 metrics: Optional[MetricsLogger] = None,
                 device_augment: bool = False,
                 resident: bool = False,
                 shard_update: bool = False,
                 sync_bn: bool = False,
                 grad_accum: int = 1,
                 keep_checkpoints: int = 1,
                 on_nan: str = "abort",
                 watchdog=None,
                 preemption=None,
                 prefetch_depth: int = 2,
                 prefetch_workers: int = 4,
                 prefetch_stats=None,
                 tracer=None,
                 live=None,
                 tp_plan=None,
                 pp_plan=None,
                 pp_schedule: str = "1f1b",
                 ckpt_format: str = "gathered",
                 drift_audit_every: int = 0,
                 drift_action: str = "abort",
                 guard_window: int = 64,
                 guard_spike_factor: float = 0.0,
                 guard_action: str = "rollback",
                 registry=None,
                 mirror=None,
                 step_probe=None):
        # Telemetry (ddp_tpu/obs/): the span tracer every phase of the
        # epoch loop reports into (default: the process tracer — a
        # NullTracer unless cli.run installed a real one).  Known first,
        # so that ``trainer_init`` holds this whole body (state built or
        # restored, ZeRO's flat momentum, the resident table, the
        # builders) and JAX's preparation of every executable from here
        # on lands on the same timeline (obs/startup.py; nothing is
        # imported, registered or read under a NullTracer).
        self.tracer = tracer if tracer is not None else get_tracer()
        if self.tracer.enabled:
            from ..obs import startup
            startup.attach(self.tracer)
        init_span = self.tracer.span("trainer_init").__enter__()
        self.model = model
        self.train_loader = train_loader
        self.mesh = mesh
        self.save_every = save_every
        self.snapshot_path = snapshot_path
        self.gpu_id = dist.process_index()  # reference's rank handle
        self.lr_schedule = lr_schedule
        # Per-step loss/LR stream (absent in the reference — SURVEY.md §5
        # flags it as required for loss-curve parity measurement).
        self.metrics = metrics if self.gpu_id == 0 else None
        self.rng = jax.random.key(seed)
        self.loss_history: List[float] = []
        self._save_thread = None
        # Written only by the writer thread, read only after its join
        # (_join_pending_save) — synchronized by Thread.join, not a lock.
        # analysis: unlocked-ok(join-synchronized error slot)
        self._save_error: Optional[BaseException] = None
        # Deferred loss read (epoch pipelining): (epoch, start_step,
        # stacked device array) of the newest epoch whose losses have not
        # been host-read yet — flushed only after the NEXT epoch is
        # dispatched, so the D2H read overlaps device compute instead of
        # idling the chips at every epoch boundary.
        self._pending_losses = None
        # Resilience wiring (ddp_tpu/resilience/): lineage retention, loss
        # health policy, preemption guard, watchdog heartbeats.  Imported
        # lazily (package-cycle hygiene, same as the zero/resident paths).
        from ..resilience.guard import StepHealthGuard
        from ..resilience.lineage import (CheckpointLineage,
                                          latest_verifiable)
        self.lineage = (CheckpointLineage(snapshot_path,
                                          keep=keep_checkpoints)
                        if snapshot_path else None)
        # Durability tier 2 (resilience/store.py): ``mirror`` is a store
        # URI (or CheckpointStore) the committed lineage is asynchronously
        # mirrored to, and the restore tier --resume falls back to when
        # the whole local checkpoint directory is gone (preemption
        # reclaims the VM's disk).  The store is resolved up front (the
        # resume below may need it); the uploader thread itself starts
        # later in __init__.
        self._mirror = None
        self._mirror_store = None
        if mirror is not None and snapshot_path:
            from ..resilience.store import open_store
            self._mirror_store = open_store(mirror)
        self._health = StepHealthGuard(on_nan, window=guard_window,
                                       spike_factor=guard_spike_factor,
                                       spike_action=guard_action,
                                       metrics=self.metrics,
                                       registry=registry)
        self._health.on_lr_backoff = self._apply_lr_backoff
        self._watchdog = watchdog
        self._preemption = preemption
        self._seed = int(seed)
        # Mid-epoch resume position (data_state): the batch offset the
        # FIRST trained epoch starts at; 0 = the whole-epoch default.
        self._resume_offset = 0
        # (epoch, batch) positions the guard's rollback condemned — the
        # streaming loop drops them instead of re-ingesting poisoned data.
        self._skip_batches: set = set()
        # epoch -> (first global step, start batch offset): the map from a
        # flushed loss's global step back to its (epoch, batch) position.
        self._epoch_origin: dict = {}
        # Set by the streaming loop when a preemption notice stops it
        # mid-epoch: (epoch, next unconsumed batch offset).
        self._preempt_pending = None
        # Batch offset a _restore_last_good() landed on (mid-epoch
        # snapshots); train()'s loop consumes it for the replayed epoch.
        self._pending_resume_offset = 0
        if ckpt_format not in ("gathered", "sharded"):
            raise ValueError(
                f"ckpt_format must be 'gathered' or 'sharded', got "
                f"{ckpt_format!r}")
        self.ckpt_format = ckpt_format
        self.tp_plan = tp_plan
        # Pipeline parallelism (parallel/pp/): a StagePlan over the mesh's
        # third ``stage`` axis.  Checked before the restore below because
        # the checkpoint loader's placement policy depends on it.
        self.pp_plan = pp_plan
        self.pp_schedule = pp_schedule
        if pp_plan is not None:
            incompatible = [flag for flag, on in (
                ("--resident (per-stage programs dispatch per step)",
                 resident),
                ("--shard_update (ZeRO shards momentum over data; pp "
                 "shards it over stages)", shard_update),
                ("--sync_bn (stage programs do not exchange batch stats)",
                 sync_bn),
                ("--drift_audit_every (params are stage-partitioned, not "
                 "replicated over data)", bool(drift_audit_every)),
                ("--ckpt_format sharded (pipeline checkpoints stay "
                 "canonical/gathered so any (d,m,s) restores anywhere)",
                 ckpt_format == "sharded"),
            ) if on]
            if incompatible:
                raise ValueError(
                    "pipeline parallelism (stage axis s>1) is incompatible "
                    "with:\n" + "\n".join(f"  - {f}" for f in incompatible))
        if getattr(model, "tokens", None):
            not_wired = [why for why, on in (
                ("--resident: the table in HBM and its row gather hold "
                 "image rows (u8[N,24,128]); token rows stream through "
                 "the host loader", resident),
                ("--shard_update: the wiring (train/step.py:"
                 "make_step_wiring) picks the local-objective loss core "
                 "(train/zero.py:_make_local_grads), which pmeans every "
                 "state leaf; a token model's routing counters are "
                 "integers that must grow by the replicas' SUM "
                 "(_reduce_state_leaf, in the replicated core only)",
                 shard_update),
                ("a tensor- or pipeline-parallel plan: the wiring picks "
                 "the same local-objective core for a plan (and "
                 "parallel/pp/ its own), which takes one label a sample "
                 "and cannot carry the integer routing counters either",
                 tp_plan is not None or pp_plan is not None),
            ) if on]
            if not_wired:
                raise ValueError(
                    f"model {model.name!r} (token ids in, a loss a "
                    "position) is not wired for:\n"
                    + "\n".join(f"  - {w}" for w in not_wired))
        self.start_epoch = 0
        self.state = init_train_state(params, batch_stats)
        if resume and snapshot_path:
            # Lineage-aware restore: the head first, then each retained
            # snapshot — a torn head is a recoverable, logged event, not a
            # fatal one (fatal only when EVERY candidate is torn).  The
            # mesh-aware loader redistributes whatever format/mesh-shape
            # is on disk straight onto THIS run's mesh (ckpt_shard.py) —
            # a (2,4)-written sharded snapshot restores onto the (2,2)
            # pod that survived a preemption, leaf-streamed, never
            # gathered (elastic resume).
            loaded = latest_verifiable(snapshot_path,
                                       loader=self._ckpt_loader(),
                                       store=self._mirror_store)
            if loaded is not None:
                ckpt, used = loaded
                self.state = TrainState(
                    jax.tree_util.tree_map(jnp.asarray, ckpt.params),
                    jax.tree_util.tree_map(jnp.asarray, _with_new_counters(
                        ckpt.batch_stats, batch_stats)),
                    jax.tree_util.tree_map(jnp.asarray, ckpt.opt_state),
                    jnp.asarray(ckpt.step, jnp.int32))
                ds = ckpt.data_state
                if isinstance(ds, dict) and "epoch" in ds:
                    # data_state IS the position to resume from: an
                    # end-of-epoch save carries (epoch+1, 0) — identical
                    # to the legacy epoch+1 rule — and a mid-epoch
                    # emergency save carries (epoch, offset), which the
                    # prefetch engine fast-forwards to, making the
                    # resumed run bit-for-bit the uninterrupted one.
                    self.start_epoch = int(ds["epoch"])
                    self._resume_offset = int(ds.get("offset", 0))
                    folds = int(ds.get("rng_folds", 0))
                    # Reconstruct the step-RNG stream: each past restore
                    # folded its ordinal into the key, so replay the
                    # folds in order (0 folds = the pristine seed key —
                    # the common case, and the bit-for-bit one).
                    for i in range(1, folds + 1):
                        self.rng = jax.random.fold_in(self.rng, i)
                    self._health.restores = folds
                else:
                    # Pre-round-12 checkpoint: no data_state record.
                    # Warned once, never an error — the file resumes at
                    # the next epoch boundary exactly as it always did.
                    self.start_epoch = ckpt.epoch + 1
                    self._resume_offset = 0
                    print("WARNING: checkpoint has no data_state record "
                          "(written before round 12); resuming at the "
                          "next epoch boundary", file=sys.stderr)
                print(f"Resuming training from snapshot at Epoch "
                      f"{ckpt.epoch}"
                      + ("" if used == snapshot_path
                         else f" (fallback snapshot {used})"))
                if self._resume_offset:
                    print(f"Mid-epoch resume: fast-forwarding epoch "
                          f"{self.start_epoch} to batch offset "
                          f"{self._resume_offset}")
        # A token model's routing counters (integer leaves of its state:
        # obs/routing.py): read here once as the baseline, then a copy an
        # epoch where its losses are flushed.
        from ..obs.routing import RoutingCounters
        self._pending_counters: dict = {}
        self.routing = RoutingCounters(
            registry, baseline=jax.device_get(_counters(
                self.state.batch_stats)))
        # A model with more than one prediction depth: the loss core leaves
        # each depth's own loss in a float leaf of the model's state
        # (ops/losses.py:LM_LOSS), which rides with the counters.
        self.lm_loss: list = []
        self._depth_losses = (isinstance(batch_stats, dict)
                              and LM_LOSS in batch_stats)
        self._lm_gauge = None
        if self._depth_losses and registry is not None:
            self._lm_gauge = registry.gauge(
                "ddp_lm_loss", "The last flushed step's mean loss at each "
                "prediction depth (0: the next token)", ("depth",))
        # Host-side mirror of state.step: reading the device scalar would
        # block on the in-flight epoch (the exact stall the deferred loss
        # read removes), and the step count per epoch is host-known.
        self._host_step = int(self.state.step)
        # loss_history[i] corresponds to global step _history_base + i —
        # the offset an --on_nan restore needs to truncate the discarded
        # trajectory's entries at the rewind point.
        self._history_base = self._host_step
        self.shard_update = shard_update
        self.grad_accum = max(grad_accum, 1)
        # Tensor parallelism (parallel/tp/): a TPPlan on a 2-D (data x
        # model) mesh.  The state — fresh init or a checkpoint restore —
        # is re-sharded onto the plan's per-leaf specs here, which is
        # also what makes checkpoints PORTABLE across mesh shapes: a
        # gathered file stays canonical and a sharded set redistributes,
        # so restore re-shards onto whatever mesh this run has (for a
        # loader-restored state this device_put is already a no-op).
        if tp_plan is not None and pp_plan is None:
            from ..parallel.tp.plan import state_shardings
            self.state = jax.device_put(self.state,
                                        state_shardings(tp_plan, mesh))
        elif pp_plan is not None:
            # Stage placement (parallel/pp/schedule.py): each stage's
            # param/momentum subtrees land on that stage's (data x model)
            # submesh — tp-sharded within the stage when a plan composes.
            # Same portability contract as the tp re-shard above: restore
            # loads host/replicated, placement happens here, so any
            # checkpoint restores onto any (d, m, s).
            from ..parallel.pp.schedule import place_state
            self.state = place_state(self.state, mesh, pp_plan, tp_plan)
        # Streaming overlap engine knobs (data/prefetch.py): how many
        # batches may be in flight beyond the worker pool's hands, and how
        # many materialise/augment workers run.  depth=0 disables the
        # overlap (bit-identical stream — tests/test_prefetch.py pins it).
        # prefetch_stats (opt-in PrefetchStats) feeds the streaming-gap
        # attribution (bench.py --stream_attr).
        self.prefetch_depth = prefetch_depth
        self.prefetch_workers = prefetch_workers
        self.prefetch_stats = prefetch_stats
        # The rolling live-stats engine (rank 0, obs/live.py).
        self._live = live if self.gpu_id == 0 else None
        # Introspection probe (obs/inspect.py): one bounded callable per
        # optimizer step — the periodic .prom rewrite and the on-demand
        # profile trigger both hang off it.  Rank 0 only (the rank that
        # owns the registry and the inspect server); the callable itself
        # must never raise into the step loop — both probes swallow and
        # self-disable on error.
        self._step_probe = step_probe if self.gpu_id == 0 else None
        # Host-side epoch mirror for the /healthz snapshot (reading the
        # loop variable from another thread needs a stable home).
        self._host_epoch = self.start_epoch
        # Mirror uploader (rank 0 — the rank that commits lineage): one
        # background thread, fed after each commit, strictly off the
        # critical path.  Lineage manifests stamp each entry's mirror
        # status through state_of_epoch.
        if self._mirror_store is not None and self.gpu_id == 0:
            from ..resilience.store import MirrorUploader
            self._mirror = MirrorUploader(
                self._mirror_store, snapshot_path,
                keep=keep_checkpoints, registry=registry,
                tracer=self.tracer)
            if self.lineage is not None:
                self.lineage.mirror_state = self._mirror.state_of_epoch
        if shard_update:
            # ZeRO-1-style weight-update sharding (train/zero.py): momentum
            # lives as one flat array sharded over ``data`` (1/R per chip;
            # [m, L] over P(model, data) when composed with a tp_plan).
            # Checkpoints stay in the canonical per-leaf format either way.
            from .zero import init_opt_shard, pytree_to_opt_shard
            opt = (pytree_to_opt_shard(self.state.opt_state.momentum_buf,
                                       mesh, plan=tp_plan)
                   if self.start_epoch
                   else init_opt_shard(params, mesh, plan=tp_plan))
            self.state = TrainState(self.state.params, self.state.batch_stats,
                                    opt, self.state.step)
        self.resident = None
        kw = dict(compute_dtype=compute_dtype, device_augment=device_augment,
                  sync_bn=sync_bn, plan=tp_plan, accum=self.grad_accum > 1,
                  shard_update=shard_update)
        if resident:
            # Device-resident path: dataset uploaded once, whole epoch as a
            # single jitted lax.scan (train/epoch.py) — zero per-step host
            # involvement.  Augmentation necessarily runs on device.
            if getattr(train_loader, "augment", False):
                raise ValueError(
                    "resident=True never materialises host batches, so the "
                    "loader's host-side augmentation would be silently "
                    "skipped; build the TrainLoader with augment=False and "
                    "pass device_augment=True instead")
            from ..data.resident import ResidentData
            from .epoch import make_train_epoch as build
            with self.tracer.span(
                    "resident_upload",
                    nbytes=train_loader.dataset.images.nbytes):
                self.resident = ResidentData(train_loader.dataset, mesh)
        elif pp_plan is not None:
            # Pipeline path: per-stage jitted programs driven by a host
            # schedule (parallel/pp/schedule.py), wrapped to the shared
            # builder signature.
            from ..parallel.pp.schedule import make_pp_step

            def build(model, sgd_config, sched, mesh, *, compute_dtype,
                      device_augment, plan, sync_bn, shard_update, accum):
                del sync_bn, shard_update  # refused above
                del accum  # the schedule's micro-batches ARE the groups
                return make_pp_step(model.name, sgd_config, sched, mesh,
                                    pp_plan, compute_dtype=compute_dtype,
                                    device_augment=device_augment,
                                    tp_plan=plan, schedule=pp_schedule,
                                    tracer=self.tracer)
        else:
            build = make_train_step
        # The guard's lr_backoff action rebuilds the jitted program with
        # a scaled schedule — keep the builder and the unscaled schedule.
        self._base_lr_schedule = lr_schedule
        self._rebuild_step = lambda sched: build(model, sgd_config, sched,
                                                 mesh, **kw)
        if resident:
            self.train_epoch = self._rebuild_step(lr_schedule)
        else:
            self.train_step = self._rebuild_step(lr_schedule)
        if self.resident is not None and self._resume_offset:
            raise ValueError(
                "resident mode dispatches whole epochs and cannot "
                f"fast-forward to batch offset {self._resume_offset} of a "
                "mid-epoch checkpoint; resume this file with the "
                "streaming loop (drop --resident)")
        # Cross-replica SDC drift audit (resilience/drift.py): every K
        # steps, bit-level per-replica parameter fingerprints compared
        # over ``data`` with one tiny psum pair.
        self._drift = None
        if drift_audit_every:
            if tp_plan is not None:
                raise ValueError(
                    "--drift_audit_every needs replicated parameters (the "
                    "DP lockstep invariant it checks); it does not "
                    "support a tensor-parallel plan yet")
            if self.resident is not None:
                raise ValueError(
                    "--drift_audit_every audits at step boundaries, which "
                    "the resident whole-epoch dispatch does not have; "
                    "drop --resident to enable the drift audit")
            from ..resilience.drift import DriftAuditor
            self._drift = DriftAuditor(mesh, self.state.params,
                                       every=drift_audit_every,
                                       action=drift_action,
                                       registry=registry)
        init_span.end()

    def _ckpt_loader(self):
        """The lineage walk's candidate loader, bound to THIS run's mesh
        and plan (train/ckpt_shard.py): a sharded snapshot redistributes
        its saved (d, m) layout onto the live layout shard-by-shard; a
        gathered v1 file streams leaf-by-leaf onto its live sharding.
        Either way no host ever stages the full pytree — and any on-disk
        format restores onto any mesh shape, which is what makes
        ``--resume`` after a pod-shrinking preemption work at all."""
        import functools

        from .ckpt_shard import load_for_mesh
        # Under a pipeline plan the loader restores replicated (specs
        # None): __init__'s place_state pass owns the stage layout, so the
        # file's mesh shape never has to match this run's (d, m, s).
        specs = (self.tp_plan.param_specs
                 if self.tp_plan is not None and self.pp_plan is None
                 else None)
        return functools.partial(load_for_mesh, mesh=self.mesh,
                                 param_specs=specs)

    def _apply_lr_backoff(self, scale: float) -> None:
        """Guard ``lr_backoff`` hook: rebuild the jitted program with the
        schedule scaled by the guard's cumulative factor.  A recompile —
        but this fires only on an anomaly verdict, never in steady
        state."""
        base = self._base_lr_schedule
        self.lr_schedule = lambda step: base(step) * scale
        if self.resident is not None:
            self.train_epoch = self._rebuild_step(self.lr_schedule)
        else:
            self.train_step = self._rebuild_step(self.lr_schedule)

    def _epoch_losses_streaming(self, epoch: int, start: int, setup):
        """Per-step dispatch over host-fed batches (the reference's loop,
        multigpu.py:104-107).  ``start`` is the mid-epoch resume offset
        (data_state): the prefetch engine fast-forwards to batch
        ``start`` without materialising the skipped prefix.  ``setup`` is
        the epoch's open ``epoch_setup`` span: the engine's body runs at
        the loop's first ``next()`` and ends the span when it is built
        (pool made, first batches submitted), just before it first waits
        for a batch."""
        epoch_losses = []
        from ..data.prefetch import prefetch_to_device
        if self.grad_accum > 1 or self.pp_plan is not None:
            # One dispatch per GROUP of grad_accum micro-batches.  The
            # scanned accumulation amortises the per-dispatch overhead A-x;
            # the threaded prefetcher still pipelines group materialisation
            # + H2D against the (A-x longer) group dispatch, at the same
            # depth knob.  _stack_groups is a plain iterable, so this takes
            # the single-thread path; the stacked sharding rides in via
            # shard_fn.
            from .step import shard_batch_stacked
            if self.pp_plan is not None:
                # Pipeline microbatch injection: the SAME stacked group
                # stream, but images land on stage 0's submesh and labels
                # on the last stage's (parallel/pp/schedule.py) — the
                # schedule slices microbatch k out of the [A, ...] stack.
                from ..parallel.pp.schedule import pp_shard_fn
                stacked_shard = pp_shard_fn(self.pp_plan)
            else:
                stacked_shard = shard_batch_stacked
            batches = prefetch_to_device(
                _stack_groups(self.train_loader, self.grad_accum),
                self.mesh, depth=self.prefetch_depth,
                workers=self.prefetch_workers, stats=self.prefetch_stats,
                shard_fn=stacked_shard, tracer=self.tracer,
                step0=self._host_step, start=start, on_ready=setup.end)
        else:
            # Worker pool augments + device_puts ahead of the loop (the
            # pin_memory/worker analogue, singlegpu.py:177); combined with
            # JAX async dispatch the chips never wait on the host in
            # steady state.  depth=0 = the unpipelined reference shape.
            batches = prefetch_to_device(
                self.train_loader, self.mesh, depth=self.prefetch_depth,
                workers=self.prefetch_workers, stats=self.prefetch_stats,
                tracer=self.tracer, step0=self._host_step, start=start,
                on_ready=setup.end)
        step = self._host_step
        k = start  # epoch-local batch offset (the data_state coordinate)
        traced = self.tracer.enabled
        stacked = self.grad_accum > 1 or self.pp_plan is not None
        t_prev = time.monotonic()
        for device_batch in batches:
            # Step-boundary preemption (resilience/preemption.py): checked
            # BEFORE the dispatch, so batch k is the first UNCONSUMED one
            # — exactly the offset the emergency data_state records.
            # Single-process this is an Event read; multi-host every rank
            # runs the same per-step collective (global step as the one
            # sync-id space), so the stop is lockstep.
            if self._preemption is not None and \
                    self._preemption.should_stop_step(step, self.mesh):
                self._preempt_pending = (epoch, k)
                break
            if (epoch, k) in self._skip_batches:
                # Guard rollback condemned this batch: drop it instead of
                # re-ingesting the poisoned window (the step counter does
                # not advance — no optimizer update happened).
                if self.metrics is not None:
                    self.metrics.log_event("batch_skipped", epoch=epoch,
                                           batch=k, step=step)
                k += 1
                continue
            # The dispatch span covers the jitted call only — enqueue
            # time plus whatever XLA makes it wait for (donated-buffer
            # availability, compile on the first step), and carries the
            # call's samples.  A step of the consumer loop is the
            # engine's data_wait and h2d (or, at depth 0, host_augment
            # and h2d), then this; epoch_setup before the first step and
            # epoch_close after the last make the epoch whole, the
            # "where did step N go" record.
            with self.tracer.span(
                    "dispatch", step=step,
                    n=_samples(device_batch["label"], stacked) if traced
                    else None):
                self.state, loss = self.train_step(
                    self.state, device_batch, self.rng)
            epoch_losses.append(loss)
            if self._live is not None:
                # Same step id as this iteration's span and loss record —
                # the three streams must join on one key.
                now = time.monotonic()
                self._live.step(now - t_prev, step=step)
                t_prev = now
            step += 1
            k += 1
            if self._drift is not None and self._drift.due(step):
                # Synchronous cross-replica fingerprint compare (drift.py)
                # — the host read doubles as the XLA:CPU hazard drain, so
                # no extra gate is needed before the audit program.
                with self.tracer.span("drift_audit", step=step):
                    self._drift.audit(self.state.params, step,
                                      metrics=self.metrics,
                                      guard=self._health)
            if self._watchdog is not None:
                self._watchdog.beat()
            if self._step_probe is not None:
                self._step_probe(step)
        # The engine has shut down by now (its own epoch_close span: the
        # loop's last next() runs its ``finally``).
        with self.tracer.span("epoch_close", step=self._host_step):
            return jnp.stack(epoch_losses) if epoch_losses else None

    def _epoch_losses_resident(self, setup):
        """One (or two, with a ragged tail) jitted scan calls per epoch.
        Every call's index matrix is built and shipped before the first
        dispatch, under the epoch's open ``epoch_setup`` span, which
        ends here with the matrices' bytes."""
        from .epoch import put_index_matrix
        full, tail = self.train_loader.epoch_index_matrix()
        if self.grad_accum > 1:
            # Group the epoch's batches into [G, A, B] optimizer-step
            # stacks for the accumulation epoch scan — the same grouping
            # _stack_groups produces on the streaming path (full groups of
            # A, a remainder group, the ragged tail alone), so optimizer
            # step counts and the LR trajectory are identical.
            a = self.grad_accum
            n_groups, rem = divmod(full.shape[0], a)
            calls = []
            if n_groups:
                calls.append(full[:n_groups * a].reshape(n_groups, a, -1))
            if rem:
                calls.append(full[n_groups * a:][None])
            if tail is not None:
                calls.append(tail[None, None, :])
        else:
            calls = [full] if full.shape[0] else []
            if tail is not None:
                calls.append(tail[None, :])
        idxs = [put_index_matrix(c, self.mesh) for c in calls]
        setup.count(nbytes=sum(c.nbytes for c in calls))
        setup.end()
        first = s = self._host_step
        parts = []
        for idx in idxs:
            # One dispatch per scan call: the span's step is the call's
            # FIRST optimizer step and its ``n`` the call's samples (the
            # whole-epoch granularity is the resident mode's dispatch
            # pattern — per-step attribution lives inside XLA, reachable
            # via --profile_dir).
            with self.tracer.span("dispatch", step=s, n=idx.size):
                self.state, losses = self.train_epoch(
                    self.state, self.resident.images,
                    self.resident.labels, idx, self.rng)
            s += idx.shape[0]
            parts.append(losses)
        with self.tracer.span("epoch_close", step=first):
            return jnp.concatenate(parts) if parts else None

    def _run_epoch(self, epoch: int, start_offset: int = 0) -> None:
        # epoch_setup: from here to the epoch's first data_wait (where
        # batches stream) or first dispatch (where they are resident).
        # Like epoch_close it carries the epoch's first global step.
        setup = self.tracer.span("epoch_setup",
                                 step=self._host_step).__enter__()
        b_sz = self.train_loader.per_replica_batch
        # Reference epoch header (multigpu.py:102) — without materialising
        # and discarding a probe batch to learn b_sz (multigpu.py:101).
        print(f"[GPU{self.gpu_id}] Epoch {epoch} | Batchsize: {b_sz} | "
              f"Steps: {len(self.train_loader)}")
        # Global-step -> (epoch, batch) origin, for mapping a flushed
        # loss's step back to its data position (guard rollback's skip
        # window, mid-epoch data_state).
        self._epoch_origin[epoch] = (self._host_step, start_offset)
        self._host_epoch = epoch
        self.train_loader.set_epoch(epoch)
        stacked = (self._epoch_losses_resident(setup)
                   if self.resident is not None else
                   self._epoch_losses_streaming(epoch, start_offset, setup))
        n_losses = int(stacked.shape[0]) if stacked is not None else 0
        start_step = self._host_step
        self._host_step += n_losses
        if self._step_probe is not None and self.resident is not None:
            # Resident mode dispatches whole epochs — the probe fires at
            # the coarsest boundary that exists (per-step capture needs
            # the streaming loop).
            self._step_probe(self._host_step)
        # Defer the host read: flush the PREVIOUS epoch's losses now that
        # this epoch's work is queued behind them — the D2H transfer and
        # the next epoch's host prep then overlap device compute.  This
        # epoch's array is read at the next epoch's dispatch (or by
        # train()'s final flush).
        # The routing counters as this epoch's last step left them ride
        # with its losses: copies, because the next step donates the state.
        # (Kept beside the losses, by the epoch's first step: the flush's
        # signature is a seam that fault drills wrap.)
        riders = _counters(self.state.batch_stats)
        if self._depth_losses:
            riders[LM_LOSS] = self.state.batch_stats[LM_LOSS]
        # Each copy is a program of its own queued behind the steps in
        # flight, and the host's wait for them lands on whichever program
        # overflows the device's queue (the stack of the losses, or one of
        # these): the boundary's span covers them too.
        with (self.tracer.span("epoch_close", step=start_step) if riders
              else contextlib.nullcontext()):
            self._pending_counters[start_step] = jax.tree_util.tree_map(
                jnp.copy, riders)
        prev, self._pending_losses = (self._pending_losses,
                                      (epoch, start_step, stacked))
        if prev is not None:
            self._flush_losses(*prev)

    def _flush_losses(self, epoch: int, start_step: int, stacked) -> None:
        with self.tracer.span(
                "loss_flush", step=start_step,
                n=int(stacked.shape[0]) if stacked is not None else 0):
            self._flush_losses_inner(epoch, start_step, stacked)

    def _flush_losses_inner(self, epoch: int, start_step: int,
                            stacked) -> None:
        # One stacked D2H transfer for the whole epoch's losses — per-scalar
        # reads pay a link round trip each on remote-device setups.
        arr, counters = jax.device_get(
            (stacked, self._pending_counters.pop(start_step, None)))
        arr = (np.asarray(arr) if stacked is not None
               else np.zeros(0, np.float32))
        if counters and LM_LOSS in counters:
            # The epoch's last step's loss at each prediction depth.
            self.lm_loss = np.asarray(counters.pop(LM_LOSS)).tolist()
            if self._lm_gauge is not None:
                for depth, value in enumerate(self.lm_loss):
                    self._lm_gauge.labels(depth=str(depth)).set(value)
        if counters:
            self.routing.update(counters)
        losses = arr.tolist()
        if self._watchdog is not None:
            self._watchdog.beat()
        self.loss_history.extend(losses)
        # Loss health policy (--on_nan), checked on the array the flush
        # ALREADY fetched — zero extra D2H.  Losses are replicated, so on
        # multi-host every rank reaches the same verdict from its own copy
        # and the abort/restore paths stay in lockstep.  May raise
        # NonFiniteLossError (abort) or RestoreFromLastGood (restore,
        # caught by train()'s loop).
        if losses:
            self._health.check(arr, epoch=epoch, start_step=start_step)
        if self.metrics is not None and losses:
            # One vectorised device eval of the schedule per epoch.
            lrs = jax.device_get(jax.vmap(self.lr_schedule)(
                jnp.arange(start_step, start_step + len(losses))))
            for i, (loss, lr) in enumerate(zip(losses, lrs)):
                self.metrics.log_step(step=start_step + i, epoch=epoch,
                                      loss=loss, lr=float(lr))

    def flush_losses(self) -> None:
        """Host-read any deferred epoch losses now (blocks on the epoch).

        The epoch loop defers each epoch's loss D2H until the next
        epoch's work is dispatched, so ``loss_history``/the metrics
        stream can lag one epoch mid-run.  An ``epoch_callback`` that
        reads them (early stopping, eval-record ordering) calls this
        first — a callback that's a no-op this epoch then costs
        nothing, keeping the pipelining (a flush on every callback
        epoch would re-serialize the boundary it exists to hide)."""
        prev, self._pending_losses = self._pending_losses, None
        if prev is not None:
            self._flush_losses(*prev)

    def _join_pending_save(self) -> None:
        """Wait for the in-flight async checkpoint write, re-raising any
        error it hit (a silently-lost checkpoint must not look saved).

        Multi-host: only rank 0 writes, so only rank 0 raises — left alone,
        ranks 1+ would block forever in the next epoch's collectives.  Tear
        down the coordination service first so the peers' heartbeats fail
        fast (a clean distributed abort, not a hang)."""
        if self._save_thread is not None:
            self._save_thread.join()
            self._save_thread = None
            if self._save_error is not None:
                err, self._save_error = self._save_error, None
                if jax.process_count() > 1:
                    print(f"[GPU{self.gpu_id}] FATAL: async checkpoint "
                          f"write failed: {err!r}; shutting down the "
                          "coordinator so peer processes abort instead of "
                          "hanging in the next collective",
                          file=sys.stderr)
                    sys.stderr.flush()
                    dist.abort()  # non-graceful: never blocks (dist.py)
                raise err

    def _mirror_drain(self, timeout: float = 30.0) -> None:
        """Bounded wait for queued mirror uploads (emergency exits give
        the remote copy a head start before the SIGKILL).  Degrades to a
        logged lag report — NEVER raises, never waits unboundedly: the
        local checkpoint is already durable at this point and the exit
        contract (preemption status, supervisor relaunch) must hold even
        with a dead remote."""
        if self._mirror is None:
            return
        if not self._mirror.drain(timeout):
            print(f"[GPU{self.gpu_id}] mirror: still "
                  f"{self._mirror.lag_epochs()} epoch(s) behind after "
                  f"{timeout:.0f}s drain window; newest state is "
                  "local-only", file=sys.stderr)

    def _data_state(self, epoch: int, offset: int) -> dict:
        """The checkpoint's resume-position record: start training at
        batch ``offset`` of ``epoch`` (an end-of-epoch save is
        ``(epoch + 1, 0)``), with the sampler seed and the number of
        restore RNG folds needed to reconstruct the step-key stream."""
        return {"version": 1, "epoch": int(epoch), "offset": int(offset),
                "seed": self._seed,
                "rng_folds": int(self._health.restores)}

    def _save_checkpoint(self, epoch: int, data_state: dict = None) -> None:
        # The serial span covers the main-thread part only (device sync,
        # snapshot copies, joining the previous writer); the file write
        # itself runs on the writer thread and records its own
        # overlap=True ckpt_write span from save_checkpoint.
        with self.tracer.span("ckpt_write", step=self._host_step):
            self._save_checkpoint_inner(epoch, data_state)

    def _save_checkpoint_inner(self, epoch: int,
                               data_state: dict = None) -> None:
        if data_state is None:
            # The default save site is the end-of-epoch gate: the resume
            # position is the NEXT epoch's first batch.
            data_state = self._data_state(epoch + 1, 0)
        # XLA:CPU hazard gate — BEFORE anything (the ZeRO conversion
        # below included) enqueues work behind the in-flight epoch: the
        # CPU backend executes per-device programs on a shared thread
        # pool and joins cross-device all-reduces via a rendezvous that
        # needs every participant running.  Dependent executions queued
        # behind the epoch's collective programs can fill the pool with
        # blocked threads and deadlock the rendezvous (observed:
        # "Expected 8 threads ... only 7 arrived", fatal Check).  TPU
        # streams have no such hazard, so only CPU pays the
        # serialization — which is exactly the (implicit)
        # pre-pipelining behavior the CPU test tier always ran with.
        if jax.default_backend() == "cpu":
            jax.block_until_ready(self.state)
        # Canonical per-leaf momentum in the file regardless of the
        # in-memory layout: snapshots interchange across modes.  The
        # conversion is a COLLECTIVE under multi-host (all-gather of the
        # sharded buffer), so every process runs it; only rank 0 writes.
        opt_state = self.state.opt_state
        if self.shard_update:
            from .zero import opt_shard_to_pytree
            opt_state = opt_shard_to_pytree(self.state.params, opt_state,
                                            self.mesh, plan=self.tp_plan)
        # Tensor parallelism, --ckpt_format gathered (v1): SAVE GATHERS —
        # the model-sharded leaves are resharded to replicated (an
        # all-gather over the ``model`` axis; collective under multi-host,
        # so it sits BEFORE the rank-0 gate like the zero conversion
        # above), keeping the file in the one canonical format every mesh
        # shape can restore.  --ckpt_format sharded SKIPS the gather
        # entirely — the leaves persist as the per-slot shard files they
        # already are (ckpt_shard.py), so the save path is O(model/m) per
        # host in both memory and write stream instead of O(model).
        # Portability holds either way: restore redistributes.
        sharded = self.ckpt_format == "sharded"
        params, stats = self.state.params, self.state.batch_stats
        gathered = False
        if self.pp_plan is not None:
            # Pipeline state lives on per-stage SUBMESHES — one jitted
            # identity cannot span the disjoint device sets, so the
            # canonical/gathered file is assembled on the host instead
            # (a D2H copy per leaf: fresh host buffers, donation-safe by
            # construction, so the snapshot pass below is skipped too).
            # Single-process only, like the stage schedule itself.
            params, stats, mom = jax.device_get(
                (params, stats, opt_state.momentum_buf))
            opt_state = SGDState(mom)
            gathered = True
        elif self.tp_plan is not None and not sharded:
            rep = replicated_sharding(self.mesh)
            params, stats, mom = jax.jit(
                lambda p, s, m: (p, s, m),
                out_shardings=(rep, rep, rep))(params, stats,
                                               opt_state.momentum_buf)
            opt_state = SGDState(mom)
            gathered = True
        if self.gpu_id != 0 and not sharded:
            # Reference rank-0 gate, multigpu.py:118.  The SHARDED format
            # is written by every host in parallel (each streams only the
            # model-slots it owns — the per-host-writer contract), so
            # ranks > 0 fall through to their own writer thread there;
            # lineage bookkeeping stays rank-0-only inside write().
            return
        # Async write: snapshot the state into FRESH device buffers (an
        # on-device copy — donation-safe: the next epoch's step donates and
        # overwrites the original state arrays), start the device->host
        # copies, and hand the file write to a background thread so the
        # 75 MB transfer + npz write overlaps the next epoch's compute
        # instead of stalling the epoch loop (the reference's torch.save
        # blocks the loop the same way, multigpu.py:110-112).  Ordering:
        # _join_pending_save above guarantees at most one writer and that
        # overwrites of the fixed path happen in epoch order.
        self._join_pending_save()
        # TP mode: the gather above already produced fresh replicated
        # arrays (never part of the donated train state) — like the zero
        # conversion's output, copying them again would be pure waste.
        snap_params, snap_stats = (
            (params, stats) if gathered
            else jax.tree_util.tree_map(jnp.copy, (params, stats)))
        snap_opt = (opt_state.momentum_buf
                    if self.shard_update or gathered
                    else jax.tree_util.tree_map(jnp.copy,
                                                opt_state.momentum_buf))
        for leaf in jax.tree_util.tree_leaves(
                (snap_params, snap_stats, snap_opt)):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        # Host mirror, not int(self.state.step): the device scalar would
        # block the epoch loop on the in-flight epoch's completion.
        step = self._host_step

        def write():
            try:
                # Lineage bookkeeping brackets the head write, all inside
                # this single writer thread (at most one in flight —
                # _join_pending_save above), which is what lets rotation
                # run lock-free and guarantees it never touches a file
                # still being written: the in-flight write is a *.tmp name
                # rotation structurally ignores (resilience/lineage.py).
                if self.lineage is not None and self.gpu_id == 0:
                    self.lineage.preserve_head()
                if sharded:
                    from .ckpt_shard import save_checkpoint_sharded
                    sha, shard_names = save_checkpoint_sharded(
                        self.snapshot_path, snap_params, snap_stats,
                        SGDState(snap_opt), step, epoch, mesh=self.mesh,
                        tracer=self.tracer, data_state=data_state)
                else:
                    sha = save_checkpoint(self.snapshot_path, snap_params,
                                          snap_stats, SGDState(snap_opt),
                                          step, epoch, tracer=self.tracer,
                                          data_state=data_state)
                    shard_names = None
                if self.gpu_id != 0:
                    return  # shard writer only: no lineage, no print
                if self.lineage is not None:
                    self.lineage.commit(epoch=epoch, step=step, sha256=sha,
                                        shards=shard_names,
                                        data_state=data_state)
                if self._mirror is not None:
                    # AFTER the commit: only durable, sha-recorded states
                    # are mirrored.  enqueue snapshots the head (hard
                    # link) and returns immediately — the upload itself
                    # runs on the mirror's own thread, so a slow or dead
                    # remote costs this writer (and the step loop) nothing.
                    self._mirror.enqueue(epoch=epoch, step=step,
                                         sha256=sha,
                                         shards=shard_names or (),
                                         data_state=data_state)
                # Reference print, singlegpu.py:122.
                print(f"Epoch {epoch} | Training checkpoint saved at "
                      f"{self.snapshot_path}")
            except BaseException as e:  # surfaced at the next join
                self._save_error = e

        self._save_thread = threading.Thread(target=write, daemon=True)
        self._save_thread.start()

    def _restore_last_good(self) -> int:
        """``--on_nan restore`` / guard rollback / drift restore: reload
        the newest verifiable checkpoint (lineage fall-back included),
        re-seed the step RNG, and return the epoch to resume from (the
        batch offset, for a mid-epoch snapshot, lands in
        ``self._pending_resume_offset``).  Runs identically on every rank
        (the verdict came from replicated losses/fingerprints), so
        multi-host stays in lockstep."""
        from ..resilience.guard import NonFiniteLossError
        from ..resilience.lineage import latest_verifiable
        self._join_pending_save()  # let any in-flight (good) write land
        self._pending_losses = None  # the poisoned trajectory's records
        self._pending_counters.clear()
        self._preempt_pending = None
        loaded = (latest_verifiable(self.snapshot_path,
                                    loader=self._ckpt_loader(),
                                    store=self._mirror_store)
                  if self.snapshot_path else None)
        if loaded is None:
            raise NonFiniteLossError(
                "--on_nan restore: no checkpoint to restore from "
                f"(snapshot_path={self.snapshot_path!r}); nothing good was "
                "ever saved")
        ckpt, used = loaded
        state = TrainState(
            jax.tree_util.tree_map(jnp.asarray, ckpt.params),
            jax.tree_util.tree_map(jnp.asarray, _with_new_counters(
                ckpt.batch_stats, self.state.batch_stats)),
            jax.tree_util.tree_map(jnp.asarray, ckpt.opt_state),
            jnp.asarray(ckpt.step, jnp.int32))
        if self.tp_plan is not None and self.pp_plan is None:
            from ..parallel.tp.plan import state_shardings
            state = jax.device_put(state,
                                   state_shardings(self.tp_plan, self.mesh))
        elif self.pp_plan is not None:
            from ..parallel.pp.schedule import place_state
            state = place_state(state, self.mesh, self.pp_plan,
                                self.tp_plan)
        if self.shard_update:
            from .zero import pytree_to_opt_shard
            state = TrainState(state.params, state.batch_stats,
                               pytree_to_opt_shard(
                                   state.opt_state.momentum_buf, self.mesh,
                                   plan=self.tp_plan),
                               state.step)
        self.state = state
        self._host_step = int(ckpt.step)
        # Drop the discarded trajectory's loss records (they include the
        # non-finite steps) so loss_history stays one entry per global
        # step with no NaNs and no duplicates after the replay.  The
        # metrics JSONL is append-only, so there the replayed steps appear
        # twice — bracketed by the restore_from_checkpoint event below;
        # last record per step wins for consumers.
        del self.loss_history[max(int(ckpt.step) - self._history_base, 0):]
        # Re-seed the step RNG stream: the augmentation/dropout keys are a
        # pure function of (rng, step), so WITHOUT this fold the rewound
        # step counter would replay the exact trajectory that diverged.
        self.rng = jax.random.fold_in(self.rng, self._health.restores)
        print(f"[GPU{self.gpu_id}] restored last-good checkpoint {used} "
              f"(epoch {ckpt.epoch}, step {ckpt.step}); re-seeded the step "
              "RNG and resuming", file=sys.stderr)
        if self.metrics is not None:
            self.metrics.log_event("restore_from_checkpoint",
                                   epoch=ckpt.epoch, step=ckpt.step,
                                   snapshot=used,
                                   restores=self._health.restores)
        ds = ckpt.data_state
        if isinstance(ds, dict) and "epoch" in ds:
            self._pending_resume_offset = int(ds.get("offset", 0))
            return int(ds["epoch"])
        self._pending_resume_offset = 0
        return ckpt.epoch + 1

    def _train_one(self, epoch: int, epoch_callback,
                   start_offset: int = 0) -> None:
        if self._watchdog is not None:
            self._watchdog.beat()
        t_epoch = self.tracer.now()  # straggler-window marker
        self._run_epoch(epoch, start_offset=start_offset)
        if self._preempt_pending is not None:
            # The streaming loop stopped mid-epoch on a preemption
            # notice: the epoch is NOT complete, so the normal save gate
            # below must not write an end-of-epoch data_state — take the
            # mid-epoch emergency checkpoint and exit instead (raises).
            self._emergency_checkpoint_midepoch()
        # NB: like the reference, epoch 0 satisfies the modulo gate
        # — snapshot_path=None disables checkpointing entirely.
        if self.snapshot_path and epoch % self.save_every == 0:
            # Land + health-check THIS epoch's losses before snapshotting
            # its state: under --on_nan abort/restore a poisoned epoch then
            # raises here and never becomes a checkpoint, so the newest
            # file on disk is always loss-verified — the invariant the
            # restore policy reloads against.  Costs one host sync on save
            # epochs only; non-save boundaries keep the deferred-flush
            # pipelining.
            self.flush_losses()
            self._save_checkpoint(epoch)
        if epoch_callback is not None:
            # NB: the epoch's losses may still be deferred here —
            # a callback that reads loss_history/metrics calls
            # trainer.flush_losses() itself (see its docstring;
            # an unconditional flush would re-serialize every
            # epoch boundary for monitored runs).
            epoch_callback(epoch)
        # The rest of the boundary is epoch_close's, like the engine's
        # shutdown and the stack of the losses before it.  The callback
        # above is not: it names its own phases (cli.run's is a
        # loss_flush and an eval).
        stop = False
        with self.tracer.span("epoch_close",
                              step=self._epoch_origin[epoch][0]):
            self._log_stragglers(epoch, t_epoch)
            # analysis: divergence-ok(ctor-time config, identical on all ranks)
            if self._preemption is not None:
                # COLLECTIVE on multi-host (resilience/preemption.py):
                # every rank calls it at every epoch boundary so the stop
                # decision — and the emergency save's collective
                # canonicalisation — run in lockstep.  The streaming loop
                # also checks per step; this boundary check catches a
                # notice that landed after the epoch's last dispatch,
                # keeping the completed epoch's checkpoint as the
                # emergency state.  Resident mode keeps the
                # epoch-granular sync-id space (its dispatch unit);
                # streaming uses the global-step space throughout so the
                # two never mix sync counters.
                stop = (self._preemption.should_stop(epoch, self.mesh)
                        if self.resident is not None else
                        self._preemption.should_stop_step(self._host_step,
                                                          self.mesh))
        if stop:
            self._emergency_checkpoint(epoch)

    def _log_stragglers(self, epoch: int, since: float) -> None:
        """Per-epoch cross-host phase attribution (obs/aggregate.py).

        Multi-host this is a COLLECTIVE (the per-host median gather), so
        the gate must evaluate identically on every rank: tracer.enabled
        comes from the shared CLI flags, never from rank-local state —
        and it sits before the preemption collective, keeping the epoch
        boundary's collective order fixed.  Single-host skips the device
        round entirely (numpy path — the XLA:CPU backend must not see
        extra programs behind an in-flight epoch, see
        _save_checkpoint_inner's hazard note)."""
        if not self.tracer.enabled:
            # analysis: divergence-ok(enabled is shared CLI config)
            return
        multi = dist.process_count() > 1
        if not multi and (self.metrics is None
                          or not getattr(self.metrics, "active", True)):
            return  # no sink would receive the record: skip building it
        if multi and jax.default_backend() == "cpu":
            # XLA:CPU hazard gate (see _save_checkpoint_inner): the
            # gather below enqueues a collective program that must not
            # queue behind the in-flight epoch's programs on the shared
            # CPU thread pool.
            jax.block_until_ready(self.state)
        from ..obs.aggregate import epoch_straggler_record
        epoch_straggler_record(self.tracer, self.mesh if multi else None,
                               since, metrics=self.metrics, epoch=epoch)

    def _emergency_checkpoint(self, epoch: int) -> None:
        """Coordinated preemption exit: flush + verify the epoch's losses,
        make sure its checkpoint is ON DISK (not just queued), and raise
        :class:`PreemptionInterrupt` for cli.run to convert into the
        distinct exit status."""
        from ..resilience.preemption import PreemptionInterrupt
        self.flush_losses()
        if self.snapshot_path and epoch % self.save_every != 0:
            self._save_checkpoint(epoch)  # the modulo gate didn't fire
        self._join_pending_save()  # async write must land before we exit
        self._mirror_drain()  # bounded head start for the remote copy
        print(f"[GPU{self.gpu_id}] preemption: emergency checkpoint for "
              f"epoch {epoch} is on disk"
              + (f" at {self.snapshot_path}" if self.snapshot_path
                 else " — DISABLED (snapshot_path=None), state lost"),
              file=sys.stderr)
        if self.metrics is not None:
            self.metrics.log_event("preemption_checkpoint", epoch=epoch,
                                   step=self._host_step,
                                   snapshot=self.snapshot_path)
            # The records describing the run's final verified state must
            # survive the SIGKILL that follows SIGTERM: line buffering
            # only reaches the page cache — force the tail to DISK.
            self.metrics.fsync()
        self.tracer.flush(fsync=True)  # same durability for the span tail
        raise PreemptionInterrupt(epoch, self.snapshot_path)

    def _emergency_checkpoint_midepoch(self) -> None:
        """Step-boundary preemption exit: the streaming loop stopped with
        the epoch partially trained.  Flush + health-check the partial
        losses (the on-disk state must stay loss-verified), save with a
        mid-epoch ``data_state`` naming the first unconsumed batch, and
        raise :class:`PreemptionInterrupt`."""
        from ..resilience.preemption import PreemptionInterrupt
        epoch, k = self._preempt_pending
        self._preempt_pending = None
        # Lands the previous epoch's deferred losses AND this epoch's
        # partial vector — both health-checked before the save, keeping
        # the every-checkpoint-is-loss-verified invariant at step
        # granularity.
        self.flush_losses()
        if self.snapshot_path:
            self._save_checkpoint(epoch,
                                  data_state=self._data_state(epoch, k))
        self._join_pending_save()  # async write must land before we exit
        self._mirror_drain()  # bounded head start for the remote copy
        print(f"[GPU{self.gpu_id}] preemption: mid-epoch emergency "
              f"checkpoint at epoch {epoch}, batch offset {k} (global "
              f"step {self._host_step})"
              + (f" is on disk at {self.snapshot_path}"
                 if self.snapshot_path
                 else " — DISABLED (snapshot_path=None), state lost"),
              file=sys.stderr)
        if self.metrics is not None:
            self.metrics.log_event("preemption_checkpoint", epoch=epoch,
                                   step=self._host_step, offset=k,
                                   snapshot=self.snapshot_path)
            self.metrics.fsync()
        self.tracer.flush(fsync=True)
        raise PreemptionInterrupt(epoch, self.snapshot_path)

    def _mark_poisoned(self, epoch, steps) -> None:
        """Map a rollback verdict's global steps to their ``(epoch,
        batch)`` data positions and condemn them — the streaming loop
        drops condemned batches on the replay."""
        origin = self._epoch_origin.get(epoch)
        if origin is None:
            return
        start_step, start_offset = origin
        marked = [(int(epoch), start_offset + int(s) - start_step)
                  for s in steps]
        self._skip_batches.update(marked)
        print(f"[GPU{self.gpu_id}] guard rollback: skipping poisoned "
              f"batch window {[m[1] for m in marked[:8]]} of epoch "
              f"{epoch} on replay", file=sys.stderr)

    def train(self, max_epochs: int, epoch_callback=None) -> None:
        """Reference ``Trainer.train`` (multigpu.py:115-119): epoch loop with
        the rank-0 ``save_every`` checkpoint gate.  ``epoch_callback(epoch)``
        runs after each epoch's checkpoint gate (used for --eval_every;
        no reference analogue).  The loop is restartable: an
        ``--on_nan restore`` verdict rewinds it to the reloaded
        checkpoint's epoch instead of unwinding the run."""
        from ..resilience.guard import RestoreFromLastGood
        try:
            epoch = self.start_epoch
            offset = self._resume_offset  # mid-epoch data_state position
            while epoch < max_epochs:
                try:
                    self._train_one(epoch, epoch_callback,
                                    start_offset=offset)
                    offset = 0
                    epoch += 1
                    if epoch == max_epochs:
                        # Final flush inside the guard: a poisoned LAST
                        # epoch still gets its policy applied.
                        self.flush_losses()
                except RestoreFromLastGood as e:
                    if getattr(e, "skip_steps", None):
                        self._mark_poisoned(e.skip_epoch, e.skip_steps)
                    epoch = self._restore_last_good()
                    offset = self._pending_resume_offset
        finally:
            # The last checkpoint write must be on disk before train()
            # returns (resume and the reference's artifact contract depend
            # on it) — on the success path AND when the loop unwinds via an
            # exception/KeyboardInterrupt, or the daemon writer would be
            # killed at interpreter exit and the newest checkpoint lost.
            if sys.exc_info()[1] is None:
                self._join_pending_save()
                self._mirror_drain()  # end-of-run: let the mirror catch up
            else:
                # Already unwinding: still land the deferred losses and
                # wait for the writer, but don't let THEIR errors REPLACE
                # the in-flight exception (e.g. a KeyboardInterrupt a
                # caller handles for graceful shutdown) — report instead.
                try:
                    self.flush_losses()
                except BaseException as e:
                    print(f"deferred loss read failed during shutdown: "
                          f"{e!r}", file=sys.stderr)
                try:
                    self._join_pending_save()
                except BaseException as e:
                    print(f"checkpoint write failed during shutdown: {e!r}",
                          file=sys.stderr)
                self._mirror_drain(timeout=5.0)  # bounded, never raises
