"""Live run statistics: rolling step-time window, samples/sec, MFU, and
prefetch occupancy, emitted through :class:`~ddp_tpu.utils.metrics.
MetricsLogger` (JSONL + TensorBoard) every ``--log_every`` steps.

This is the always-on answer to "is the run healthy *right now*" —
median/p90 step time over a rolling window (p90 >> median is the local
straggler/input-stall signature), achieved samples/sec, MFU against the
device kind's table peak when the model has a FLOP model, and the prefetch
engine's occupancy (consumer wait ≈ 0 means the input pipeline is fully
hidden behind compute).  The offline twin — exact per-step attribution —
is the span spill (obs/tracer.py + ``python -m ddp_tpu.obs``).

The FLOP model and the peak table live HERE (single home);
bench.py imports them for its offline MFU records, so the live and
bench numbers can never disagree on the denominator.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Optional

# MFU denominator: the published bf16 peak of one chip, keyed by
# ``jax.devices()[0].device_kind`` — the right denominator for fp32 too,
# because the fp32 path's convs also run as single-pass bf16-input/
# fp32-accum MXU passes.  This table is the ONLY source: a device kind
# that is not in it gets no MFU field.
# v5e: 197 TFLOP/s bf16 (Google Cloud documentation, "TPU v5e").
PEAK_TFLOPS_BF16_PASS = {"TPU v5 lite": 197.0}

# Per-sample train FLOPs, derived per model from the SAME cost model
# BUDGETS.json gates (analysis/costmodel.py counts the fwd+bwd heavy
# ops of the traced grad) — every registered model gets a live MFU from
# one source of truth, instead of the old hand-maintained {"vgg": 3.6}
# table that silently omitted MFU for deepnn/resnet18 runs.  None caches
# a failed derivation so a broken model costs one attempt, not one per
# emission.
_GFLOP_CACHE: Dict[str, Optional[float]] = {}


def train_gflop_per_sample(model_name: Optional[str]) -> Optional[float]:
    """GFLOP per sample of one training step (forward + backward heavy
    ops), counted by tracing ``grad(loss)`` abstractly at batch 1 through
    :func:`~ddp_tpu.analysis.costmodel.cost_of_jaxpr`.  Cached per model;
    None when the model is unknown or untraceable."""
    if not model_name:
        return None
    if model_name in _GFLOP_CACHE:
        return _GFLOP_CACHE[model_name]
    try:
        import jax
        import jax.numpy as jnp

        from ..analysis.costmodel import cost_of_jaxpr
        from ..models import get_model
        model = get_model(model_name)
        params, stats = jax.eval_shape(model.init, jax.random.key(0))

        def _sds(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)), tree)

        def loss(p, s, x, y, rng):
            logits, _ = model.apply(p, s, x, train=True, rng=rng)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

        closed = jax.make_jaxpr(jax.grad(loss))(
            _sds(params), _sds(stats),
            jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            _sds(jax.random.key(0)))
        gflop = cost_of_jaxpr(closed.jaxpr).flops / 1e9
    except Exception:  # no MFU beats a wrong or crashing one
        gflop = None
    _GFLOP_CACHE[model_name] = gflop
    return gflop


def model_mfu(samples_per_sec_per_chip: float, model: Optional[str],
              device_kind: Optional[str]) -> Optional[float]:
    """MFU for a measured per-chip rate: counted-jaxpr FLOPs achieved
    per second over the device kind's peak in ``PEAK_TFLOPS_BF16_PASS``.
    None when the model cannot be FLOP-counted or the kind is not in the
    table."""
    gflop = train_gflop_per_sample(model)
    peak = PEAK_TFLOPS_BF16_PASS.get(device_kind or "")
    if gflop is None or peak is None:
        return None
    return samples_per_sec_per_chip * gflop * 1e9 / (peak * 1e12)


class LiveStats:
    """Rolling-window live stats engine, fed per-step durations by the
    trainer's streaming loop; every ``log_every`` steps one ``live``
    record lands in the metrics stream (rank 0 — the caller gates).

    ``prefetch_stats`` (a :class:`~ddp_tpu.data.prefetch.PrefetchStats`)
    is sampled differentially per emission, so occupancy describes the
    window just measured, not the whole run's average.
    """

    def __init__(self, metrics, *, global_batch: int, n_chips: int,
                 log_every: int = 50, window: int = 100,
                 model: Optional[str] = None,
                 device_kind: Optional[str] = None,
                 prefetch_stats=None):
        self._metrics = metrics
        self.global_batch = int(global_batch)
        self.n_chips = max(int(n_chips), 1)
        self.log_every = max(int(log_every), 1)
        self._durs: deque = deque(maxlen=max(int(window), 2))
        self._count = 0
        self.model = model
        self.device_kind = device_kind
        self._pf = prefetch_stats
        self._pf_prev = self._pf_snapshot()
        # Consumer-loop seconds accumulated since the last emission — the
        # occupancy denominator.  Wall-clock since the last emit would
        # fold in compile, epoch boundaries (flush/checkpoint/eval) and
        # pre-training setup, reporting ~1.0 occupancy for a first window
        # that in truth waited on input the whole time.
        self._win_s = 0.0

    def _pf_snapshot(self) -> Dict[str, float]:
        if self._pf is None:
            return {}
        return {"wait_s": self._pf.wait_s, "host_s": self._pf.host_s,
                "h2d_s": self._pf.h2d_s, "batches": self._pf.batches}

    def step(self, dur_s: float, step: int) -> None:
        """Record one consumer-loop step duration; emits on the cadence."""
        self._durs.append(float(dur_s))
        self._win_s += float(dur_s)
        self._count += 1
        if self._count % self.log_every == 0:
            self._emit(step)

    def _emit(self, step: int) -> None:
        durs = sorted(self._durs)
        n = len(durs)
        median = durs[n // 2] if n % 2 else (durs[n // 2 - 1]
                                             + durs[n // 2]) / 2.0
        # Nearest-rank p90: ceil(0.9 n)-th order statistic — with a small
        # window this still surfaces a single straggler step (an
        # interpolating quantile would average it away).
        p90 = durs[min(-(-9 * n // 10) - 1, n - 1)]
        fields: Dict[str, float] = {
            "step_ms_median": round(median * 1e3, 3),
            "step_ms_p90": round(p90 * 1e3, 3),
            "window_steps": n,
        }
        if median > 0:
            sps = self.global_batch / median
            fields["samples_per_sec"] = round(sps, 2)
            fields["samples_per_sec_per_chip"] = round(sps / self.n_chips, 2)
            mfu = model_mfu(sps / self.n_chips, self.model, self.device_kind)
            if mfu is not None:
                fields["mfu"] = round(mfu, 4)
        if self._pf is not None:
            cur = self._pf_snapshot()
            db = cur["batches"] - self._pf_prev["batches"]
            elapsed = max(self._win_s, 1e-9)
            dwait = max(cur["wait_s"] - self._pf_prev["wait_s"], 0.0)
            if db > 0:
                fields["prefetch_wait_ms_per_step"] = round(
                    dwait / db * 1e3, 3)
                fields["prefetch_host_ms_per_step"] = round(
                    max(cur["host_s"] - self._pf_prev["host_s"], 0.0)
                    / db * 1e3, 3)
                fields["prefetch_h2d_ms_per_step"] = round(
                    max(cur["h2d_s"] - self._pf_prev["h2d_s"], 0.0)
                    / db * 1e3, 3)
            # Occupancy: fraction of the window the consumer loop was NOT
            # blocked waiting for a batch — 1.0 means the input pipeline
            # is fully hidden behind compute (PrefetchStats' wait_s is
            # exactly the measured pipeline bubble).
            fields["prefetch_occupancy"] = round(
                min(max(1.0 - dwait / elapsed, 0.0), 1.0), 4)
            self._pf_prev = cur
        self._win_s = 0.0
        self._metrics.log_live(step=step, **fields)
