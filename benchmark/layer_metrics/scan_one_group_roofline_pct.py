"""The one-group Mamba-2 scan's share of its roofline: the least time the
chip could take for the traced tokens — the larger of its operations over
the bf16 peak and its bytes over the HBM rate
(``flops_granite.scan_train_flops`` and ``scan_train_bytes``: ``x``,
``B``, ``C``, ``z`` in and ``y`` out, ``dt`` in float32, once forward and
twice backward, every Mamba-2 layer) — over the device time in the scope
``ssm_scan`` (ops/ssd.py's kernels a tile of heads a grid step, the
tiles' ``dB``/``dC`` sums, and the gate and the norm over all of a
token's channels in XLA).  At the published sizes the bytes bound it.
None with no time in the scope."""
from benchmark import flops_granite
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "ssm_scan")
    if not secs:
        return None
    tokens = ctx["trace"]["samples"] * ctx["seq_len"] / ctx["chips"]
    dm, peak = ctx["layers"], ctx["peak"]
    least = max(
        flops_granite.scan_train_flops(dm, tokens) / peak["bf16_flops_per_s"],
        flops_granite.scan_train_bytes(dm, tokens) / peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
