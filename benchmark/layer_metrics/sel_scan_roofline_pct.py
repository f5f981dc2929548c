"""The selective scan's share of its roofline: the least time the chip
could take for the traced tokens — the larger of its operations over the
bf16 peak and its bytes over the HBM rate (``flops_sambay.
scan_train_flops`` and ``scan_train_bytes``: ``x``, ``z`` in and ``y``
out in the compute type, ``dt`` in float32, ``B``, ``C``; once forward
and twice backward) — over the device time in the scope ``sel_scan``.
At the published sizes the bytes bound it.  None with no time in the
scope."""
from benchmark import flops_sambay
from benchmark.layer_metrics import _scopes


def read(ctx):
    secs = _scopes.seconds(ctx, "sel_scan")
    if not secs:
        return None
    tokens = ctx["trace"]["samples"] * ctx["seq_len"] / ctx["chips"]
    dm, peak = ctx["layers"], ctx["peak"]
    least = max(
        flops_sambay.scan_train_flops(dm, tokens)
        / peak["bf16_flops_per_s"],
        flops_sambay.scan_train_bytes(dm, tokens)
        / peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
