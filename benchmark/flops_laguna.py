"""Operations a ``laguna`` training step needs, from its layer sizes alone
(the yardstick of the ``laguna`` cells; nothing of the program is read).

``dm`` is what the configuration's reference lists
(``reference/laguna.py:layer_shapes``).  Counted per token of a sequence
of ``t`` tokens, one multiply-accumulate as two operations, at their
least: a layer's gated projections (``q``, ``k``, ``v``, the per-head
gate and the output, at the layer's own head count); its core, scores
and values (a key costs a head ``2 hd`` multiply-adds), over the causal
half in a full layer and over ``min(i + 1, window)`` keys for the query
at position ``i`` in a window layer; the dense layer's three products;
an expert layer's router, shared expert and the routed experts held here
at uniform routing (``top_k * count / router`` assignments a token,
three matrices an expert); the head over the vocabulary slice.  A
training step is three passes (forward, and backward for the input and
for the weights); nothing recomputed is counted, and neither are the
elementwise layers (norms, rotary embedding, the gates' sigmoid, the
update).
"""
from __future__ import annotations

PASSES = 3


def window_keys(t: int, window: int) -> float:
    """The mean over a sequence's ``t`` queries of the keys a window of
    ``window`` (the query counted) shows: ``min(i + 1, window)``."""
    w = min(window, t)
    return (w * (w + 1) / 2 + (t - w) * w) / t


def macs_per_token(dm: dict, t: int) -> dict:
    """``{part: multiply-accumulates a token}`` of the whole model held;
    ``full_core`` and ``swa_core`` are the two kinds of attention core."""
    d, hd, g = dm["d"], dm["hd"], dm["kv_heads"]
    out = dict.fromkeys(("gated_proj", "full_core", "swa_core", "dense",
                         "router", "shared", "routed", "head"), 0.0)
    for full, dense, h in zip(dm["full"], dm["dense"], dm["heads"]):
        out["gated_proj"] += 2 * d * h * hd + 2 * d * g * hd + d * h
        keys = t / 2 if full else window_keys(t, dm["window"])
        out["full_core" if full else "swa_core"] += h * 2 * hd * keys
        if dense:
            out["dense"] += 3 * d * dm["ff"]
        else:
            out["router"] += d * dm["router"]
            out["shared"] += 3 * d * dm["shared"]
            out["routed"] += (dm["top_k"] * dm["count"] / dm["router"]
                              * 3 * d * dm["expert"])
    out["head"] = d * dm["vocab"]
    return out


def train_flops_per_sequence(dm: dict, t: int) -> float:
    """Forward and backward operations for one sequence of ``t`` tokens:
    the numerator of ``train_mfu_pct`` (a sample is a sequence)."""
    return PASSES * 2.0 * sum(macs_per_token(dm, t).values()) * t


def core_train_flops(dm: dict, t: int, sequences: float, kind: str) -> float:
    """One kind of core's operations (``full_core`` or ``swa_core``),
    every layer of that kind, three passes, for that many sequences."""
    return PASSES * 2.0 * macs_per_token(dm, t)[kind] * t * sequences
