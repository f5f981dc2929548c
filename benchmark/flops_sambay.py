"""Operations and bytes a SambaY training step needs, from its layer
sizes alone (the yardstick of the ``sambay`` cells; nothing of the
program is read).

``dm`` is what the configuration's reference lists
(``reference/sambay.py:layer_shapes``): the sizes, and ``kinds``, the
mixer of each layer held (``mamba``, ``window``, ``full``, ``gmu``,
``cross``).  Counted per token of a sequence of ``t`` tokens, one
multiply-accumulate as two operations, at their least: every projection;
the depthwise conv; the selective scan's three multiply-adds a state
element a token (decay, input, read-out); differential attention's two
score maps and ONE value product a head pair (the maps subtracted first)
over the keys a query sees: ``min(i + 1, window)`` in a window layer, the
causal half in a full or a cross layer; the gated memory unit's two
products; the SwiGLU products; the head over the vocabulary slice.  A
training step is three passes (forward, and backward for the input and
for the weights); nothing recomputed is counted, and neither are the
elementwise layers (norms, gates, the update).
"""
from __future__ import annotations

PASSES = 3
ATTENTION = ("window", "full", "cross")


def visible_keys(kind: str, t: int, window: int) -> float:
    """The mean over a sequence's ``t`` queries of the keys one sees."""
    if kind == "window":
        w = min(window, t)
        return (w * (w + 1) / 2 + (t - w) * w) / t
    return (t + 1) / 2


def layer_macs_per_token(dm: dict, t: int) -> dict:
    """``{kind: {part: multiply-accumulates a token}}`` for the five
    mixers, ``mlp`` (every layer has one) and ``head``."""
    d, di, n = dm["d"], dm["d_inner"], dm["n"]
    width = dm["pairs"] * 2 * dm["hd"]          # all query heads
    kv_width = dm["kv_pairs"] * 2 * dm["hd"]    # keys; the values' too
    # A visible key costs a token two 64-wide scores and one 128-wide
    # value row a pair: 2 x width in all.

    def core(kind):
        return 2 * width * visible_keys(kind, t, dm["window"])

    self_proj = d * (width + 2 * kv_width) + width * d
    return {
        "mamba": {"in_proj": d * 2 * di, "conv": dm["k"] * di,
                  "x_proj": di * (dm["dt_rank"] + 2 * n),
                  "dt_proj": dm["dt_rank"] * di, "scan": 3 * di * n,
                  "out_proj": di * d},
        "window": {"proj": self_proj, "core": core("window")},
        "full": {"proj": self_proj, "core": core("full")},
        "cross": {"proj": d * width + width * d, "core": core("cross")},
        "gmu": {"in": d * di, "out": di * d},
        "mlp": {"up": d * 2 * dm["ff"], "down": dm["ff"] * d},
        "head": {"head": d * dm["vocab"]},
    }


def forward_flops_per_token(dm: dict, t: int) -> float:
    macs = layer_macs_per_token(dm, t)
    total = sum(macs["head"].values())
    for kind in dm["kinds"]:
        total += sum(macs[kind].values()) + sum(macs["mlp"].values())
    return 2.0 * total


def train_flops_per_sequence(dm: dict, t: int) -> float:
    """Forward and backward operations for one sequence of ``t`` tokens:
    the numerator of ``train_mfu_pct`` (a sample is a sequence)."""
    return PASSES * forward_flops_per_token(dm, t) * t


def n_layers(dm: dict, kind: str) -> int:
    return dm["kinds"].count(kind)


def scan_train_flops(dm: dict, tokens: float) -> float:
    """The selective scan's operations, all Mamba layers, three passes."""
    return (PASSES * 2.0 * layer_macs_per_token(dm, 1)["mamba"]["scan"]
            * tokens * n_layers(dm, "mamba"))


def scan_train_bytes(dm: dict, tokens: float, act_bytes: int = 2) -> float:
    """The bytes the scan and its gate cannot avoid: ``x`` and ``z`` in
    and ``y`` out in the compute type, ``dt`` in float32, ``B`` and ``C``,
    once forward and twice backward, all Mamba layers."""
    a_token = ((3 * dm["d_inner"] + 2 * dm["n"]) * act_bytes
               + dm["d_inner"] * 4)
    return PASSES * a_token * tokens * n_layers(dm, "mamba")


def attn_core_train_flops(dm: dict, t: int, sequences: float,
                          kinds=ATTENTION) -> float:
    """Scores and values of the attention layers of ``kinds`` over the
    keys their queries see, three passes."""
    macs = layer_macs_per_token(dm, t)
    return sum(PASSES * 2.0 * macs[k]["core"] * t * sequences
               * n_layers(dm, k) for k in kinds)


def mlp_train_flops(dm: dict, tokens: float) -> float:
    """The SwiGLU products of every layer held, three passes."""
    return (PASSES * 2.0 * sum(layer_macs_per_token(dm, 1)["mlp"].values())
            * tokens * len(dm["kinds"]))
