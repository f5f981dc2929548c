"""Operations and bytes a ``granite_hybrid`` training step needs, from its
layer sizes alone (the yardstick of the ``granite_hybrid`` cells; nothing
of the program is read).

``dm`` is what the configuration's reference lists
(``reference/granite_hybrid.py:layer_shapes``).  Counted per token of a
sequence of ``t`` tokens, one multiply-accumulate as two operations, at
their least: every layer's MLP (three products of the dense width); a
Mamba-2 layer's projections in and out, its depthwise convolution and
its state recurrence at its least (three multiply-adds a state element a
token: decay, input, read-out; not the chunked form's extra products); an
attention layer's projections and its core over the causal half (a key
costs a head ``2 hd`` multiply-adds); the tied head over the vocabulary
slice.  A training step is three passes (forward, and backward for the
input and for the weights); nothing recomputed is counted, and neither
are the elementwise layers (norms, gates, the µP scalars, the update).
"""
from __future__ import annotations

PASSES = 3


def macs_per_token(dm: dict, t: int) -> dict:
    """``{part: multiply-accumulates a token}`` of the whole stage held."""
    d, hd = dm["d"], dm["head_dim"]
    hq, hkv = dm["heads"], dm["kv_heads"]
    out = dict.fromkeys(("mlp", "ssm_proj", "ssm_conv", "scan", "attn_proj",
                         "attn_core", "head"), 0.0)
    for kind in dm["kinds"]:
        out["mlp"] += 3 * d * dm["ff"]
        if kind == "mamba":
            out["ssm_proj"] += d * (2 * dm["d_inner"] + 2 * dm["g"] * dm["n"]
                                    + dm["h"]) + dm["d_inner"] * d
            out["ssm_conv"] += dm["k"] * dm["conv_dim"]
            out["scan"] += 3 * dm["h"] * dm["p"] * dm["n"]
        else:
            out["attn_proj"] += 2 * d * hq * hd + 2 * d * hkv * hd
            # scores and values, each over the t/2 keys of the causal half
            out["attn_core"] += 2 * hq * hd * t / 2
    out["head"] = d * dm["vocab"]
    return out


def train_flops_per_sequence(dm: dict, t: int) -> float:
    """Forward and backward operations for one sequence of ``t`` tokens:
    the numerator of ``train_mfu_pct`` (a sample is a sequence)."""
    return PASSES * 2.0 * sum(macs_per_token(dm, t).values()) * t


def scan_train_flops(dm: dict, tokens: float) -> float:
    """The state recurrence's operations, every Mamba-2 layer, three
    passes, for that many tokens."""
    return PASSES * 2.0 * macs_per_token(dm, 1)["scan"] * tokens


def scan_train_bytes(dm: dict, tokens: float, act_bytes: int = 2) -> float:
    """The bytes the recurrence cannot avoid (``flops_seq.py``'s measure):
    ``x``, ``B``, ``C``, ``z`` in and ``y`` out in the compute type, ``dt``
    in float32, once forward and twice backward, every Mamba-2 layer."""
    a_token = ((3 * dm["d_inner"] + 2 * dm["g"] * dm["n"]) * act_bytes
               + dm["h"] * 4)
    return PASSES * a_token * tokens * dm["kinds"].count("mamba")
