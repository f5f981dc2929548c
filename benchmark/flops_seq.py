"""Operations and bytes a sequence model's training step needs, from its
layer sizes alone (the yardstick for token-trained cells: ``flops.py``
knows ``conv`` and ``linear`` per sample; nothing of the program is read).

``dm`` is what a configuration's reference lists (``reference/<name>.py:
layer_shapes``).  Counted per token of a sequence of ``t`` tokens, one
multiply-accumulate as two operations: every projection, the depthwise
conv, the state recurrence at its least (three multiply-adds a state
element a token: decay, input, read-out; not the chunked form's extra
products), the causal half of the attention scores and values, the
router, the shared expert, the routed experts held here at uniform
routing (``top_k * count / router`` assignments a token) and the head
over the vocabulary slice.  A training step is three passes (forward, and
backward for the input and for the weights); nothing recomputed is
counted, and neither are the elementwise layers (norms, gates, the
update).
"""
from __future__ import annotations

PASSES = 3


def layer_macs_per_token(dm: dict, t: int) -> dict:
    """``{kind: {part: multiply-accumulates a token}}`` for ``M``, ``*``,
    ``E`` and ``head``."""
    d = dm["d"]
    state = dm["h"] * dm["p"] * dm["n"]
    hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    routed_share = dm["top_k"] * dm["count"] / dm["router"]
    return {
        "M": {"in_proj": d * (2 * dm["d_inner"] + 2 * dm["g"] * dm["n"]
                              + dm["h"]),
              "conv": dm["k"] * dm["conv_dim"],
              "scan": 3 * state,
              "out_proj": dm["d_inner"] * d},
        "*": {"proj": 2 * d * hq * hd + 2 * d * hkv * hd,
              # scores and values, each over the t/2 keys of the causal half
              "core": 2 * hq * hd * t / 2},
        "E": {"router": d * dm["router"],
              "shared": 2 * d * dm["shared"],
              "routed": routed_share * 2 * d * dm["expert"]},
        "head": {"head": d * dm["vocab"]},
    }


def forward_flops_per_token(dm: dict, t: int) -> float:
    macs = layer_macs_per_token(dm, t)
    total = sum(macs["head"].values())
    for kind in dm["pattern"]:
        total += sum(macs[kind].values())
    return 2.0 * total


def train_flops_per_sequence(dm: dict, t: int) -> float:
    """Forward and backward operations for one sequence of ``t`` tokens:
    the numerator of ``train_mfu_pct`` (a sample is a sequence)."""
    return PASSES * forward_flops_per_token(dm, t) * t


def n_layers(dm: dict, kind: str) -> int:
    return dm["pattern"].count(kind)


def scan_train_flops(dm: dict, tokens: int) -> float:
    """The state recurrence's operations, all ``M`` layers, three passes."""
    return (PASSES * 2.0 * layer_macs_per_token(dm, 1)["M"]["scan"]
            * tokens * n_layers(dm, "M"))


def scan_train_bytes(dm: dict, tokens: int, act_bytes: int = 2) -> float:
    """The bytes the recurrence cannot avoid: ``x``, ``B``, ``C``, ``z``
    in and ``y`` out in the compute type, ``dt`` in float32, once forward
    and twice backward, all ``M`` layers."""
    a_token = ((3 * dm["d_inner"] + 2 * dm["g"] * dm["n"]) * act_bytes
               + dm["h"] * 4)
    return PASSES * a_token * tokens * n_layers(dm, "M")


def expert_train_flops(dm: dict, assignments: float) -> float:
    """The routed experts' operations for that many assignments (counted
    by the program, over all ``E`` layers), three passes."""
    return PASSES * 2.0 * 2 * dm["d"] * dm["expert"] * assignments


def attn_core_train_flops(dm: dict, t: int, sequences: float) -> float:
    """Scores and values over the causal half, all ``*`` layers, three
    passes."""
    return (PASSES * 2.0 * layer_macs_per_token(dm, t)["*"]["core"] * t
            * sequences * n_layers(dm, "*"))
