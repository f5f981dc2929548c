"""Operations a ``glm4_moe_lite`` training step needs, from its layer
sizes alone (the yardstick of the ``glm4_moe_lite`` cells; nothing of the
program is read).

``dm`` is what the configuration's reference lists
(``reference/glm4_moe_lite.py:layer_shapes``).  Counted per token of a
sequence of ``t`` tokens, one multiply-accumulate as two operations, at
their least: latent attention's five projections; its core over the
causal half (a key costs a head ``qk`` multiply-adds for the score and
``v`` for the value: 512 at the published widths); the dense layer's
three products; an expert layer's router, shared expert and the routed
experts held here at uniform routing (``top_k * count / router``
assignments a token, three matrices an expert); the prediction module's
``W_eh`` and its expert block; the head over the vocabulary slice once a
prediction depth.  A training step is three passes (forward, and backward
for the input and for the weights); nothing recomputed is counted, and
neither are the elementwise layers (norms, rotary embedding, gates, the
update).
"""
from __future__ import annotations

PASSES = 3


def block_macs_per_token(dm: dict, t: int) -> dict:
    """``{part: multiply-accumulates a token}`` of the parts a block is
    made of, and of ``w_eh`` and ``head`` (one depth's)."""
    d, h = dm["d"], dm["heads"]
    routed_share = dm["top_k"] * dm["count"] / dm["router"]
    return {
        "mla_proj": (d * dm["q_rank"] + dm["q_rank"] * h * dm["qk"]
                     + d * (dm["kv_rank"] + dm["rope"])
                     + dm["kv_rank"] * h * (dm["nope"] + dm["v"])
                     + h * dm["v"] * d),
        # scores and values, each over the t/2 keys of the causal half
        "mla_core": h * (dm["qk"] + dm["v"]) * t / 2,
        "dense": 3 * d * dm["ff"],
        "router": d * dm["router"],
        "shared": 3 * d * dm["shared"],
        "routed": routed_share * 3 * d * dm["expert"],
        "w_eh": 2 * d * d,
        "head": d * dm["vocab"],
    }


def macs_per_token(dm: dict, t: int) -> dict:
    """``{part: multiply-accumulates a token}`` of the whole model held:
    ``first_dense`` dense blocks, the expert blocks after them, and the
    prediction module (its own expert block, ``W_eh``, a second head)."""
    m = block_macs_per_token(dm, t)
    blocks = dm["layers"] + dm["mtp"]
    expert_blocks = blocks - dm["first_dense"]
    return {
        "mla_proj": blocks * m["mla_proj"],
        "mla_core": blocks * m["mla_core"],
        "dense": dm["first_dense"] * m["dense"],
        "experts": expert_blocks * (m["router"] + m["shared"] + m["routed"]),
        "w_eh": dm["mtp"] * m["w_eh"],
        "head": (1 + dm["mtp"]) * m["head"],
    }


def train_flops_per_sequence(dm: dict, t: int) -> float:
    """Forward and backward operations for one sequence of ``t`` tokens:
    the numerator of ``train_mfu_pct`` (a sample is a sequence)."""
    return PASSES * 2.0 * sum(macs_per_token(dm, t).values()) * t


def mla_core_train_flops(dm: dict, t: int, sequences: float) -> float:
    """Scores and values over the causal half, every block held (the
    module's too), three passes."""
    return PASSES * 2.0 * macs_per_token(dm, t)["mla_core"] * t * sequences


def expert_train_flops(dm: dict, assignments: float) -> float:
    """The routed experts' operations for that many assignments (counted
    by the program, over all expert blocks), three matrices an expert,
    three passes."""
    return PASSES * 2.0 * 3 * dm["d"] * dm["expert"] * assignments
