"""``glm4_moe_lite``: the latent-attention expert language model of
GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``), built from a
configuration file (the keys of the published ``config.json`` plus the
share this chip holds: ``experts_held``, ``vocab_held``).  The contract
is every model's — ``apply(params, state, x, *, train, rng,
compute_dtype)`` — with ``x`` token ids ``i32[B,T]``.

Every block is ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
no bias anywhere; after the last block a final RMSNorm and an untied head.

- **MLA**, multi-head latent attention in its training form: the queries
  through a latent of ``q_lora_rank`` with its own norm, keys and values
  through one of ``kv_lora_rank`` with its own; a head's query and key are
  a part without position (``qk_nope_head_dim``) beside a rotary part
  (``qk_rope_head_dim``), and the key's rotary part is ONE vector a
  token, straight from the input, that every head sees.  Keys and values
  are materialised a head; the core is ``ops/attention.py:causal_gqa``
  (every query head its own key-value head) where ``kernel_applies`` says
  so, else the blocked XLA loop of ``ops/seq.py``.
- **FFN**: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them the expert layer of
  models/moe.py with three-matrix SwiGLU experts: a sigmoid router over
  ALL ``router_experts``, the ``num_experts_per_tok`` largest ``s + b``,
  weights normalised and scaled; this chip computes the shared expert and
  the chosen experts it holds.
- **Multi-token prediction** (``num_nextn_predict_layers`` 1; DeepSeek-V3's
  module): at position ``i``, ``W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)]`` with ``h_i`` the main model's output after its final
  norm, one more expert block, its own final norm, and the main model's
  embedding and head; it predicts ``t_{i+2}``.  From the ids alone
  ``Emb(t_{i+1})`` is the ids moved one position earlier; the last
  position has no next token (its own id stands in) and its label is
  ignored by the loss.

With ``train=True`` and a prediction module the model yields a
:class:`~ddp_tpu.ops.losses.DepthLogits` (both depths' outputs, the one
head, the weights 1 and ``mtp_loss_weight``): the loss core takes a
depth's head and loss at a time.  Otherwise float32 logits ``[B,T,V_held]``
of the main model.

Precision: parameters float32; matrix products in ``compute_dtype`` with
float32 accumulation; the router, softmax statistics, the rotary angles
and rotation, and every norm's statistics in float32.

State: per expert layer (and the module, ``mtp``) the router's ``e_bias``
and the counters ``assignments``, ``dropped`` and ``live_tiles``
(models/moe.py), and
``lm_loss``, the float leaf the loss core fills with each depth's loss.
:data:`TRACED` tallies the layers traced by kind and which path took the
attention core.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, seq
from ..ops.layers import linear
from ..ops.seq import rms_norm
from ..ops.losses import LM_LOSS, DepthLogits
from . import moe

F32 = jnp.float32
ATTN_QUERY_BLOCK = 1024

TRACED = {"dense": 0, "expert": 0, "mtp": 0, "core_kernel": 0, "core_xla": 0}


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    router = int(config.get("router_experts", config["n_routed_experts"]))
    first, count = config.get("experts_held", (0, router))
    if int(count) != int(config["n_routed_experts"]):
        raise ValueError(
            f"n_routed_experts counts the experts held here "
            f"({config['n_routed_experts']}), experts_held says {count}")
    if not 0 <= int(first) <= int(first) + int(count) <= router:
        raise ValueError(f"experts_held {first, count} lies outside the "
                         f"router's {router} experts")
    if config.get("rope_scaling") is not None \
            or config.get("partial_rotary_factor", 1) != 1:
        raise ValueError("glm4_moe_lite rotates all of qk_rope_head_dim "
                         "without scaling; the file asks for more")
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    nope, rope = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]))
    return {
        "d": int(config["hidden_size"]), "eps": float(config["rms_norm_eps"]),
        "layers": int(config["num_hidden_layers"]),
        "first_dense": int(config["first_k_dense_replace"]),
        "mtp": int(config.get("num_nextn_predict_layers", 0)),
        "mtp_weight": float(config.get("mtp_loss_weight", 0.3)),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": nope, "rope": rope, "qk": nope + rope,
        "v": int(config["v_head_dim"]),
        "theta": float(config["rope_theta"]),
        "ff": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "router": router, "first": int(first), "count": int(count),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "vocab": int(v1) - int(v0),
        "init_std": float(config.get("initializer_range", 0.02)),
        "remat": config.get("remat", "block"),
    }


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


# -- rotary embedding ----------------------------------------------------------------

def rope_angles(t: int, width: int, theta: float):
    """``(cos, sin)`` float32 ``[t, width / 2]``: position ``i`` turns
    pair ``j`` by ``i * theta ** (-2 j / width)``."""
    freq = theta ** (-jnp.arange(0, width, 2, dtype=F32) / width)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin, out_dtype):
    """Rotate the interleaved pairs ``(x[2j], x[2j+1])`` of ``x`` ``[B,T,
    ..., width]`` in float32.  The result holds the pairs' first elements,
    then their second (``[even' | odd']``): queries and keys both come
    through here, and a dot product does not see a permutation applied to
    both."""
    pairs = x.astype(F32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([a * c - b * s, a * s + b * c],
                           axis=-1).astype(out_dtype)


# -- MLA -----------------------------------------------------------------------------

def mla_heads(p, x, dm: dict, cd, cos, sin):
    """The heads' queries, keys and values, ``[B,H,T,qk]``, ``[B,H,T,qk]``
    and ``[B,H,T,v]``: the five projections' first four, the two latent
    norms, the rotary embedding and the assembly."""
    bsz, t, _ = x.shape
    h, nope, rp = dm["heads"], dm["nope"], dm["rope"]
    c_q = rms_norm(linear(x, p["q_a"].astype(cd)), p["q_norm"], dm["eps"],
                   cd)
    q = linear(c_q, p["q_b"].astype(cd)).reshape(bsz, t, h, dm["qk"])
    kv_a = linear(x, p["kv_a"].astype(cd))
    c_kv = rms_norm(kv_a[..., :dm["kv_rank"]], p["kv_norm"], dm["eps"], cd)
    # ONE rotary key a token, from the input and past the latent's norm.
    k_pe = rope(kv_a[..., dm["kv_rank"]:], cos, sin, cd)
    kv = linear(c_kv, p["kv_b"].astype(cd)).reshape(bsz, t, h,
                                                    nope + dm["v"])
    q_h = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], cos, sin, cd)], axis=-1)
    k_h = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (bsz, t, h, rp))], axis=-1)
    return tuple(a.transpose(0, 2, 1, 3)
                 for a in (q_h, k_h, kv[..., nope:]))


def mla(p, x, dm: dict, cd, cos, sin):
    bsz, t, _ = x.shape
    h, qk, vd = dm["heads"], dm["qk"], dm["v"]
    with jax.named_scope("mla_proj"):
        q, k, v = mla_heads(p, x, dm, cd, cos, sin)
    with jax.named_scope("mla_core"):
        scale = 1.0 / math.sqrt(qk)
        # The blocked kernel where the shapes and the backend allow it
        # (every query head is its own pair: R = 1); else the XLA loop,
        # a (sequence, head) pair at a time.
        if qk == vd and attention.kernel_applies(t, qk,
                                                 jnp.dtype(cd).itemsize):
            TRACED["core_kernel"] += 1
            o = attention.causal_gqa(
                q.reshape(bsz * h, 1, t, qk), k.reshape(bsz * h, t, qk),
                v.reshape(bsz * h, t, vd), scale)
        else:
            TRACED["core_xla"] += 1
            o = lax.map(
                lambda a: seq.attend_head(*a, scale=scale, cd=cd,
                                          block=ATTN_QUERY_BLOCK),
                (q.reshape(bsz * h, 1, t, qk), k.reshape(bsz * h, t, qk),
                 v.reshape(bsz * h, t, vd)))
        o = o.reshape(bsz, h, t, vd).transpose(0, 2, 1, 3).reshape(
            bsz, t, h * vd)
    with jax.named_scope("mla_proj"):
        return linear(o, p["o"].astype(cd))


# -- the blocks ------------------------------------------------------------------------

def dense_mlp(p, u, cd):
    with jax.named_scope("dense_mlp"):
        return linear(jax.nn.silu(linear(u, p["gate"].astype(cd)))
                      * linear(u, p["up"].astype(cd)), p["down"].astype(cd))


def _block(dense: bool, p, st, x, cos, sin, dm: dict, cd, train: bool):
    h = x + mla(p, rms_norm(x, p["norm1"], dm["eps"], cd), dm, cd, cos, sin)
    u = rms_norm(h, p["norm2"], dm["eps"], cd)
    if dense:
        return h + dense_mlp(p, u, cd), st
    y, st = moe.expert_layer(p, st, u, dm, cd, train=train, form=moe.SWIGLU)
    return h + y, st


def build(config: dict):
    """``(init, apply, (vocabulary held, sequence length))`` for one
    configuration."""
    dm = dims(config)
    if dm["mtp"] not in (0, 1):
        raise ValueError("glm4_moe_lite knows one prediction module "
                         f"(num_nextn_predict_layers {dm['mtp']})")

    def init(key) -> Tuple[Dict, Dict]:
        d, std = dm["d"], dm["init_std"]
        keys = iter(jax.random.split(key, 16 * (dm["layers"] + 2)))

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, F32)

        def block(dense: bool):
            h, e = dm["heads"], dm["expert"]
            p = {"norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32),
                 "q_a": normal((d, dm["q_rank"])),
                 "q_norm": jnp.ones((dm["q_rank"],), F32),
                 "q_b": normal((dm["q_rank"], h * dm["qk"])),
                 "kv_a": normal((d, dm["kv_rank"] + dm["rope"])),
                 "kv_norm": jnp.ones((dm["kv_rank"],), F32),
                 "kv_b": normal((dm["kv_rank"],
                                 h * (dm["nope"] + dm["v"]))),
                 "o": normal((h * dm["v"], d))}
            if dense:
                p.update(gate=normal((d, dm["ff"])),
                         up=normal((d, dm["ff"])),
                         down=normal((dm["ff"], d)))
                return p, None
            p.update(router=normal((d, dm["router"])),
                     shared_gate=normal((d, dm["shared"])),
                     shared_up=normal((d, dm["shared"])),
                     shared_down=normal((dm["shared"], d)),
                     gate=normal((dm["count"], d, e)),
                     up=normal((dm["count"], d, e)),
                     down=normal((dm["count"], e, d)))
            return p, {
                # Seeded non-zero, so that leaving it out of the choice (or
                # putting it into the weights) shows.
                "e_bias": normal((dm["router"],), 0.05),
                "assignments": jnp.zeros((dm["count"],), jnp.int32),
                "dropped": jnp.zeros((), jnp.int32),
                "live_tiles": jnp.zeros((), jnp.int32),
                "buffer_tiles": jnp.zeros((), jnp.int32)}

        layers, state = {}, {}
        for i in range(dm["layers"]):
            layers[layer_name(i)], st = block(i < dm["first_dense"])
            if st is not None:
                state[layer_name(i)] = st
        # Embeddings at the residual stream's scale, as nemotron_h's: at
        # initializer_range the held experts' load drifts to 2.6-3.9 times
        # the mean within 64 steps against 1.6-3.4 (PERF.md, findings of
        # PR 35).  Matrices at initializer_range.
        params = {"embed": normal((dm["vocab"], d), 1.0),
                  "layers": layers, "norm_f": jnp.ones((d,), F32),
                  "head": normal((d, dm["vocab"]))}
        if dm["mtp"]:
            blk, state["mtp"] = block(False)
            params["mtp"] = {"enorm": jnp.ones((d,), F32),
                             "hnorm": jnp.ones((d,), F32),
                             "eh_proj": normal((2 * d, d)), "block": blk,
                             "norm_f": jnp.ones((d,), F32)}
            state[LM_LOSS] = jnp.zeros((1 + dm["mtp"],), F32)
        return params, state

    def apply(params, state, x, *, train: bool = False,
              rng: Optional[jax.Array] = None, compute_dtype=None):
        del rng  # no dropout
        if not jnp.issubdtype(x.dtype, jnp.integer) or x.ndim != 2:
            raise ValueError(f"glm4_moe_lite takes token ids i32[B,T], got "
                             f"{x.dtype}{list(x.shape)}")
        cd = compute_dtype or F32
        cos, sin = rope_angles(x.shape[1], dm["rope"], dm["theta"])
        new_state = dict(state)

        def run(dense: bool, p, st, h):
            block = functools.partial(_block, dense, dm=dm, cd=cd,
                                      train=train)
            if dm["remat"] == "block":
                block = jax.checkpoint(block)
            return block(p, st, h, cos, sin)

        h = params["embed"][x].astype(cd)
        for i in range(dm["layers"]):
            name, dense = layer_name(i), i < dm["first_dense"]
            TRACED["dense" if dense else "expert"] += 1
            h, st = run(dense, params["layers"][name], state.get(name), h)
            if st is not None:
                new_state[name] = st
        with jax.named_scope("lm_head"):
            h = rms_norm(h, params["norm_f"], dm["eps"], cd)
        if not (train and dm["mtp"]):
            with jax.named_scope("lm_head"):
                logits = jnp.matmul(h, params["head"].astype(cd),
                                    preferred_element_type=F32)
            return logits, new_state
        TRACED["mtp"] += 1
        m = params["mtp"]
        with jax.named_scope("mtp"):
            # Emb(t_{i+1}): the ids one position earlier; the last position
            # has no next token, its own id stands in and its label (the
            # loss core's shift) is ignored.
            nxt = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
            e = rms_norm(params["embed"][nxt].astype(cd), m["enorm"],
                         dm["eps"], cd)
            h2 = linear(jnp.concatenate(
                [e, rms_norm(h, m["hnorm"], dm["eps"], cd)], axis=-1),
                m["eh_proj"].astype(cd))
        h2, new_state["mtp"] = run(False, m["block"], state["mtp"], h2)
        with jax.named_scope("mtp"):
            h2 = rms_norm(h2, m["norm_f"], dm["eps"], cd)
        with jax.named_scope("lm_head"):
            head = params["head"].astype(cd)
        return DepthLogits((h, h2), head, (1.0, dm["mtp_weight"])), new_state

    # seq_len is the training context the CLI's synthetic data takes; the
    # model itself runs at any length (0: the file gives none).
    return init, apply, (dm["vocab"], int(config.get("seq_len", 0)))
