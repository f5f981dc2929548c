"""``laguna``: the expert language model of Laguna-XS.2 (``model_type``
``laguna``), built from a configuration file (the keys of the published
``config.json`` plus the share this chip holds: ``experts_held``,
``vocab_held``).  The contract is every model's — ``apply(params, state,
x, *, train, rng, compute_dtype)`` — with ``x`` token ids ``i32[B,T]``.

Every block is ``h = x + A(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``, no
bias anywhere; after the last block a final RMSNorm and an untied head.
What kind a layer is comes from the file, a layer at a time:
``layer_types`` (full or window attention), ``mlp_layer_types`` (dense or
experts) and ``num_attention_heads_per_layer``.

- **Attention** ``A``: ``H`` query heads over ``num_key_value_heads``
  key-value heads of ``head_dim``, each query head with the key-value head
  of its group of ``R = H / G``.  Rotary embedding in the half-split
  (``rotate_half``) convention over the first ``partial_rotary_factor``
  of a head's lanes, the rest passing through; each layer kind has its
  own ``rope_parameters``: ``default`` frequencies, or YaRN's blend of
  frequencies kept and frequencies divided by ``factor`` with cosine and
  sine scaled by ``attention_factor`` (:func:`rope_frequencies`).  The
  core is causal in a full layer and over a query's last
  ``sliding_window`` keys, itself counted, in a window layer:
  ``ops/attention.py:causal_gqa`` where ``kernel_applies`` says so, else
  the blocked XLA loop of ``ops/seq.py``.  A per-head gate
  ``sigmoid(h W_g)`` (``gating``) multiplies each head's result before
  the output projection.
- **F**: SwiGLU of ``intermediate_size`` in a dense layer; else the
  expert layer of models/moe.py with three-matrix SwiGLU experts of
  ``moe_intermediate_size`` and a shared expert of
  ``shared_expert_intermediate_size``: a sigmoid router over ALL
  ``router_experts``, the ``num_experts_per_tok`` largest chosen, their
  weights normalised and scaled by ``moe_routed_scaling_factor``; this
  chip computes the shared expert and the chosen experts it holds.

Precision: parameters float32; matrix products in ``compute_dtype`` with
float32 accumulation; the router, the gate's sigmoid, softmax statistics,
the rotary angles and rotation, and every norm's statistics in float32.

State: per expert layer the router's ``e_bias`` (zeros: the file names no
correction bias) and the counters ``assignments``, ``dropped``,
``live_tiles`` and ``buffer_tiles`` (models/moe.py).  :data:`TRACED`
tallies the layers traced by kind and which path took each kind of core.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import attention, seq
from ..ops.layers import linear
from ..ops.seq import rms_norm
from . import moe

F32 = jnp.float32
ATTN_QUERY_BLOCK = 1024
# ``layer_types``' names (and ``rope_parameters``' keys) -> the core's
# kind, which names its scope.
KINDS = {"full_attention": "full", "sliding_attention": "swa"}

TRACED = {"dense": 0, "expert": 0, "full_kernel": 0, "full_xla": 0,
          "swa_kernel": 0, "swa_xla": 0}


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    n = int(config["num_hidden_layers"])
    router = int(config.get("router_experts", config["num_experts"]))
    first, count = config.get("experts_held", (0, router))
    if int(count) != int(config["num_experts"]):
        raise ValueError(f"num_experts counts the experts held here "
                         f"({config['num_experts']}), experts_held says "
                         f"{count}")
    if not 0 <= int(first) <= int(first) + int(count) <= router:
        raise ValueError(f"experts_held {first, count} lies outside the "
                         f"router's {router} experts")
    per_layer = [config[k] for k in ("layer_types", "mlp_layer_types",
                                     "num_attention_heads_per_layer")]
    if any(len(v) != n for v in per_layer):
        raise ValueError(f"the per-layer lists do not hold {n} layers")
    if config["gating"] not in (True, "per-head") \
            or config["moe_apply_router_weight_on_input"] \
            or config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("laguna knows a per-head output gate, the routing "
                         "weight on the expert's output, no bias and an "
                         "untied head; the file asks for another")
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    return {
        "d": int(config["hidden_size"]), "eps": float(config["rms_norm_eps"]),
        "layers": n,
        "kinds": tuple(KINDS[k] for k in per_layer[0]),
        "dense": tuple(m == "dense" for m in per_layer[1]),
        "heads": tuple(int(h) for h in per_layer[2]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "window": int(config["sliding_window"]),
        "rope": {kind: config["rope_parameters"][name]
                 for name, kind in KINDS.items()},
        "ff": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["shared_expert_intermediate_size"]),
        "router": router, "first": int(first), "count": int(count),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["moe_routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "vocab": int(v1) - int(v0),
        "init_std": float(config.get("initializer_range", 0.02)),
        "remat": config.get("remat", "block"),
    }


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


# -- rotary embedding ----------------------------------------------------------------

def rope_frequencies(rp: dict, hd: int) -> Tuple[np.ndarray, float]:
    """``(inverse frequencies [rot/2], factor on cos and sin)`` of one
    layer kind's ``rope_parameters`` over ``rot = partial_rotary_factor *
    hd`` lanes: pair ``j`` turns by ``theta^(-2j/rot)`` a position; under
    ``yarn`` pairs below the correction range's low end keep that, pairs
    above its high end divide it by ``factor``, a linear ramp between,
    and cos and sin are scaled by ``attention_factor``."""
    rot = int(hd * rp.get("partial_rotary_factor", 1))
    base = float(rp["rope_theta"])
    freq = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return freq, 1.0
    if kind != "yarn":
        raise ValueError(f"laguna knows default and yarn rotary, not {kind}")
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def edge(turns):  # the pair that turns ``turns`` times over ``orig``
        return rot * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(edge(rp["beta_fast"])), 0)
    high = min(math.ceil(edge(rp["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / ((high - low) or 0.001),
                   0, 1)
    return (freq / factor * ramp + freq * (1 - ramp),
            float(rp.get("attention_factor", 0.1 * math.log(factor) + 1)))


def rope_tables(t: int, rp: dict, hd: int):
    """``(cos, sin)`` float32 ``[t, rot/2]``, the factor included."""
    freq, mult = rope_frequencies(rp, hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(freq, F32)[None]
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def rope(x, cos, sin, out_dtype):
    """Rotate the first ``2 * cos.shape[1]`` lanes of each head of ``x``
    ``[B,T,H,hd]`` in float32, halves ``(a, b)`` to ``(a cos - b sin,
    b cos + a sin)``; the other lanes pass through."""
    half = cos.shape[1]
    a, b = (x[..., i * half:(i + 1) * half].astype(F32) for i in (0, 1))
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [(a * c - b * s).astype(out_dtype), (b * c + a * s).astype(out_dtype),
         x[..., 2 * half:].astype(out_dtype)], axis=-1)


# -- attention ---------------------------------------------------------------------

def _attend_head(q, k, v, *, scale: float, cd, window: Optional[int]):
    """The XLA loop where the kernel does not apply (ops/seq.py)."""
    return seq.attend_head(q, k, v, scale=scale, cd=cd,
                           block=ATTN_QUERY_BLOCK, window=window)


def gated_attention(p, h, kind: str, dm: dict, cd, tables):
    """``h`` [B,T,d] (normed) -> the layer's attention result [B,T,d]."""
    bsz, t, _ = h.shape
    g, hd = dm["kv_heads"], dm["hd"]
    heads = p["head_gate"].shape[1]
    r = heads // g
    cos, sin = tables[kind]
    with jax.named_scope("gated_proj"):
        q = rope(linear(h, p["q"].astype(cd)).reshape(bsz, t, heads, hd),
                 cos, sin, cd)
        k = rope(linear(h, p["k"].astype(cd)).reshape(bsz, t, g, hd),
                 cos, sin, cd)
        v = linear(h, p["v"].astype(cd)).reshape(bsz, t, g, hd)
        gate = jax.nn.sigmoid(jnp.matmul(h, p["head_gate"].astype(cd),
                                         preferred_element_type=F32))
        # Query head j*R + i reads key-value head j.
        qkv = (q.reshape(bsz, t, g, r, hd).transpose(0, 2, 3, 1, 4)
               .reshape(bsz * g, r, t, hd),
               k.transpose(0, 2, 1, 3).reshape(bsz * g, t, hd),
               v.transpose(0, 2, 1, 3).reshape(bsz * g, t, hd))
    window = dm["window"] if kind == "swa" else None
    with jax.named_scope(f"{kind}_core"):
        scale = 1.0 / math.sqrt(hd)
        if attention.kernel_applies(t, hd, jnp.dtype(cd).itemsize):
            TRACED[f"{kind}_kernel"] += 1
            o = attention.causal_gqa(*qkv, scale, window=window)
        else:
            TRACED[f"{kind}_xla"] += 1
            o = lax.map(lambda a: _attend_head(*a, scale=scale, cd=cd,
                                               window=window), qkv)
    with jax.named_scope("gated_proj"):
        o = o.reshape(bsz, g, r, t, hd).transpose(0, 3, 1, 2, 4).reshape(
            bsz, t, heads, hd)
        o = (o.astype(F32) * gate[..., None]).astype(cd)
        return linear(o.reshape(bsz, t, heads * hd), p["o"].astype(cd))


# -- the blocks ------------------------------------------------------------------------

def dense_mlp(p, u, cd):
    """SwiGLU of ``intermediate_size``: the experts' form, whole."""
    with jax.named_scope("dense_mlp"):
        return moe.SWIGLU.fn(linear, u,
                             lambda i: p[moe.SWIGLU.routed[i]].astype(cd))


def _block(kind: str, dense: bool, p, st, x, tables, dm: dict, cd,
           train: bool):
    h = x + gated_attention(p, rms_norm(x, p["norm1"], dm["eps"], cd), kind,
                            dm, cd, tables)
    u = rms_norm(h, p["norm2"], dm["eps"], cd)
    if dense:
        return h + dense_mlp(p, u, cd), st
    y, st = moe.expert_layer(p, st, u, dm, cd, train=train, form=moe.SWIGLU)
    return h + y, st


def build(config: dict):
    """``(init, apply, (vocabulary held, sequence length))`` for one
    configuration."""
    dm = dims(config)

    def init(key) -> Tuple[Dict, Dict]:
        d, std, hd, g = dm["d"], dm["init_std"], dm["hd"], dm["kv_heads"]
        keys = iter(jax.random.split(key, 16 * (dm["layers"] + 1)))

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, F32)

        def block(i: int):
            h, e, f = dm["heads"][i], dm["expert"], dm["shared"]
            p = {"norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32),
                 "q": normal((d, h * hd)), "k": normal((d, g * hd)),
                 "v": normal((d, g * hd)), "o": normal((h * hd, d)),
                 "head_gate": normal((d, h))}
            if dm["dense"][i]:
                p.update(gate=normal((d, dm["ff"])), up=normal((d, dm["ff"])),
                         down=normal((dm["ff"], d)))
                return p, None
            p.update(router=normal((d, dm["router"])),
                     shared_gate=normal((d, f)), shared_up=normal((d, f)),
                     shared_down=normal((f, d)),
                     gate=normal((dm["count"], d, e)),
                     up=normal((dm["count"], d, e)),
                     down=normal((dm["count"], e, d)))
            return p, {"e_bias": jnp.zeros((dm["router"],), F32),
                       "assignments": jnp.zeros((dm["count"],), jnp.int32),
                       "dropped": jnp.zeros((), jnp.int32),
                       "live_tiles": jnp.zeros((), jnp.int32),
                       "buffer_tiles": jnp.zeros((), jnp.int32)}

        layers, state = {}, {}
        for i in range(dm["layers"]):
            layers[layer_name(i)], st = block(i)
            if st is not None:
                state[layer_name(i)] = st
        # Embeddings at the residual stream's scale, as nemotron_h's and
        # glm4_moe_lite's (PERF.md, findings of PR 28 and 35); matrices at
        # initializer_range.
        return {"embed": normal((dm["vocab"], d), 1.0), "layers": layers,
                "norm_f": jnp.ones((d,), F32),
                "head": normal((d, dm["vocab"]))}, state

    def apply(params, state, x, *, train: bool = False,
              rng: Optional[jax.Array] = None, compute_dtype=None):
        del rng  # no dropout
        if not jnp.issubdtype(x.dtype, jnp.integer) or x.ndim != 2:
            raise ValueError(f"laguna takes token ids i32[B,T], got "
                             f"{x.dtype}{list(x.shape)}")
        cd = compute_dtype or F32
        tables = {kind: rope_tables(x.shape[1], dm["rope"][kind], dm["hd"])
                  for kind in sorted(set(dm["kinds"]))}
        new_state = dict(state)
        h = params["embed"][x].astype(cd)
        for i in range(dm["layers"]):
            name, dense = layer_name(i), dm["dense"][i]
            TRACED["dense" if dense else "expert"] += 1
            block = functools.partial(_block, dm["kinds"][i], dense, dm=dm,
                                      cd=cd, train=train)
            if dm["remat"] == "block":
                block = jax.checkpoint(block)
            h, st = block(params["layers"][name], state.get(name), h, tables)
            if st is not None:
                new_state[name] = st
        with jax.named_scope("lm_head"):
            h = rms_norm(h, params["norm_f"], dm["eps"], cd)
            logits = jnp.matmul(h, params["head"].astype(cd),
                                preferred_element_type=F32)
        return logits, new_state

    # seq_len is the training context the CLI's synthetic data takes; the
    # model itself runs at any length (0: the file gives none).
    return init, apply, (dm["vocab"], int(config.get("seq_len", 0)))
