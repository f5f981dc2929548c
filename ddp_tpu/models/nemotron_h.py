"""``nemotron_h``: the hybrid Mamba-2 / attention / expert language model
of NVIDIA-Nemotron-3-Nano-30B-A3B, built from a configuration file (the
keys of the published ``config.json`` plus the share this chip holds).

Unlike the other models this one is no module of constants:
:func:`build` closes ``init``/``apply`` over a configuration dict.  The
contract is every model's — ``apply(params, state, x, *, train, rng,
compute_dtype) -> (logits, state)`` — with ``x`` token ids ``i32[B,T]``
and float32 logits ``[B,T,V_held]``.

Each block is ``x + mixer(RMSNorm(x))``, one mixer a block by the
character of ``hybrid_override_pattern``:

- ``M``, a Mamba-2 mixer in the chunked form: products inside chunks of
  ``chunk_size`` tokens and a recurrence over the chunks' states: the
  blocked kernels of ops/ssd.py where they apply (a TPU, whole chunks and
  lanes), else plain XLA with a ``lax.scan`` over the chunks; its causal
  convolution through the kernel pair of ops/causal_conv.py where that
  applies (``causal_conv.TRACED`` tallies the path), else plain XLA.
- ``*``, grouped-query causal attention without positional encoding:
  the blocked kernel of ops/attention.py where it applies (a TPU, whole
  blocks), else a block of queries at a time against the keys before it.
- ``E``, a sigmoid router over ALL ``router_experts`` experts, the
  ``num_experts_per_tok`` largest ``s + b`` chosen; this chip computes
  the shared expert and the weighted results of the chosen experts it
  holds (``experts_held = [first, count]``), a tile of rows at a time over
  the row tiles, sorted by expert, that hold a row (so the layer's time
  follows the held experts' load): the layer of models/moe.py, which
  ``glm4_moe_lite`` shares, with this model's expert (two matrices around
  ``relu2``).  Dropless while the held experts' load is within
  ``moe.MOE_LOAD_HEADROOM`` times what uniform routing sends here;
  ``dropped`` counts the rest.

Precision: parameters float32; matrix products in ``compute_dtype`` with
float32 accumulation; the router, the softmax, ``dt``, ``A``, the state
recurrence over chunks and every norm's statistics in float32.

The model's state pytree holds, per expert layer, the router's
``e_bias`` (``e_score_correction_bias``: state, not trained) and three
integer counters, ``assignments`` (to each held expert), ``dropped`` and
``live_tiles`` (row tiles that held a row: the tiles multiplied), which
a training step adds to (train/step.py sums integer state over
replicas; the Trainer exports them where it flushes losses).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, seq, ssd
from ..ops.layers import linear
from ..ops.seq import rms_norm
from . import moe

F32 = jnp.float32
ATTN_QUERY_BLOCK = 1024


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    router = int(config.get("router_experts", config["n_routed_experts"]))
    first, count = config.get("experts_held", (0, router))
    if int(count) != int(config["n_routed_experts"]):
        raise ValueError(
            f"n_routed_experts counts the experts held here "
            f"({config['n_routed_experts']}), experts_held says {count}")
    if not 0 <= int(first) <= int(first) + int(count) <= router:
        raise ValueError(f"experts_held {first, count} lies outside the "
                         f"router's {router} experts")
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    published = config.get("published", {})
    return {
        "pattern": config["hybrid_override_pattern"],
        "d": int(config["hidden_size"]), "eps": float(config["norm_eps"]),
        "h": h, "p": p, "g": g, "n": n, "d_inner": h * p,
        "conv_dim": h * p + 2 * g * n, "k": int(config["conv_kernel"]),
        "chunk": int(config["chunk_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "router": router, "first": int(first), "count": int(count),
        "top_k": int(config["num_experts_per_tok"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["moe_shared_expert_intermediate_size"]),
        "scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "vocab": int(v1) - int(v0),
        "init_std": float(config.get("initializer_range", 0.02)),
        "remat": config.get("remat", "block"),
        # rescale_prenorm_residual divides by the PUBLISHED depth.
        "depth": int(published.get("num_hidden_layers",
                                   config["num_hidden_layers"])),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
    }


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


# -- M: Mamba-2 ---------------------------------------------------------------

def chunk_state_scan(states, decay):
    """The recurrence over chunks, in float32: ``H_c = decay_c H_{c-1} +
    S_c`` from ``H_{-1} = 0``; returns the state ENTERING each chunk.
    ``states`` [nc,B,H,P,N], ``decay`` [nc,B,H]."""
    def step(h_prev, inp):
        s_c, d_c = inp
        return d_c[..., None, None] * h_prev + s_c, h_prev

    states = states.astype(F32)
    # The zero state is made from the data so that, under shard_map, it
    # varies over the mesh as the carry it becomes does.
    _, entering = lax.scan(step, states[0] * 0.0,
                           (states, decay.astype(F32)))
    return entering


def gated_norm(y, z, weight, groups: int, eps: float, out_dtype):
    """``GroupRMSNorm(y * silu(z))``: the gate goes in before the norm."""
    y = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shape = y.shape
    y = y.reshape(*shape[:-1], groups, shape[-1] // groups)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(shape) * weight).astype(out_dtype)


def ssd_chunked(x, dt, a, b, c, d_skip, chunk: int, cd):
    """``y_t = H_t C_t + D x_t`` with ``H_t = exp(dt_t A) H_{t-1} + dt_t
    x_t (x) B_t``, in chunks: inside a chunk the products of the decay-
    weighted ``C B^T`` with ``x``; between chunks the chunk states
    through :func:`chunk_state_scan`.  ``x`` [B,T,H,P], ``dt`` [B,T,H]
    float32, ``a`` [H], ``b``/``c`` [B,T,G,N]; float32 result [B,T,H,P].
    A ragged last chunk is padded with ``dt = 0``, which leaves the state
    as it is."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    nc, q = (t + pad) // chunk, chunk
    xc = x.reshape(bsz, nc, q, h, p)
    dtc = dt.reshape(bsz, nc, q, h)
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)

    cs = jnp.cumsum(dtc * a, axis=2)            # [B,nc,Q,H], <= 0
    cs_h = cs.transpose(0, 1, 3, 2)             # [B,nc,H,Q]
    # Inside a chunk: M[i,j] = (C_i.B_j) exp(cs_i - cs_j) dt_j for j <= i.
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc, bc,
                    preferred_element_type=F32)
    seg = cs_h[..., :, None] - cs_h[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    m = (decay * dtc.transpose(0, 1, 3, 2)[..., None, :]).reshape(
        bsz, nc, g, r, q, q) * cb[:, :, :, None]
    y = jnp.einsum("bcgrqk,bckgrp->bcqgrp", m.astype(cd),
                   xc.reshape(bsz, nc, q, g, r, p),
                   preferred_element_type=F32)
    # A chunk's own state: sum_j exp(cs_last - cs_j) dt_j x_j (x) B_j.
    to_end = jnp.exp(cs[:, :, -1:, :] - cs) * dtc
    xw = (xc * to_end[..., None]).astype(cd).reshape(bsz, nc, q, g, r, p)
    states = jnp.einsum("bckgrp,bckgn->bcgrpn", xw, bc,
                        preferred_element_type=F32)
    entering = chunk_state_scan(
        states.reshape(bsz, nc, h, p, n).transpose(1, 0, 2, 3, 4),
        jnp.exp(cs[:, :, -1, :]).transpose(1, 0, 2))
    entering = entering.transpose(1, 0, 2, 3, 4).reshape(
        bsz, nc, g, r, p, n)
    y_in = jnp.einsum("bcqgn,bcgrpn->bcqgrp", cc, entering.astype(cd),
                      preferred_element_type=F32)
    y = y + y_in * jnp.exp(cs).reshape(bsz, nc, q, g, r)[..., None]
    y = y.reshape(bsz, nc * q, h, p) \
        + d_skip[:, None] * x.astype(F32)
    return y[:, :t]


def mamba_params(dm: dict, normal, uniform, out_std: float) -> dict:
    """A Mamba-2 mixer's parameters, as mamba_ssm initialises them:
    ``normal(shape[, std])`` and ``uniform(shape, lo, hi)`` draw the next
    key; matrices normal, the output's at ``out_std``, the convolution
    uniform within ``1/sqrt(k)``, ``dt_bias`` the inverse softplus of a
    log-uniform ``dt`` in ``[dt_min, dt_max]`` (floored), ``A`` uniform in
    ``[1, 16]``, ``D`` and the norm's weight 1."""
    d, h, bound = dm["d"], dm["h"], 1.0 / math.sqrt(dm["k"])
    dt = jnp.exp(uniform((h,), math.log(dm["dt_min"]),
                         math.log(dm["dt_max"])))
    dt = jnp.maximum(dt, dm["dt_floor"])
    return dict(
        in_proj=normal((d, 2 * dm["d_inner"] + 2 * dm["g"] * dm["n"] + h)),
        conv_w=uniform((dm["k"], dm["conv_dim"]), -bound, bound),
        conv_b=uniform((dm["conv_dim"],), -bound, bound),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.log(uniform((h,), 1.0, 16.0)),
        D=jnp.ones((h,), F32),
        gate_norm=jnp.ones((dm["d_inner"],), F32),
        out_proj=normal((dm["d_inner"], d), out_std))


def mamba_mixer(p, x, dm: dict, cd):
    bsz, t, _ = x.shape
    d_inner, g, n = dm["d_inner"], dm["g"], dm["n"]
    with jax.named_scope("ssm_proj"):
        zxbcdt = linear(x, p["in_proj"].astype(cd))
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + dm["conv_dim"]]
    dt = zxbcdt[..., d_inner + dm["conv_dim"]:]
    with jax.named_scope("ssm_conv"):
        xbc = seq.causal_conv_silu(xbc, p["conv_w"], p["conv_b"], cd)
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :d_inner].reshape(bsz, t, dm["h"], dm["p"])
        b = xbc[..., d_inner:d_inner + g * n].reshape(bsz, t, g, n)
        c = xbc[..., d_inner + g * n:].reshape(bsz, t, g, n)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"])
        # The blocked kernels where the shapes and the backend allow it
        # (ops/ssd.py: a chunk's decay matrices and the running state stay
        # in VMEM, and the gate and the norm, whose groups are the scan's,
        # are applied there); else the chunked form in plain XLA.
        if ssd.kernel_applies(t, dm["h"], dm["p"], g, n, dm["chunk"],
                              jnp.dtype(cd).itemsize):
            ssd.TRACED["kernel"] += 1
            y = ssd.ssd_scan(xs, dt, a, b, c, p["D"], z, p["gate_norm"],
                             dm["chunk"], dm["eps"])
        else:
            ssd.TRACED["xla"] += 1
            y = ssd_chunked(xs, dt, a, b, c, p["D"], dm["chunk"], cd)
            y = gated_norm(y.reshape(bsz, t, d_inner), z, p["gate_norm"],
                           g, dm["eps"], cd)
    with jax.named_scope("ssm_proj"):
        return linear(y, p["out_proj"].astype(cd))


# -- *: attention -------------------------------------------------------------

def _attend_head(q, k, v, *, scale: float, cd):
    """The XLA loop where the kernel does not apply: a block of
    ``ATTN_QUERY_BLOCK`` queries at a time, keys-first (ops/seq.py)."""
    return seq.attend_head(q, k, v, scale=scale, cd=cd,
                           block=ATTN_QUERY_BLOCK)


def attention_mixer(p, x, dm: dict, cd):
    bsz, t, _ = x.shape
    hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    with jax.named_scope("attn_proj"):
        q = linear(x, p["q"].astype(cd)).reshape(
            bsz, t, hkv, hq // hkv, hd).transpose(0, 2, 3, 1, 4)
        k = linear(x, p["k"].astype(cd)).reshape(
            bsz, t, hkv, hd).transpose(0, 2, 1, 3)
        v = linear(x, p["v"].astype(cd)).reshape(
            bsz, t, hkv, hd).transpose(0, 2, 1, 3)
    with jax.named_scope("attn_core"):
        # A model with its own scale of the scores names it in ``dims``.
        scale = dm.get("attn_scale", 1.0 / math.sqrt(hd))
        qkv = (q.reshape(bsz * hkv, hq // hkv, t, hd),
               k.reshape(bsz * hkv, t, hd), v.reshape(bsz * hkv, t, hd))
        # The blocked kernel where the shapes and the backend allow it
        # (ops/attention.py: a tile's scores stay in VMEM); else the XLA
        # loop, one (sequence, key-value head) pair at a time: a batched
        # product over a batch of 2 x 2 is what XLA:TPU lowers to a
        # dilated convolution (1.4% of the roofline on the chip: PERF.md,
        # findings of PR 28).
        if attention.kernel_applies(t, hd, jnp.dtype(cd).itemsize):
            attention.TRACED["kernel"] += 1
            o = attention.causal_gqa(*qkv, scale)
        else:
            attention.TRACED["xla"] += 1
            o = lax.map(lambda a: _attend_head(*a, scale=scale, cd=cd), qkv)
        o = o.reshape(bsz, hkv, hq // hkv, t, hd).transpose(
            0, 3, 1, 2, 4).reshape(bsz, t, hq * hd)
    with jax.named_scope("attn_proj"):
        return linear(o, p["o"].astype(cd))


# -- E: experts ---------------------------------------------------------------

def expert_mixer(p, st, x, dm: dict, cd, *, train: bool):
    """The expert layer the token models share (models/moe.py), with this
    model's expert: two matrices around ``relu2``."""
    return moe.expert_layer(p, st, x, dm, cd, train=train, form=moe.RELU2)


# -- the network ---------------------------------------------------------------

def _block(kind: str, p, st, x, dm: dict, cd, train: bool):
    h = rms_norm(x, p["norm"], dm["eps"], cd)
    if kind == "M":
        return x + mamba_mixer(p, h, dm, cd), st
    if kind == "*":
        return x + attention_mixer(p, h, dm, cd), st
    y, st = expert_mixer(p, st, h, dm, cd, train=train)
    return x + y, st


def build(config: dict):
    """``(init, apply, (vocabulary held, sequence length))`` for one
    configuration."""
    dm = dims(config)
    bad = set(dm["pattern"]) - set("M*E")
    if bad:
        raise ValueError(f"hybrid_override_pattern holds {sorted(bad)}; "
                         "known mixers are M, * and E")
    if len(dm["pattern"]) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")

    def init(key) -> Tuple[Dict, Dict]:
        d, std = dm["d"], dm["init_std"]
        out_std = std / math.sqrt(dm["depth"])
        keys = iter(jax.random.split(key, 16 * len(dm["pattern"]) + 4))

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, F32)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, F32, lo, hi)

        layers, state = {}, {}
        for i, kind in enumerate(dm["pattern"]):
            p = {"norm": jnp.ones((d,), F32)}
            if kind == "M":
                p.update(mamba_params(dm, normal, uniform, out_std))
            elif kind == "*":
                hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
                p.update(q=normal((d, hq * hd)), k=normal((d, hkv * hd)),
                         v=normal((d, hkv * hd)), o=normal((hq * hd, d)))
            else:
                p.update(
                    router=normal((d, dm["router"])),
                    shared_up=normal((d, dm["shared"])),
                    shared_down=normal((dm["shared"], d), out_std),
                    up=normal((dm["count"], d, dm["expert"])),
                    down=normal((dm["count"], dm["expert"], d), out_std))
                state[layer_name(i)] = {
                    # Seeded non-zero, so that leaving it out of the
                    # choice (or putting it into the weights) shows.
                    "e_bias": normal((dm["router"],), 0.05),
                    "assignments": jnp.zeros((dm["count"],), jnp.int32),
                    "dropped": jnp.zeros((), jnp.int32),
                    "live_tiles": jnp.zeros((), jnp.int32),
                    "buffer_tiles": jnp.zeros((), jnp.int32)}
            layers[layer_name(i)] = p
        # Embeddings at the residual stream's scale (torch's nn.Embedding
        # default): at 0.02 a token's identity is drowned by the first
        # mixers' outputs, and within ten steps of SGD the deeper routers
        # send every token to the same experts (PERF.md, findings of PR 28).
        params = {"embed": normal((dm["vocab"], d), 1.0), "layers": layers,
                  "norm_f": jnp.ones((d,), F32),
                  "head": normal((d, dm["vocab"]))}
        return params, state

    def apply(params, state, x, *, train: bool = False,
              rng: Optional[jax.Array] = None, compute_dtype=None):
        del rng  # no dropout
        if not jnp.issubdtype(x.dtype, jnp.integer) or x.ndim != 2:
            raise ValueError(f"nemotron_h takes token ids i32[B,T], got "
                             f"{x.dtype}{list(x.shape)}")
        cd = compute_dtype or F32
        h = params["embed"][x].astype(cd)
        new_state = dict(state)
        for i, kind in enumerate(dm["pattern"]):
            name = layer_name(i)
            block = functools.partial(_block, kind, dm=dm, cd=cd,
                                      train=train)
            if dm["remat"] == "block":
                block = jax.checkpoint(block)
            h, st = block(params["layers"][name], state.get(name), h)
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
