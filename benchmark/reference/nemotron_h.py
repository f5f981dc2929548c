"""Plain reference of the ``nemotron_h`` architecture (NVIDIA-Nemotron-3-
Nano-30B-A3B-BF16, ``model_type`` ``nemotron_h``): one mixer a block by
the pattern character, ``x + mixer(RMSNorm(x))``, a final RMSNorm and an
untied head, in float32 ``jax.numpy`` at ``highest`` matmul precision.
Nothing here imports the program; the parameter names are the program's,
so the same seeded weights feed both.

- ``M``, Mamba-2: the state recurrence as a plain ``lax.scan`` over time,
  one step a token (not the chunked form the program runs).
- ``*``, attention: a masked softmax over all earlier positions, a block
  of queries at a time.  No positional encoding: the ``nemotron_h``
  attention module applies none.
- ``E``, experts: sigmoid router over ALL experts, the ``top_k`` largest
  ``s + b`` chosen, weights ``s`` over their sum times the scaling
  factor; a loop over the experts HELD here with a dense mask, plus the
  shared expert.  What experts held elsewhere would add is left out, as
  in the program (``experts_held``; the head and the embedding hold
  ``vocab_held`` rows).

It is computed in blocks so that it fits at the published sizes, which
changes no arithmetic: a sequence at a time, ``jax.checkpoint`` a block
of the network, a block of queries, and a block of ``SCAN_BLOCK`` time
steps (an unblocked 8,192-step scan would keep 8,192 states of 2.1 MB in
its backward pass).

Every array takes its type from the parameters, so the same code run on
parameters cast to bfloat16 is the reference "in the nearest precision
below" that the first-step check's limits are set against
(``reference_check_lm.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
SCAN_BLOCK = 128
QUERY_BLOCK = 512
IGNORE = -1


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    h = int(config["mamba_num_heads"])
    p = int(config["mamba_head_dim"])
    g = int(config["n_groups"])
    n = int(config["ssm_state_size"])
    router = int(config.get("router_experts", config["n_routed_experts"]))
    first, count = config.get("experts_held", (0, router))
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    return {
        "pattern": config["hybrid_override_pattern"],
        "d": int(config["hidden_size"]), "eps": float(config["norm_eps"]),
        "h": h, "p": p, "g": g, "n": n, "d_inner": h * p,
        "conv_dim": h * p + 2 * g * n, "k": int(config["conv_kernel"]),
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
    }


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


# -- M: Mamba-2 ---------------------------------------------------------------

def recurrence(x, dt, a, b, c, d_skip):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t
    + D x_t``, a token a step.  ``x`` [T,H,P], ``dt`` [T,H], ``a`` [H],
    ``b``/``c`` [T,G,N] (head ``h`` reads group ``h // (H/G)``)."""
    t, h, p = x.shape
    rep = h // b.shape[1]
    n = b.shape[2]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)
        c_h = jnp.repeat(c_t, rep, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y_t = jnp.sum(state * c_h[:, None, :], axis=-1) \
            + d_skip[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, inp):
        return lax.scan(step, state, inp)

    # The same scan, cut into blocks of SCAN_BLOCK steps so that its
    # backward pass keeps a state a block, not a state a step.  A ragged
    # last block is filled with dt = 0 and x = 0, which leave the state
    # as it is.
    pad = -t % SCAN_BLOCK
    blocks = tuple(
        jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
            (t + pad) // SCAN_BLOCK, SCAN_BLOCK, *v.shape[1:])
        for v in (x, dt, b, c))
    _, y = lax.scan(block, jnp.zeros((h, p, n), x.dtype), blocks)
    return y.reshape(t + pad, h, p)[:t]


def mamba(p, x, dm):
    """One sequence, ``x`` [T,D]."""
    t = x.shape[0]
    d_inner, g, n, k = dm["d_inner"], dm["g"], dm["n"], dm["k"]
    zxbcdt = mm(x, p["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + dm["conv_dim"]]
    dt = zxbcdt[:, d_inner + dm["conv_dim"]:]
    # Causal depthwise conv: tap k-1 multiplies the current position.
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = sum(padded[i:i + t] * p["conv_w"][i] for i in range(k)) \
        + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :d_inner].reshape(t, dm["h"], dm["p"])
    b = xbc[:, d_inner:d_inner + g * n].reshape(t, g, n)
    c = xbc[:, d_inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y = recurrence(xs, dt, a, b, c, p["D"]).reshape(t, d_inner)
    # Gated norm: the gate goes in BEFORE the norm, groups of d_inner/G.
    y = y * jax.nn.silu(z)
    y = y.reshape(t, g, d_inner // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + dm["eps"])
    y = y.reshape(t, d_inner) * p["gate_norm"]
    return mm(y, p["out_proj"])


# -- *: attention -------------------------------------------------------------

def attention(p, x, dm):
    t = x.shape[0]
    hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    q = mm(x, p["q"]).reshape(t, hq, hd)
    k = jnp.repeat(mm(x, p["k"]).reshape(t, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(mm(x, p["v"]).reshape(t, hkv, hd), hq // hkv, axis=1)

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        scores = jnp.einsum("qhd,shd->hqs", q_blk, k, precision=HI) \
            / math.sqrt(hd)
        qi = start + jnp.arange(q_blk.shape[0])[:, None]
        si = jnp.arange(t)[None, :]
        scores = jnp.where(si <= qi, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=HI)

    # A block of queries at a time against all the keys, those past a
    # query's own position masked; a ragged last block is filled with
    # queries that are thrown away.
    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (t + pad) // blk, blk, hq, hd)
    out = lax.map(block, (q_blocks, jnp.arange(0, t + pad, blk)))
    return mm(out.reshape(t + pad, hq * hd)[:t], p["o"])


# -- E: experts ---------------------------------------------------------------

def route(p, e_bias, x, dm):
    """Dense routing weights [T, router]: zero but for the ``top_k``
    chosen experts of each token."""
    s = jax.nn.sigmoid(mm(x, p["router"]))
    _, idx = lax.top_k(s + e_bias, dm["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dm["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * dm["scale"]
    onehot = idx[..., None] == jnp.arange(dm["router"])
    return jnp.sum(jnp.where(onehot, w[..., None], 0), axis=1)


def experts(p, e_bias, x, dm):
    weights = route(p, e_bias, x, dm)
    y = mm(relu2(mm(x, p["shared_up"])), p["shared_down"])
    for j in range(dm["count"]):  # the experts held here, no others
        w_j = weights[:, dm["first"] + j][:, None]
        y = y + w_j * mm(relu2(mm(x, p["up"][j])), p["down"][j])
    return y


# -- the network ---------------------------------------------------------------

def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def forward_sequence(config: dict):
    """``apply(params, state, ids [T]) -> logits [T, V_held]``."""
    dm = dims(config)

    def apply(params, state, ids):
        x = params["embed"][ids]
        for i, kind in enumerate(dm["pattern"]):
            name = layer_name(i)

            @jax.checkpoint
            def block(p, x, kind=kind, name=name):
                h = rms_norm(x, p["norm"], dm["eps"])
                if kind == "M":
                    return x + mamba(p, h, dm)
                if kind == "*":
                    return x + attention(p, h, dm)
                if kind == "E":
                    return x + experts(p, state[name]["e_bias"], h, dm)
                raise ValueError(f"unknown layer kind {kind!r}")

            x = block(params["layers"][name], x)
        x = rms_norm(x, params["norm_f"], dm["eps"])
        return mm(x, params["head"])

    return apply


def forward(config: dict):
    """``apply(params, state, ids [B,T]) -> (logits [B,T,V_held], state)``,
    a sequence at a time."""
    one = forward_sequence(config)

    def apply(params, state, ids):
        return jnp.stack([one(params, state, row) for row in ids]), state

    return apply


def cross_entropy_sum(logits, targets):
    """Sum of the next-token loss over the positions whose target is not
    ``IGNORE`` (float32 whatever the logits' type), and their count."""
    logits = logits.astype(jnp.float32)
    valid = targets != IGNORE
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0)), jnp.sum(valid)


def loss_and_grads(config: dict, params, state, ids, targets):
    """Mean loss over the batch's valid positions and its gradient, a
    sequence at a time (the sum of the per-sequence sums over the count),
    and the first sequence's logits: ``(loss, grads, logits0)``."""
    one = forward_sequence(config)
    count = int((targets != IGNORE).sum())

    @jax.jit
    def seq(params, state, row, tgt, count):
        # Everything a seed decides is an argument: one compiled program
        # serves every seed (and the compile cache of the next run).
        def f(p):
            logits = one(p, state, row)
            return cross_entropy_sum(logits, tgt)[0] / count, logits
        (part, logits), g = jax.value_and_grad(f, has_aux=True)(params)
        return part, g, logits

    # Summed on the host: the device holds one sequence's gradients.
    loss, grads, logits0 = 0.0, None, None
    for b in range(ids.shape[0]):
        part, g, logits = jax.device_get(
            seq(params, state, ids[b], targets[b], np.float32(count)))
        loss = loss + float(part)
        grads = g if grads is None else jax.tree_util.tree_map(
            lambda a, c: a + c, grads, g)
        if b == 0:
            logits0 = logits
    return loss, grads, logits0


def layer_shapes(config: dict) -> dict:
    """What ``flops_seq.py`` counts: the derived sizes."""
    return dims(config)
