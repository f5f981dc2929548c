"""Plain reference of the ``laguna`` architecture (Laguna-XS.2) in float32
``jax.numpy`` at ``highest`` matmul precision, written from the equations
of ISSUE 39.  Nothing here imports the program; the parameter names are
the program's, so the same seeded weights feed both.

No bias anywhere; RMSNorm with ``rms_norm_eps``.  A block is ``h = x +
A(RMSNorm(x))``, ``y = h + F(RMSNorm(h))``; after the last block a final
RMSNorm and an untied head over the vocabulary slice.

- A: ``q = u W_q`` (``H`` heads of the layer), ``k = u W_k``, ``v = u
  W_v`` (``G`` heads), each query head ``i`` against key-value head ``i //
  (H / G)``.  Rotary embedding with ``rotate_half`` over the first
  ``partial_rotary_factor * 128`` lanes of a head: a full layer's
  frequencies are YaRN's (written out below from the formula), cos and
  sin times ``attention_factor``; a window layer's the plain ones over all
  128 lanes.  A dense masked softmax of ``q . k / sqrt(128)``, a block of
  queries at a time against all keys: key ``s`` is seen by query ``t``
  where ``s <= t`` and, in a window layer, ``s > t - sliding_window``.
  Each head's result times ``sigmoid(u W_g)`` of that head, then ``W_o``.
- F: ``down(silu(u W_gate) * u W_up)`` of ``intermediate_size`` in a
  dense layer; else ``s = sigmoid(u W_r)`` over all ``router_experts``,
  the ``num_experts_per_tok`` largest by ``s``, their weights ``s`` over
  their sum times ``moe_routed_scaling_factor``, a plain scan over the
  experts HELD here, each on every token with a dense mask, plus the
  shared expert.  What experts held elsewhere would add is left out, as
  in the program.
- Loss: the mean next-token cross-entropy over the counted positions
  (the last position's target is ignored).

Computed in blocks so that 8,192 tokens fit beside the float32 weights
and gradients, which changes no arithmetic: a sequence at a time,
``jax.checkpoint`` a block of the network and a block of queries.

Every array takes its type from the parameters (the rotary tables are
made in float32 and cast), so the same code run on parameters cast to
bfloat16 is the reference "in the nearest precision below" that the
first-step limits (``TOLERANCE``) are set against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
# 64 heads x 256 queries x 8,192 keys of float32 scores: 0.5 GB a block.
QUERY_BLOCK = 256
IGNORE = -1

# The first-step limits of this model (``runners/train_tok.py`` holds the
# program to them through ``reference_check_tok.py``), at 2 x 8,192 tokens
# and the published widths.  Two readings on the chip stand behind each
# (PERF.md, findings of PR 39; my chip runs): the system's (bf16 matrix
# products with float32 accumulation; float32 parameters, router, gates'
# sigmoid, softmax, rotary embedding and norms) over eleven seeds, and
# this reference run in bfloat16 throughout (``tools/tok_check_readings.py``)
# over three, which fails every limit but ``loss_abs`` on each of them.
# With no recurrence and no second depth, every vector measure separates
# the two precisions here, by 1.1 to 1.6 times:
#   logits_rel       all positions of the first sequence as one vector.
#                    System 0.01430-0.01489, bf16 0.02151-0.02182; the
#                    geometric middle.
#   logits_rel_median  the median over positions of a position's own
#                    error.  System 0.007283-0.007306, bf16 0.008299-
#                    0.008348: what bf16 statistics cost the tokens whose
#                    experts did not change; the geometric middle, 6.6% of
#                    room against a spread of 0.3%.
#   momentum_rel, update_rel  all leaves as one vector (the update is -lr
#                    times the gradient on this step).  System 0.00620-
#                    0.00677, bf16 0.01063-0.01139; 23% of room above the
#                    system, 28% below the control.
#   momentum_rel_thin  the root mean square over the thin leaves
#                    (THIN_LEAVES) of a leaf's own error.  System 0.0239-
#                    0.0392, bf16 0.0578-0.0655: the routers carry it and
#                    swing with the seed's changed tokens, so the limit
#                    leaves the system 38% and the control 7%.
#   momentum_rel_worst  the worst single leaf, a router's on every seed.
#                    System 0.0375-0.0887, bf16 0.1073-0.1536: as above,
#                    13% of room above the system, 7% below the control.
#   loss_abs         the loss, absolute.  System 2.2e-5 to 2.0e-4, bf16
#                    5.9e-5 to 3.4e-4: the precision does not move it, so
#                    it takes the accepted cells' limit (a hundred times
#                    the reading) and guards the formula.
TOLERANCE = {
    "loss_abs": 0.02,
    "logits_rel": 0.0178,
    "logits_rel_median": 0.00779,
    "momentum_rel": 0.0083,
    "momentum_rel_thin": 0.054,
    "momentum_rel_worst": 0.10,
    "update_rel": 0.0083,
}
# The thin leaves: each layer's per-head gate (a row of 48 or 64 a
# position, through the sigmoid and every head's result) and each router
# (through the choice of experts).
THIN_LEAVES = ("head_gate", "router")


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    n = int(config["num_hidden_layers"])
    router = int(config.get("router_experts", config["num_experts"]))
    first, count = config.get("experts_held", (0, router))
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    return {
        "d": int(config["hidden_size"]), "eps": float(config["rms_norm_eps"]),
        "layers": n,
        "full": tuple(k == "full_attention"
                      for k in config["layer_types"][:n]),
        "dense": tuple(k == "dense" for k in config["mlp_layer_types"][:n]),
        "heads": tuple(int(h) for h in
                       config["num_attention_heads_per_layer"][:n]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "window": int(config["sliding_window"]),
        "rope_full": config["rope_parameters"]["full_attention"],
        "rope_window": config["rope_parameters"]["sliding_attention"],
        "ff": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["shared_expert_intermediate_size"]),
        "router": router, "first": int(first), "count": int(count),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["moe_routed_scaling_factor"]),
        "vocab": int(v1) - int(v0),
    }


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


# -- rotary embedding ----------------------------------------------------------------

def yarn_inv_freq(rp: dict, dim: int):
    """transformers' ``_compute_yarn_parameters``, transcribed: inverse
    frequencies over ``dim`` rotated lanes and the attention factor."""
    base, factor = rp["rope_theta"], rp["factor"]
    orig = rp["original_max_position_embeddings"]

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1 - ramp
    inv_freq = interpolation * (1 - keep) + extrapolation * keep
    attention_factor = rp.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(attention_factor)


def inv_freq_of(rp: dict, hd: int):
    """``(rotated lanes, inverse frequencies, factor on cos and sin)``."""
    dim = int(hd * rp.get("partial_rotary_factor", 1.0))
    if rp.get("rope_type", "default") == "yarn":
        inv_freq, att = yarn_inv_freq(rp, dim)
    else:
        inv_freq = 1.0 / rp["rope_theta"] ** (
            np.arange(0, dim, 2, dtype=np.float64) / dim)
        att = 1.0
    return dim, inv_freq, att


def rotate(x, rp: dict):
    """``x`` [T, heads, hd]: ``rotate_half`` rotary over the first ``dim``
    lanes of each head, the rest passed through unrotated."""
    t, hd = x.shape[0], x.shape[-1]
    dim, inv_freq, att = inv_freq_of(rp, hd)
    freqs = np.arange(t, dtype=np.float32)[:, None] \
        * inv_freq.astype(np.float32)[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)          # [T, dim]
    cos = jnp.asarray((np.cos(emb) * att)[:, None, :], x.dtype)
    sin = jnp.asarray((np.sin(emb) * att)[:, None, :], x.dtype)
    x_rot, x_pass = x[..., :dim], x[..., dim:]
    x1, x2 = x_rot[..., :dim // 2], x_rot[..., dim // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate([x_rot * cos + rotated * sin, x_pass], axis=-1)


# -- attention ----------------------------------------------------------------------

def attention(p, u, full: bool, dm):
    t, g, hd = u.shape[0], dm["kv_heads"], dm["hd"]
    heads = p["head_gate"].shape[1]
    rp = dm["rope_full"] if full else dm["rope_window"]
    q = rotate(mm(u, p["q"]).reshape(t, heads, hd), rp)
    k = rotate(mm(u, p["k"]).reshape(t, g, hd), rp)
    v = mm(u, p["v"]).reshape(t, g, hd)
    k = jnp.repeat(k, heads // g, axis=1)                 # [T, H, hd]
    v = jnp.repeat(v, heads // g, axis=1)
    window = None if full else dm["window"]

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        scores = jnp.einsum("qhd,shd->hqs", q_blk, k, precision=HI) \
            / math.sqrt(hd)
        qi = start + jnp.arange(q_blk.shape[0])[:, None]
        si = jnp.arange(t)[None, :]
        seen = si <= qi
        if window is not None:
            seen = seen & (si > qi - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=HI)

    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (t + pad) // blk, blk, heads, hd)
    o = lax.map(block, (q_blocks, jnp.arange(0, t + pad, blk)))
    o = o.reshape(t + pad, heads, hd)[:t]
    gate = jax.nn.sigmoid(mm(u, p["head_gate"]))           # [T, H]
    return mm((o * gate[..., None]).reshape(t, heads * hd), p["o"])


# -- experts ---------------------------------------------------------------------------

def route(p, u, dm):
    """Dense routing weights [T, router]: zero but for the ``top_k``
    chosen experts of each token."""
    s = jax.nn.sigmoid(mm(u, p["router"]))
    w, idx = lax.top_k(s, dm["top_k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True) * dm["scale"]
    onehot = idx[..., None] == jnp.arange(dm["router"])
    return jnp.sum(jnp.where(onehot, w[..., None], 0), axis=1)


def experts(p, u, dm):
    """The shared expert plus each expert held here, no others, on every
    token, weighted by its routing weight (zero where not chosen): a scan
    over the held experts, each under its own checkpoint, so that 32 of
    them compile as one and keep nothing of their own for the backward."""
    held = route(p, u, dm)[:, dm["first"]:dm["first"] + dm["count"]]

    @jax.checkpoint
    def one(y, expert):
        gate, up, down, w = expert
        return y + w[:, None] * swiglu(u, gate, up, down), None

    y = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    return lax.scan(one, y, (p["gate"], p["up"], p["down"], held.T))[0]


# -- the network ----------------------------------------------------------------------------

def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def network_block(p, x, full: bool, dense: bool, dm):
    @jax.checkpoint
    def run(p, x):
        h = x + attention(p, rms_norm(x, p["norm1"], dm["eps"]), full, dm)
        u = rms_norm(h, p["norm2"], dm["eps"])
        if dense:
            return h + swiglu(u, p["gate"], p["up"], p["down"])
        return h + experts(p, u, dm)
    return run(p, x)


def forward_sequence(config: dict):
    """``apply(params, ids [T]) -> logits [T, V_held]``."""
    dm = dims(config)

    def apply(params, ids):
        x = params["embed"][ids]
        for i in range(dm["layers"]):
            x = network_block(params["layers"][layer_name(i)], x,
                              dm["full"][i], dm["dense"][i], dm)
        return mm(rms_norm(x, params["norm_f"], dm["eps"]), params["head"])

    return apply


def forward(config: dict):
    """``apply(params, state, ids [B,T]) -> (logits [B,T,V_held], state)``,
    a sequence at a time (the state holds nothing the reference reads)."""
    one = forward_sequence(config)

    def apply(params, state, ids):
        return jnp.stack([one(params, row) for row in ids]), state

    return apply


def cross_entropy_sum(logits, targets):
    """Sum of the loss over the positions whose target is not ``IGNORE``
    (float32 whatever the logits' type)."""
    logits = logits.astype(jnp.float32)
    valid = targets != IGNORE
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0))


def loss_and_grads(config: dict, params, state, ids, targets):
    """The mean loss over the batch's counted positions and its gradient,
    a sequence at a time, and the first sequence's logits at the one
    prediction depth: ``(loss, grads, logits0 [1,T,V])``."""
    del state  # zero correction bias and counters: nothing to read
    one = forward_sequence(config)
    count = np.float32((np.asarray(targets) != IGNORE).sum())

    @jax.jit
    def seq(params, row, tgt, count):
        # Everything a seed decides is an argument: one compiled program
        # serves every seed (and the compile cache of the next run).
        def f(p):
            logits = one(p, row)
            return cross_entropy_sum(logits, tgt) / count, logits
        (part, logits), g = jax.value_and_grad(f, has_aux=True)(params)
        return part, g, logits

    # Summed on the host: the device holds one sequence's gradients.
    loss, grads, logits0 = 0.0, None, None
    for b in range(ids.shape[0]):
        part, g, logits = jax.device_get(seq(params, ids[b], targets[b],
                                             count))
        loss = loss + float(part)
        grads = g if grads is None else jax.tree_util.tree_map(
            lambda a, c: a + c, grads, g)
        if b == 0:
            logits0 = logits[None]
    return loss, grads, logits0


def layer_shapes(config: dict) -> dict:
    """What ``flops_laguna.py`` counts: the derived sizes."""
    return dims(config)
