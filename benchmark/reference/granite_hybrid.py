"""Plain reference of the ``granite_hybrid`` architecture (granite-4.0-h-
micro, ``model_type`` ``granitemoehybrid`` with no experts) in float32
``jax.numpy`` at ``highest`` matmul precision.  Nothing here imports the
program; the parameter names are the program's, so the same seeded
weights feed both.  The state recurrence is ``reference/nemotron_h.py``'s
plain scan, a token a step.

Every layer: ``h = x + r * mixer(RMSNorm(x))``, ``y = h + r * W_out(
silu(u W_g) * u W_u)`` with ``u = RMSNorm(h)`` and ``[W_g | W_u]`` =
``input_linear``; ``r`` = ``residual_multiplier``; RMSNorm with
``rms_norm_eps``.  The input is ``embedding_multiplier * E[id]``, the
output ``RMSNorm(h) E^T / logits_scaling`` with the same ``E``, the
vocabulary slice held here.

- Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC = silu(causal depthwise
  conv(xBC) + b)``; ``x`` is ``H`` heads of ``P``, ``B`` and ``C`` are
  ``G`` groups of ``N`` (head ``i`` reads group ``i // (H/G)``), ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``H_t = exp(dt_t A)
  H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t``; ``out =
  W_out(RMSNorm_G(y * silu(z)) * w)``, the norm over each of the ``G``
  groups' channels (all 4,096 at one group).
- Attention: ``q = u W_q`` (``H`` heads of ``d / H``), ``k``, ``v`` (``G``
  heads), no positional encoding; ``softmax(attention_multiplier * q k^T
  + causal mask) v``, a block of queries at a time; then ``W_o``.
- Loss: the mean next-token cross-entropy over the counted positions.

Computed in blocks so that 8,192 tokens fit beside the float32 weights
and gradients, which changes no arithmetic: a sequence at a time,
``jax.checkpoint`` a layer, a block of queries, a block of the scan's
steps.  Every array takes its type from the parameters, so the same code
run on parameters cast to bfloat16 is the reference "in the nearest
precision below" that the first-step limits (``TOLERANCE``) are set
against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .nemotron_h import recurrence

HI = lax.Precision.HIGHEST
# 32 heads x 512 queries x 8,192 keys of float32 scores: 0.5 GB a block.
QUERY_BLOCK = 512
IGNORE = -1

# The first-step limits of this model (``runners/train_tok.py`` holds the
# program to them through ``reference_check_tok.py``), at 2 x 8,192 tokens
# and the published widths.  Two readings on the chip stand behind each
# (PERF.md, findings of PR 41): the system's (bf16 matrix products with
# float32 accumulation; float32 parameters, residual stream, dt, A, the
# scan's state and every norm's statistics) over nine seeds, and this
# reference run in bfloat16 throughout (``tools/tok_check_readings.py``)
# over three, which fails at least the five limits marked * on each of
# them.  The limits were set at the geometric middles between the control
# and a first form of the system that rounded the stream to bf16 after
# every branch (ten seeds: 0.0182-0.0184, 0.0181-0.0184, 0.0155-0.0163,
# 0.0194-0.0222, 0.029-0.056, 0.0158-0.0167 in the order below); a
# further seed, 767197033, crossed a limit, and the stream went to
# float32 with the limits as they were:
#   logits_rel *     all positions of the first sequence as one vector.
#                    System 0.01390-0.01413, bf16 0.02078-0.02160: 39%
#                    above the one and 6% below the other.
#   logits_rel_median *  the median over positions of a position's own
#                    error.  System 0.01386-0.01408, bf16 0.02073-0.02149.
#   momentum_rel *, update_rel *  all leaves as one vector (the update is
#                    -lr times the gradient on this step).  System
#                    0.01311-0.01380 and 0.01345-0.01421, bf16 0.01818-
#                    0.01894 both.
#   momentum_rel_thin *  the root mean square over the scan's own leaves
#                    (THIN_LEAVES) of a leaf's own error: the gradient of
#                    dt and A passes through the whole recurrence.  System
#                    0.01645-0.01963, bf16 0.0271-0.0637; 23% above the one
#                    and 12% below the other.
#   momentum_rel_worst  the worst single leaf, a dt_bias where it was
#                    named.  System 0.0227-0.0354, bf16 0.047-0.192: it
#                    separates nothing, and guards a fault in one layer's
#                    backward pass (which reads about 1 in that layer's
#                    leaves), between the reading and 1 with the more room
#                    above.
#   loss_abs         the loss, absolute.  System 6.7e-6 to 2.9e-5, bf16
#                    6.2e-6 to 4.6e-5: the precision does not move it, so
#                    it takes the accepted cells' limit and guards the
#                    formula (a sum for a mean, ignored positions counted).
TOLERANCE = {
    "loss_abs": 0.02,
    "logits_rel": 0.0196,
    "logits_rel_median": 0.0195,
    "momentum_rel": 0.0172,
    "momentum_rel_thin": 0.0242,
    "momentum_rel_worst": 0.15,
    "update_rel": 0.0174,
}
# The thin leaves: the scan's own parameters, whose gradient passes
# through the whole recurrence.
THIN_LEAVES = ("A_log", "dt_bias")


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    n = int(config["num_hidden_layers"])
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, s = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    return {
        "layers": n, "kinds": tuple(config["layer_types"][:n]),
        "d": d, "eps": float(config["rms_norm_eps"]),
        "h": h, "p": p, "g": g, "n": s, "d_inner": h * p,
        "conv_dim": h * p + 2 * g * s, "k": int(config["mamba_d_conv"]),
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads, "ff": int(config["shared_intermediate_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "vocab": int(v1) - int(v0),
    }


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def mamba(p, u, dm):
    """One sequence, ``u`` [T,D] (normed)."""
    t = u.shape[0]
    d_inner, g, n, k = dm["d_inner"], dm["g"], dm["n"], dm["k"]
    zxbcdt = mm(u, p["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + dm["conv_dim"]]
    dt = zxbcdt[:, d_inner + dm["conv_dim"]:]
    # Causal depthwise conv: tap k-1 multiplies the current position.
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + t] * p["conv_w"][i] for i in range(k))
                      + p["conv_b"])
    x = xbc[:, :d_inner].reshape(t, dm["h"], dm["p"])
    b = xbc[:, d_inner:d_inner + g * n].reshape(t, g, n)
    c = xbc[:, d_inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, p["D"])
    # The gate goes in BEFORE the norm; the norm's groups are the scan's.
    y = (y.reshape(t, d_inner) * jax.nn.silu(z)).reshape(t, g, d_inner // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + dm["eps"])
    return mm(y.reshape(t, d_inner) * p["gate_norm"], p["out_proj"])


def attention(p, u, dm):
    t = u.shape[0]
    hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    q = mm(u, p["q"]).reshape(t, hq, hd)
    k = jnp.repeat(mm(u, p["k"]).reshape(t, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat(mm(u, p["v"]).reshape(t, hkv, hd), hq // hkv, axis=1)

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        scores = jnp.einsum("qhd,shd->hqs", q_blk, k, precision=HI) \
            * dm["attention_multiplier"]
        qi = start + jnp.arange(q_blk.shape[0])[:, None]
        si = jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(si <= qi, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=HI)

    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (t + pad) // blk, blk, hq, hd)
    o = lax.map(block, (q_blocks, jnp.arange(0, t + pad, blk)))
    return mm(o.reshape(t + pad, hq * hd)[:t], p["o"])


def mlp(p, u, dm):
    gu = mm(u, p["input_linear"])
    return mm(jax.nn.silu(gu[:, :dm["ff"]]) * gu[:, dm["ff"]:],
              p["output_linear"])


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def forward_sequence(config: dict):
    """``apply(params, ids [T]) -> logits [T, V_held]``."""
    dm = dims(config)

    def apply(params, ids):
        x = dm["embedding_multiplier"] * params["embed"][ids]
        for i, kind in enumerate(dm["kinds"]):
            @jax.checkpoint
            def layer(p, x, kind=kind):
                mixer = mamba if kind == "mamba" else attention
                h = x + dm["residual"] * mixer(
                    p, rms_norm(x, p["norm1"], dm["eps"]), dm)
                return h + dm["residual"] * mlp(
                    p, rms_norm(h, p["norm2"], dm["eps"]), dm)

            x = layer(params["layers"][layer_name(i)], x)
        x = rms_norm(x, params["norm_f"], dm["eps"])
        return mm(x, params["embed"].T) / dm["logits_scaling"]

    return apply


def forward(config: dict):
    """``apply(params, state, ids [B,T]) -> (logits [B,T,V_held], state)``,
    a sequence at a time (the state is empty)."""
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
    del state  # empty
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
    """What ``flops_granite.py`` counts: the derived sizes."""
    return dims(config)
