"""Plain reference of the ``glm4_moe_lite`` architecture (GLM-4.7-Flash)
in float32 ``jax.numpy`` at ``highest`` matmul precision, written from
the equations of ISSUE 35.  Nothing here imports the program; the
parameter names are the program's, so the same seeded weights feed both.

No bias anywhere; RMSNorm with ``rms_norm_eps``.  A block is ``h = x +
MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; after the last block a
final RMSNorm and an untied head.

- MLA: ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` split a head into
  ``q_nope | q_pe``; ``u W_kva`` splits ``c_kv | k_pe``; ``kv =
  RMSNorm(c_kv) W_kvb`` split a head into ``k_nope | v``.  Rotary
  embedding (interleaved pairs, all of ``qk_rope_head_dim``) on ``q_pe`` a
  head and on ``k_pe``, ONE vector a token for all heads.  A dense masked
  softmax of ``[q_nope | rope(q_pe)] . [k_nope | rope(k_pe)] /
  sqrt(qk)``, a block of queries at a time against all keys.
- FFN: ``down(silu(gate(u)) * up(u))`` of ``intermediate_size`` in the
  first ``first_k_dense_replace`` layers; after them ``s = sigmoid(u
  W_r)``, the ``top_k`` largest ``s + b``, weights ``s`` over their sum
  times the scaling factor, a plain loop over the experts HELD here with
  a dense mask, plus the shared expert.  What experts held elsewhere
  would add is left out, as in the program.
- Multi-token prediction: ``h' = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)]``, ``h_i`` the main model's output after its final
  norm; one more expert block; its own final norm; the main model's head.
  Scored against the targets moved one position earlier; the last
  position reads its own id and its label is ignored.  ``L = L_main +
  mtp_loss_weight L_mtp``, each a mean over its own counted positions.

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
QUERY_BLOCK = 512
IGNORE = -1

# The first-step limits of this model (``runners/train_tok.py`` holds the
# program to them through ``reference_check_tok.py``), at 2 x 8,192 tokens
# and the published widths.  Two readings on the chip stand behind each
# (PERF.md, findings of PR 35; my chip runs): the system's (bf16 matrix
# products with float32 accumulation; float32 parameters, router,
# softmax, rotary embedding and norms) and this reference run in bfloat16
# throughout (``tools/tok_check_readings.py``), which fails BOTH limits
# marked * on every seed read.  A GUARD is a limit the control passes on
# some seed: it catches a fault, not a precision.
#   logits_rel_median *, logits_rel_median_d1 *   the median over the
#                    first sequence's positions of a position's own
#                    relative error, main model and prediction module.
#                    System 0.008164 to 0.008205 and 0.007361 to 0.007413
#                    over twelve seeds (it hardly moves), bf16 reference
#                    0.009010 to 0.009065 and 0.008368 to 0.008399 (four): what
#                    bf16 softmax statistics, norms, rotary tables and
#                    router cost the tokens whose experts did not change.
#                    Each limit is the geometric middle of the two sides:
#                    4.8% and 6.2% of room, against a spread under 0.8%.
#   logits_rel, logits_rel_d1   GUARDS: all positions as one vector.
#                    System 0.0182 to 0.0257 and 0.0169 to 0.0219 over
#                    seventeen seeds, bf16 reference 0.0262 to 0.0364 and
#                    0.0253 to 0.0306 over eight: a token whose score
#                    crosses the top-4 boundary under bf16 operands takes
#                    another expert and is far off on BOTH sides, so the
#                    vector's error swings with the seed by more than the
#                    sides lie apart (the median above does not see them).
#                    Twice the largest reading.
#   momentum_rel_thin   GUARD: the root mean square over the thin leaves
#                    (THIN_LEAVES) of a leaf's own relative error.  System
#                    0.0193 to 0.0356, bf16 reference 0.0302 to 0.0725:
#                    the routers carry it, and they move with the seed's
#                    changed tokens.  It guards ONE thin leaf's backward
#                    pass (a leaf of two dozen at 1 reads 0.2).
#   momentum_rel_worst  GUARD: the worst single leaf, always a router's
#                    or a routed expert's.  System 0.058 to 0.143, bf16
#                    reference 0.088 to 0.343.  Between the reading and 1
#                    (one layer's backward pass at fault), the more room
#                    above.
#   momentum_rel, update_rel   GUARDS: all leaves as one vector (the
#                    update is -lr times the gradient on this step).
#                    System 0.0084 to 0.0183, bf16 reference 0.0130 to
#                    0.0446; between the reading and 1 (a state left
#                    unchanged), the more room above: the rate and the
#                    sign.
#   loss_abs         the weighted loss of both depths, absolute.  System
#                    2e-5 to 4.5e-4, bf16 reference 1e-5 to 5e-4: the
#                    precision does not move it, so it takes the accepted
#                    cells' limit (forty times the reading) and guards the
#                    formula (the weight 0.3, each depth's own count).
TOLERANCE = {
    "loss_abs": 0.02,
    "logits_rel": 0.05,
    "logits_rel_median": 0.0086,
    "logits_rel_d1": 0.05,
    "logits_rel_median_d1": 0.00787,
    "momentum_rel": 0.1,
    "momentum_rel_thin": 0.1,
    "momentum_rel_worst": 0.4,
    "update_rel": 0.1,
}
# The thin leaves, which stand where a scan model's A_log and dt_bias
# stood: each latent's down-projection and norm take their gradient
# through the latent's norm and the whole attention core, and a router's
# through the choice of experts.  On the chip the latents' leaves read
# 1.2 to 1.4 times further off in bfloat16 than in the system and the
# routers' 2 to 2.7 times, but a router's own error swings by 3 between
# seeds (PERF.md, findings of PR 35), so they are held as a guard and the
# precision is held by the logits' median.
THIN_LEAVES = ("q_a", "kv_a", "q_norm", "kv_norm", "router")


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    router = int(config.get("router_experts", config["n_routed_experts"]))
    first, count = config.get("experts_held", (0, router))
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
        "v": int(config["v_head_dim"]), "theta": float(config["rope_theta"]),
        "ff": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "router": router, "first": int(first), "count": int(count),
        "top_k": int(config["num_experts_per_tok"]),
        "scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "vocab": int(v1) - int(v0),
    }


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


# -- MLA ------------------------------------------------------------------------------

def rotate(x, theta: float):
    """Rotary embedding of ``x`` ``[T, ..., w]``: the interleaved pair
    ``(x[2j], x[2j+1])`` at position ``i`` turns by ``i theta^(-2j/w)``.
    The layout stays interleaved."""
    t, w = x.shape[0], x.shape[-1]
    freq = theta ** (-np.arange(0, w, 2, dtype=np.float32) / w)
    ang = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (w // 2,)
    cos = jnp.asarray(np.cos(ang).reshape(shape), x.dtype)
    sin = jnp.asarray(np.sin(ang).reshape(shape), x.dtype)
    pairs = x.reshape(*x.shape[:-1], w // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla(p, u, dm):
    t, h, nope = u.shape[0], dm["heads"], dm["nope"]
    c_q = rms_norm(mm(u, p["q_a"]), p["q_norm"], dm["eps"])
    q = mm(c_q, p["q_b"]).reshape(t, h, dm["qk"])
    kv_a = mm(u, p["kv_a"])
    c_kv, k_pe = kv_a[:, :dm["kv_rank"]], kv_a[:, dm["kv_rank"]:]
    kv = mm(rms_norm(c_kv, p["kv_norm"], dm["eps"]), p["kv_b"]).reshape(
        t, h, nope + dm["v"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], dm["theta"])],
                        axis=-1)
    k_rot = rotate(k_pe, dm["theta"])                   # [T, rope]: one a token
    k = jnp.concatenate(
        [k_nope, jnp.repeat(k_rot[:, None, :], h, axis=1)], axis=-1)

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        scores = jnp.einsum("qhd,shd->hqs", q_blk, k, precision=HI) \
            / math.sqrt(dm["qk"])
        qi = start + jnp.arange(q_blk.shape[0])[:, None]
        scores = jnp.where(jnp.arange(t)[None, :] <= qi, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v, precision=HI)

    blk = min(QUERY_BLOCK, t)
    pad = -t % blk
    q_blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        (t + pad) // blk, blk, h, dm["qk"])
    o = lax.map(block, (q_blocks, jnp.arange(0, t + pad, blk)))
    return mm(o.reshape(t + pad, h * dm["v"])[:t], p["o"])


# -- experts ---------------------------------------------------------------------------

def route(p, e_bias, u, dm):
    """Dense routing weights [T, router]: zero but for the ``top_k``
    chosen experts of each token."""
    s = jax.nn.sigmoid(mm(u, p["router"]))
    _, idx = lax.top_k(s + e_bias, dm["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if dm["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * dm["scale"]
    onehot = idx[..., None] == jnp.arange(dm["router"])
    return jnp.sum(jnp.where(onehot, w[..., None], 0), axis=1)


def experts(p, e_bias, u, dm):
    weights = route(p, e_bias, u, dm)
    y = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    for j in range(dm["count"]):  # the experts held here, no others
        w_j = weights[:, dm["first"] + j][:, None]
        y = y + w_j * swiglu(u, p["gate"][j], p["up"][j], p["down"][j])
    return y


# -- the network ----------------------------------------------------------------------------

def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def network_block(p, e_bias, x, dm):
    """One block: ``e_bias`` None for a dense layer."""
    @jax.checkpoint
    def run(p, x):
        h = x + mla(p, rms_norm(x, p["norm1"], dm["eps"]), dm)
        u = rms_norm(h, p["norm2"], dm["eps"])
        if e_bias is None:
            return h + swiglu(u, p["gate"], p["up"], p["down"])
        return h + experts(p, e_bias, u, dm)
    return run(p, x)


def forward_sequence(config: dict):
    """``apply(params, state, ids [T]) -> hidden [D, T, d]``: each
    prediction depth's output after its final norm (the head is the
    caller's: one matrix for every depth)."""
    dm = dims(config)

    def apply(params, state, ids):
        x = params["embed"][ids]
        for i in range(dm["layers"]):
            name = layer_name(i)
            x = network_block(
                params["layers"][name],
                None if i < dm["first_dense"] else state[name]["e_bias"],
                x, dm)
        h = rms_norm(x, params["norm_f"], dm["eps"])
        out = [h]
        if dm["mtp"]:
            m = params["mtp"]
            nxt = jnp.concatenate([ids[1:], ids[-1:]])
            joined = jnp.concatenate(
                [rms_norm(params["embed"][nxt], m["enorm"], dm["eps"]),
                 rms_norm(h, m["hnorm"], dm["eps"])], axis=-1)
            x2 = network_block(m["block"], state["mtp"]["e_bias"],
                               mm(joined, m["eh_proj"]), dm)
            out.append(rms_norm(x2, m["norm_f"], dm["eps"]))
        return jnp.stack(out)

    return apply


def forward(config: dict):
    """``apply(params, state, ids [B,T]) -> (logits [D,B,T,V_held],
    state)``: every depth's logits, a sequence at a time."""
    one = forward_sequence(config)

    def apply(params, state, ids):
        hidden = jnp.stack([one(params, state, row) for row in ids], axis=1)
        return mm(hidden, params["head"]), state

    return apply


def depth_targets(targets, depth: int):
    """The labels of prediction depth ``depth``: the targets moved that
    many positions earlier along the last axis, ``IGNORE`` past the end."""
    if depth == 0:
        return targets
    pad = np.full(targets.shape[:-1] + (depth,), IGNORE, targets.dtype)
    return np.concatenate([targets[..., depth:], pad], axis=-1)


def cross_entropy_sum(logits, targets):
    """Sum of the loss over the positions whose target is not ``IGNORE``
    (float32 whatever the logits' type), and their count."""
    logits = logits.astype(jnp.float32)
    valid = targets != IGNORE
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, logz - picked, 0.0)), jnp.sum(valid)


def loss_and_grads(config: dict, params, state, ids, targets):
    """``L = sum_k w_k L_k`` (``w = 1, mtp_loss_weight``), each ``L_k``
    the mean over the batch's positions that depth ``k`` counts, and its
    gradient, a sequence at a time; and the first sequence's logits of
    every depth: ``(loss, grads, logits0 [D,T,V])``."""
    dm = dims(config)
    one = forward_sequence(config)
    depths = 1 + dm["mtp"]
    weights = (1.0, dm["mtp_weight"])[:depths]
    tgt = np.stack([depth_targets(np.asarray(targets), k)
                    for k in range(depths)], axis=1)      # [B,D,T]
    counts = np.asarray([(tgt[:, k] != IGNORE).sum() for k in range(depths)],
                        np.float32)

    @jax.jit
    def seq(params, state, row, tgt, counts):
        # Everything a seed decides is an argument: one compiled program
        # serves every seed (and the compile cache of the next run).
        def f(p):
            logits = mm(one(p, state, row), p["head"])
            part = sum(w * cross_entropy_sum(logits[k], tgt[k])[0]
                       / counts[k] for k, w in enumerate(weights))
            return part, logits
        (part, logits), g = jax.value_and_grad(f, has_aux=True)(params)
        return part, g, logits

    # Summed on the host: the device holds one sequence's gradients.
    loss, grads, logits0 = 0.0, None, None
    for b in range(ids.shape[0]):
        part, g, logits = jax.device_get(
            seq(params, state, ids[b], tgt[b], counts))
        loss = loss + float(part)
        grads = g if grads is None else jax.tree_util.tree_map(
            lambda a, c: a + c, grads, g)
        if b == 0:
            logits0 = logits
    return loss, grads, logits0


def layer_shapes(config: dict) -> dict:
    """What ``flops_glm4_moe_lite.py`` counts: the derived sizes."""
    return dims(config)
