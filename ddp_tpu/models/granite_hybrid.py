"""``granite_hybrid``: the hybrid Mamba-2 / attention language model of
granite-4.0-h-micro (``model_type`` ``granitemoehybrid`` with no experts),
built from a configuration file (the keys of the published
``config.json`` plus the share this chip holds: ``vocab_held``).  The
contract is every model's — ``apply(params, state, x, *, train, rng,
compute_dtype)`` — with ``x`` token ids ``i32[B,T]`` and float32 logits
``[B,T,V_held]``.

Every layer is a mixer and then a dense SwiGLU MLP, each in its own
pre-norm residual branch scaled by ``residual_multiplier``: ``h = x + r
mixer(RMSNorm(x))``, ``y = h + r W_out(silu(u W_g) * u W_u)`` with ``u =
RMSNorm(h)`` (``input_linear`` holds ``W_g`` then ``W_u``).  The mixer is
the layer's ``layer_types`` entry:

- ``mamba``: ``models/nemotron_h.py:mamba_mixer``, the Mamba-2 mixer the
  first token model runs, at ``mamba_n_groups`` groups: the blocked
  kernels of ops/ssd.py where they apply (at ONE group of 64 heads a tile
  of heads a grid step, the gate and the norm over all of a token's
  channels after them), else ``ssd_chunked`` in plain XLA.
- ``attention``: ``nemotron_h.attention_mixer``, grouped-query causal
  attention with no positional encoding (``position_embedding_type``
  ``nope``), its scores scaled by ``attention_multiplier`` in place of
  ``1/sqrt(head_dim)``: ``ops/attention.py:causal_gqa`` where
  ``kernel_applies`` says so, else the blocked XLA loop of ``ops/seq.py``
  (heads 64 wide: the loop).

The µP scalars: the embedding times ``embedding_multiplier``, each branch
times ``residual_multiplier``, the scores times ``attention_multiplier``,
the logits over ``logits_scaling``.  Embedding and head are one matrix
(``tie_word_embeddings``): the rows of the vocabulary held here, read at
the input and multiplied at the output.

Precision: parameters float32; matrix products in ``compute_dtype`` with
float32 accumulation; the residual stream, ``dt``, ``A``, the scan's state
and every norm's statistics in float32.  The stream is float32 because the
µP scalars make each branch an addend to ``12 E[id]`` whose low bits a
bf16 stream rounds away, twenty times a step: that put the first step
1.3 times as far from the float32 reference at the cell's widths
(PERF.md, section 6, the fifth token model).  Each norm's bf16 result is
written out once (:func:`_normed`).  No state is carried (the state
pytree stays empty).
:data:`TRACED` tallies the layers traced by kind and which path
took each scan, each convolution and each attention core.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import attention, causal_conv, ssd
from ..ops.layers import linear
from ..ops.seq import rms_norm
from . import nemotron_h

F32 = jnp.float32

TRACED = {"mamba": 0, "attention": 0, "scan_kernel": 0, "scan_xla": 0,
          "conv_kernel": 0, "conv_xla": 0, "core_kernel": 0, "core_xla": 0}


def dims(config: dict) -> dict:
    """The sizes the layers are built from, under the key names the shared
    mixers read, and the share held here."""
    n = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])
    if len(kinds) != n or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types must hold {n} entries of mamba and "
                         f"attention, got {list(kinds)}")
    if int(config["num_local_experts"]) or config["attention_bias"] \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] \
            or not config["tie_word_embeddings"] \
            or config["position_embedding_type"] != "nope" \
            or config["hidden_act"] != "silu":
        raise ValueError(
            "granite_hybrid knows dense silu MLPs, no bias but the "
            "convolution's, no positional encoding and a tied head; the "
            "file asks for another")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, s = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    return {
        "kinds": kinds, "d": d, "eps": float(config["rms_norm_eps"]),
        # models/nemotron_h.py:mamba_mixer's
        "h": h, "p": p, "g": g, "n": s, "d_inner": h * p,
        "conv_dim": h * p + 2 * g * s, "k": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
        # nemotron_h.attention_mixer's
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads,
        "attn_scale": float(config["attention_multiplier"]),
        "ff": int(config["shared_intermediate_size"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual": float(config["residual_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "vocab": int(v1) - int(v0),
        "init_std": float(config.get("initializer_range", 0.02)),
        "remat": config.get("remat", "block"),
    }


def layer_name(i: int) -> str:
    return f"layer_{i:02d}"


def _tally(kind: str, counter: dict, run):
    """``run()``, and which path its mixer took (the shared module's own
    tally, ``counter``) added to :data:`TRACED` under ``kind``."""
    before = dict(counter)
    out = run()
    for path in ("kernel", "xla"):
        TRACED[f"{kind}_{path}"] += counter[path] - before[path]
    return out


def mlp(p, u, dm: dict, cd):
    """``W_out(silu(u W_g) * u W_u)``, ``input_linear`` = ``[W_g | W_u]``."""
    with jax.named_scope("mlp"):
        gu = linear(u, p["input_linear"].astype(cd))
        gate, up = gu[..., :dm["ff"]], gu[..., dm["ff"]:]
        return linear(jax.nn.silu(gate) * up, p["output_linear"].astype(cd))


def _normed(x, weight, dm: dict, cd):
    """``RMSNorm(x)`` in ``cd``, written out once.  Without the barrier
    XLA fuses the norm of the float32 stream into the operand of every
    product that reads it, and a product re-reads its operand for each
    tile of its output: the float32 stream cost 15% of the step that way
    (PERF.md, section 6, the fifth token model)."""
    return jax.lax.optimization_barrier(rms_norm(x, weight, dm["eps"], cd))


def _block(kind: str, p, x, dm: dict, cd):
    u = _normed(x, p["norm1"], dm, cd)
    if kind == "mamba":
        y = _tally("conv", causal_conv.TRACED, lambda: _tally(
            "scan", ssd.TRACED, lambda: nemotron_h.mamba_mixer(p, u, dm, cd)))
    else:
        y = _tally("core", attention.TRACED,
                   lambda: nemotron_h.attention_mixer(p, u, dm, cd))
    h = x + y.astype(F32) * dm["residual"]
    u = _normed(h, p["norm2"], dm, cd)
    return h + mlp(p, u, dm, cd).astype(F32) * dm["residual"]


def build(config: dict):
    """``(init, apply, (vocabulary held, sequence length))`` for one
    configuration."""
    dm = dims(config)

    def init(key) -> Tuple[Dict, Dict]:
        d, std, ff = dm["d"], dm["init_std"], dm["ff"]
        keys = iter(jax.random.split(key, 16 * (len(dm["kinds"]) + 1)))

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, F32)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, F32, lo, hi)

        layers = {}
        for i, kind in enumerate(dm["kinds"]):
            p = {"norm1": jnp.ones((d,), F32), "norm2": jnp.ones((d,), F32)}
            if kind == "mamba":
                p.update(nemotron_h.mamba_params(dm, normal, uniform, std))
            else:
                hq, hkv, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
                p.update(q=normal((d, hq * hd)), k=normal((d, hkv * hd)),
                         v=normal((d, hkv * hd)), o=normal((hq * hd, d)))
            p.update(input_linear=normal((d, 2 * ff)),
                     output_linear=normal((ff, d)))
            layers[layer_name(i)] = p
        # Every matrix, the tied embedding among them, at
        # initializer_range: the family's one normal initialiser.
        return {"embed": normal((dm["vocab"], d)), "layers": layers,
                "norm_f": jnp.ones((d,), F32)}, {}

    def apply(params, state, x, *, train: bool = False,
              rng: Optional[jax.Array] = None, compute_dtype=None):
        del rng, train  # no dropout, nothing counted
        if not jnp.issubdtype(x.dtype, jnp.integer) or x.ndim != 2:
            raise ValueError(f"granite_hybrid takes token ids i32[B,T], got "
                             f"{x.dtype}{list(x.shape)}")
        cd = compute_dtype or F32
        embed = params["embed"]
        h = embed[x] * dm["embedding_multiplier"]  # the stream: float32
        for i, kind in enumerate(dm["kinds"]):
            TRACED[kind] += 1
            block = functools.partial(_block, kind, dm=dm, cd=cd)
            if dm["remat"] == "block":
                block = jax.checkpoint(block)
            h = block(params["layers"][layer_name(i)], h)
        with jax.named_scope("lm_head"):
            h = _normed(h, params["norm_f"], dm, cd)
            logits = jnp.einsum("btd,vd->btv", h, embed.astype(cd),
                                preferred_element_type=F32)
        return logits / dm["logits_scaling"], state

    # seq_len is the training context the CLI's synthetic data takes; the
    # model itself runs at any length (0: the file gives none).
    return init, apply, (dm["vocab"], int(config.get("seq_len", 0)))


def _main(argv) -> int:
    """``python -m ddp_tpu.models.granite_hybrid CONFIG``: trace one
    training step's forward and backward at the file's widths and
    ``seq_len`` (two sequences, bf16; shapes only, nothing runs) and print
    :data:`TRACED`.  On a TPU it fails unless every scan and every
    convolution took the kernels."""
    import json
    import sys

    from ..ops.losses import cross_entropy_sum_count
    from ..ops.gather import _use_pallas
    with open(argv[0]) as f:
        config = json.load(f)
    init, apply, (_vocab, t) = build(config)
    params, state = jax.eval_shape(lambda: init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, t), jnp.int32)

    def loss(p, ids):
        logits, _ = apply(p, state, ids, train=True,
                          compute_dtype=jnp.bfloat16)
        s, c = cross_entropy_sum_count(logits, ids)
        return s / c

    jax.eval_shape(jax.grad(loss), params, ids)
    print(f"granite_hybrid: {math.prod(ids.shape)} tokens traced through "
          f"{TRACED}", flush=True)
    if _use_pallas() and TRACED["scan_xla"]:
        print("granite_hybrid: a scan took ssd_chunked on a TPU",
              file=sys.stderr)
        return 1
    if _use_pallas() and TRACED["conv_xla"]:
        print("granite_hybrid: a convolution took the XLA form on a TPU",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    import sys

    # Through the module as the program imports it: its TRACED.
    from ddp_tpu.models import granite_hybrid
    sys.exit(granite_hybrid._main(sys.argv[1:]))
