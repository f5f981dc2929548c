"""``sambay``: the decoder-hybrid-decoder language model of
Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; SambaY,
arXiv:2507.06607), built from a configuration file (the keys of the
published ``config.json`` plus the share this chip holds: ``layers_held``,
``vocab_held``).  The contract is every model's — ``apply(params, state,
x, *, train, rng, compute_dtype) -> (logits, state)`` — with ``x`` token
ids ``i32[B,T]``, float32 logits ``[B,T,V_held]`` and no state.

Every layer is ``h = x + mixer(LN1(x))``, ``x' = h + MLP(LN2(h))``
(LayerNorm with weight and bias, SwiGLU), the embedding and the head one
matrix, no positional encoding.  The mixer by the PUBLISHED layer index
``l`` (:func:`kind_of`; ``half`` is the published depth over two):

- ``mamba``, a Mamba-1 mixer: the selective scan (a diagonal ``A``
  ``[d_inner, N]`` and a step ``dt`` a channel, so no product over
  chunks as Mamba-2 has) runs a token a step over a carried float32
  state.  Where ``ops/selscan.py:kernel_applies`` says so (a TPU, whole
  blocks of tokens and lane tiles of channels: read off the input) the
  scope ``sel_scan`` (softplus, recurrence, skip, gate) is that module's
  Pallas kernel pair, which keeps the ``[N, tile]`` state and a block's
  states in VMEM, forward and backward; everywhere else it is
  :func:`selective_scan`, in chunks under a checkpoint in plain XLA.
  Layer ``half`` hands its scan's result ``m`` (before the gate) to
  every later layer.
- ``window`` and ``full``, differential attention (two softmax maps a
  head pair, subtracted, a norm over the pair's 128-wide result) with
  64-wide queries and keys against a 128-wide value.  Where
  ``ops/attention.py:kernel_applies`` says so (a TPU, 64-wide maps beside
  a 128-wide value, whole blocks of tokens: read off the input) the core
  of all three patterns (``window``, ``full``, ``cross``) is that
  module's Pallas kernel pair ``diff_attention``, which keeps a tile's
  scores in VMEM, reads ``q``, ``k``, ``v`` as the projections wrote
  them, never visits a key tile past the diagonal or before the window,
  and subtracts and norms a block's float32 rows where they lie.
  Everywhere else (the CPU, the tests' tiny shapes, a length that is not
  whole blocks) it is :func:`_core_loop`: a block of
  queries at a time, keys-first (ops/seq.py), the window layer against
  the key blocks that its window touches only.  The ``full`` layer
  (``half + 1``) hands its keys and values on.
- ``gmu``, the gated memory unit ``(silu(u W1) * m) W2``, and ``cross``,
  differential attention of this layer's queries over layer ``half +
  1``'s keys and values.

The blocks are not independent: under ``remat: block`` a block's
checkpoint takes and returns the shared memory (``m``, ``k``, ``v``)
beside the residual stream.

Precision: parameters float32; matrix products in ``compute_dtype`` with
float32 accumulation; softmax statistics, ``lam``, both norms'
statistics, ``dt``, ``A``, the state recurrence, ``y``, the skip and
the gate in float32, on the kernels' paths as on the XLA paths (the scan
kernel reads ``x``, ``z``, ``B``, ``C`` and writes ``gated`` and ``m`` in
``compute_dtype`` exactly where the mixer casts; forward the attention
kernel casts each map's probabilities for their own value product and
subtracts the float32 results, where the loop casts the maps' float32
difference: the same sum).

The trace-time tallies of :data:`TRACED` say which attention pattern and
how many scans the compiled program holds and how many attention cores
went through the kernel pair (``core_kernel``) and through the loop
(``core_xla``), and ``ops/selscan.py:TRACED`` how many of the scans went
through the kernel (the model has no data-dependent event to count, so no
counter rides its state).  On the chip the cell's program reads
``core_kernel`` 3, ``core_xla`` 0.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention, selscan, seq
from ..ops.layers import linear

F32 = jnp.float32
# Queries a block of the XLA attention loop: the window layer's is its
# window, so that a block walks two key blocks; the global layers' as
# nemotron_h's.  (The kernel's blocks are ops/attention.py's.)
ATTN_QUERY_BLOCK = 1024
# Tokens a chunk of the selective scan: a carried state a chunk is what
# its backward pass keeps.  (Chunks of 256, and 8 or 16 steps unrolled in
# the loop, read no faster on the chip: PERF.md, findings of PR 33.)
SCAN_CHUNK = 128
_TN = (((0,), (0,)), ((), ()))

# Layers traced by kind, and which path took an attention layer's core.
TRACED = {"mamba": 0, "window": 0, "full": 0, "cross": 0, "gmu": 0,
          "core_kernel": 0, "core_xla": 0}


def kind_of(l: int, half: int) -> str:
    if l <= half + 1:
        if l % 2 == 0:
            return "mamba"
        return "window" if l < half else "full"
    return "gmu" if l % 2 == 0 else "cross"


def lam0_of(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def layer_name(l: int) -> str:
    return f"layer_{l:02d}"


def dims(config: dict) -> dict:
    """The sizes the layers are built from, and the share held here."""
    d = int(config["hidden_size"])
    published = config.get("published", {})
    depth = int(published.get("num_hidden_layers",
                              config["num_hidden_layers"]))
    lo, hi = (int(v) for v in config.get("layers_held", (0, depth)))
    if hi - lo != int(config["num_hidden_layers"]):
        raise ValueError(
            f"num_hidden_layers counts the layers held here "
            f"({config['num_hidden_layers']}), layers_held says {lo, hi}")
    half = depth // 2
    if depth % 4 or not 0 <= lo < hi <= depth:
        raise ValueError(f"layers_held {lo, hi} of {depth} layers: the "
                         "depth is a multiple of 4 and holds the range")
    if hi > half + 2 and lo > half:
        raise ValueError(
            f"layers {lo}-{hi - 1} read the memory of layers {half} and "
            f"{half + 1}, which are not held here")
    heads, kv_heads = (int(config["num_attention_heads"]),
                       int(config["num_key_value_heads"]))
    if heads % 2 or kv_heads % 2 or (heads // 2) % (kv_heads // 2):
        raise ValueError(f"{heads} query heads over {kv_heads} key-value "
                         "heads do not pair up")
    v0, v1 = config.get("vocab_held", (0, config["vocab_size"]))
    rank = config.get("mamba_dt_rank", "auto")
    return {
        "d": d, "eps": float(config["layer_norm_eps"]),
        "depth": depth, "half": half, "layers": list(range(lo, hi)),
        "ff": int(config["intermediate_size"]),
        "d_inner": int(config.get("mamba_expand", 2)) * d,
        "n": int(config.get("mamba_d_state", 16)),
        "k": int(config.get("mamba_d_conv", 4)),
        "dt_rank": math.ceil(d / 16) if rank == "auto" else int(rank),
        "pairs": heads // 2, "kv_pairs": kv_heads // 2, "hd": d // heads,
        "window": int(config["sliding_window"]),
        "vocab": int(v1) - int(v0),
        "init_std": float(config.get("initializer_range", 0.02)),
        "remat": config.get("remat", "block"),
    }


def layer_norm(x, w, b, eps: float, out_dtype):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    y = xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * w + b).astype(out_dtype)


def mlp(p, u, cd):
    with jax.named_scope("mlp"):
        g, v = jnp.split(linear(u, p["mlp_up"].astype(cd)), 2, axis=-1)
        return linear(jax.nn.silu(g) * v, p["mlp_down"].astype(cd))


# -- Mamba-1 ---------------------------------------------------------------------

def selective_scan(x, dt, a, b, c, chunk: int):
    """``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t``, ``y_t = C_t . s_t``,
    a token a step over a float32 state ``[B,N,C]`` (the channels on the
    lanes).  ``x`` [B,T,C], ``dt`` [B,T,C] float32, ``a`` [N,C], ``b``,
    ``c`` [B,T,N]; float32 ``y`` [B,T,C].  The whole sequence's states
    never exist at once: a chunk of ``chunk`` steps at a time under a
    checkpoint, so that the backward pass keeps one state a chunk and
    makes a chunk's again.  A ragged last chunk is filled with ``dt = 0``
    and ``x = 0``, which leave the state as it is."""
    bsz, t, ch = x.shape
    pad = -t % chunk
    nc = (t + pad) // chunk

    def chunks(v):  # [B,T,W] -> [nc, chunk, B, W], time first
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return v.reshape(bsz, nc, chunk, v.shape[-1]).transpose(1, 2, 0, 3)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = (v.astype(F32) for v in inp)
        s = (jnp.exp(dt_t[:, None, :] * a) * s
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(s, inp):
        return lax.scan(step, s, inp)

    # The zero state is made from the data so that, under shard_map, it
    # varies over the mesh as the carry it becomes does.
    s0 = jnp.zeros((bsz, a.shape[0], ch), F32) + 0.0 * dt[:, :1, :]
    _, y = lax.scan(one_chunk, s0, tuple(chunks(v) for v in (x, dt, b, c)))
    return y.transpose(2, 0, 1, 3).reshape(bsz, t + pad, ch)[:, :t]


def mamba_mixer(p, u, dm: dict, cd):
    """-> (the mixer's result, the scan's result before the gate)."""
    n, r = dm["n"], dm["dt_rank"]
    with jax.named_scope("ssm_proj"):
        xs, z = jnp.split(linear(u, p["in_proj"].astype(cd)), 2, axis=-1)
    with jax.named_scope("ssm_conv"):
        xs = seq.causal_conv_silu(xs, p["conv_w"], p["conv_b"], cd)
    with jax.named_scope("ssm_proj"):
        rbc = linear(xs, p["x_proj"].astype(cd))
        dt = jnp.matmul(rbc[..., :r], p["dt_proj"].astype(cd),
                        preferred_element_type=F32)
    kernel = selscan.kernel_applies(xs.shape[1], xs.shape[2], n,
                                    jnp.dtype(cd).itemsize)
    selscan.TRACED["kernel" if kernel else "xla"] += 1
    with jax.named_scope("sel_scan"):
        if kernel:
            gated, y = selscan.selscan(
                xs, z, dt, p["dt_bias"], -jnp.exp(p["A_log"]).T,
                rbc[..., r:r + n], rbc[..., r + n:], p["D"])
        else:
            dt = jax.nn.softplus(dt + p["dt_bias"])
            a = -jnp.exp(p["A_log"]).T
            y = selective_scan(xs, dt, a, rbc[..., r:r + n],
                               rbc[..., r + n:], SCAN_CHUNK) \
                + p["D"] * xs.astype(F32)
            gated = (y * jax.nn.silu(z.astype(F32))).astype(cd)
    with jax.named_scope("ssm_proj"):
        return linear(gated, p["out_proj"].astype(cd)), y.astype(cd)


def gmu_mixer(p, u, m, cd):
    with jax.named_scope("gmu"):
        return linear(jax.nn.silu(linear(u, p["gmu_in"].astype(cd))) * m,
                      p["gmu_out"].astype(cd))


# -- differential attention --------------------------------------------------------

def lam_of(p, l: int):
    return (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
            - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0_of(l))


def _diff_block(q, k, v, lam, sub_norm, *, start: int, lo: int, window,
                scale: float, gain: float, eps: float, cd):
    """One key-value pair of one sequence, a block of queries: ``q``
    [2,R,bq,hd] (the pair's two maps, ``R`` query pairs), ``k`` [2,S,hd],
    ``v`` [S,2hd]: ``RMSNorm((A1 - lam A2) V) gain`` as [R,bq,2hd].  ONE
    value product: the maps are subtracted in float32 first."""
    _, r, bq, _ = q.shape
    a1, a2 = (seq.block_probs(q[j], k[j], start=start, scale=scale, lo=lo,
                              window=window) for j in (0, 1))
    o = lax.dot_general((a1 - lam * a2).astype(cd), v, _TN,
                        preferred_element_type=F32)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * (sub_norm * gain)).astype(cd).reshape(r, bq, v.shape[-1])


def _core_loop(q, k, v, lam, sub_norm, *, window: Optional[int], cd, **kw):
    """The core in plain XLA: a (sequence, key-value pair) at a time (a
    product batched over them is what XLA:TPU lowers to a dilated
    convolution: PERF.md, findings of PR 28), a block of queries at a time
    under its own checkpoint, against the keys from the block that holds
    its window's first key on: the window layer never visits a key block
    before that."""
    bsz, t, pairs, _, hd = q.shape
    kvp = k.shape[2]
    rep = pairs // kvp
    block = min(window, ATTN_QUERY_BLOCK) if window else ATTN_QUERY_BLOCK

    def unit(args):
        q_u, k_u, v_u = args  # [2,R,T,hd], [2,T,hd], [T,2hd]
        out = []
        for s in range(0, t, block):
            lo, hi = (max(0, s - window) if window else 0), s + block
            f = jax.checkpoint(functools.partial(
                _diff_block, start=s, lo=lo, window=window, cd=cd, **kw))
            out.append(f(q_u[:, :, s:s + block], k_u[:, lo:hi],
                         v_u[lo:hi], lam, sub_norm))
        return jnp.concatenate(out, axis=1)  # [R,T,2hd]

    o = lax.map(unit, (
        q.reshape(bsz, t, kvp, rep, 2, hd).transpose(0, 2, 4, 3, 1, 5)
        .reshape(bsz * kvp, 2, rep, t, hd),
        k.transpose(0, 2, 3, 1, 4).reshape(bsz * kvp, 2, t, hd),
        v.transpose(0, 2, 1, 3).reshape(bsz * kvp, t, 2 * hd)))
    return o.reshape(bsz, kvp, rep, t, 2 * hd).transpose(
        0, 3, 1, 2, 4).reshape(bsz, t, pairs * 2 * hd)


def _core_kernel(q, k, v, lam, sub_norm, *, window: Optional[int],
                 scale: float, gain: float, eps: float, cd,
                 interpret: bool = False):
    """The core through ``ops/attention.py:diff_attention``: the kernel
    pair reads ``q``, ``k`` and ``v`` (in ``cd``, the result's type too)
    as the projections wrote them (a pair's two maps are 128 lanes side by
    side) and holds a block of rows of ``A1 V`` and ``A2 V`` in float32
    where it subtracts and norms them; the gradients of ``lam`` and
    ``sub_norm`` come back from the backward kernel."""
    del cd
    bsz, t = q.shape[:2]
    return attention.diff_attention(
        q.reshape(bsz, t, -1), k.reshape(bsz, t, -1), v.reshape(bsz, t, -1),
        lam, sub_norm * gain, scale, eps, window, interpret)


def diff_core(p, q, k, v, l: int, dm: dict, cd, window: Optional[int]):
    """``q`` [B,T,pairs,2,hd], ``k`` [B,T,kv_pairs,2,hd], ``v``
    [B,T,kv_pairs,2hd] -> [B,T,pairs*2hd]: the blocked Pallas kernel pair
    where ``ops/attention.py:kernel_applies`` says so (a TPU, two 64-wide
    maps beside a 128-wide value, whole blocks of tokens within the VMEM
    budget: read off the input), the XLA loop everywhere else."""
    t, hd = q.shape[1], q.shape[-1]
    kernel = attention.kernel_applies(t, hd, jnp.dtype(cd).itemsize, 2 * hd)
    TRACED["core_kernel" if kernel else "core_xla"] += 1
    return (_core_kernel if kernel else _core_loop)(
        q, k, v, lam_of(p, l).astype(F32), p["sub_norm"], window=window,
        scale=1.0 / math.sqrt(hd), gain=1.0 - lam0_of(l), eps=dm["eps"],
        cd=cd)


def self_attention(p, u, l: int, dm: dict, cd, window: Optional[int]):
    """-> (the mixer's result, (k, v))."""
    bsz, t, _ = u.shape
    hd, pairs, kvp = dm["hd"], dm["pairs"], dm["kv_pairs"]
    nq, nkv = pairs * 2 * hd, kvp * 2 * hd
    with jax.named_scope("attn_proj"):
        qkv = linear(u, p["qkv"].astype(cd), p["qkv_b"].astype(cd))
        q = qkv[..., :nq].reshape(bsz, t, pairs, 2, hd)
        k = qkv[..., nq:nq + nkv].reshape(bsz, t, kvp, 2, hd)
        v = qkv[..., nq + nkv:].reshape(bsz, t, kvp, 2 * hd)
    with jax.named_scope("attn_window" if window else "attn_full"):
        o = diff_core(p, q, k, v, l, dm, cd, window)
    with jax.named_scope("attn_proj"):
        return linear(o, p["o"].astype(cd), p["o_b"].astype(cd)), (k, v)


def cross_attention(p, u, k, v, l: int, dm: dict, cd):
    bsz, t, _ = u.shape
    with jax.named_scope("attn_proj"):
        q = linear(u, p["q"].astype(cd), p["q_b"].astype(cd)).reshape(
            bsz, t, dm["pairs"], 2, dm["hd"])
    with jax.named_scope("attn_cross"):
        o = diff_core(p, q, k, v, l, dm, cd, None)
    with jax.named_scope("attn_proj"):
        return linear(o, p["o"].astype(cd), p["o_b"].astype(cd))


# -- the network ---------------------------------------------------------------------

def _block(kind: str, l: int, p, x, mem: dict, dm: dict, cd):
    """One layer: ``(x, mem) -> (x', mem')``.  ``mem`` is what earlier
    layers handed on: ``m`` (layer ``half``'s scan), ``k``, ``v`` (layer
    ``half + 1``'s)."""
    TRACED[kind] += 1
    u = layer_norm(x, p["ln1_w"], p["ln1_b"], dm["eps"], cd)
    if kind == "mamba":
        out, y = mamba_mixer(p, u, dm, cd)
        if l == dm["half"]:
            mem = dict(mem, m=y)
    elif kind in ("window", "full"):
        out, (k, v) = self_attention(
            p, u, l, dm, cd, dm["window"] if kind == "window" else None)
        if kind == "full":
            mem = dict(mem, k=k, v=v)
    elif kind == "gmu":
        out = gmu_mixer(p, u, mem["m"], cd)
    else:
        out = cross_attention(p, u, mem["k"], mem["v"], l, dm, cd)
    h = x + out
    return h + mlp(p, layer_norm(h, p["ln2_w"], p["ln2_b"], dm["eps"], cd),
                   cd), mem


def build(config: dict):
    """``(init, apply, (vocabulary held, sequence length))`` for one
    configuration."""
    dm = dims(config)

    def init(key) -> Tuple[Dict, Dict]:
        d, di, n, r = dm["d"], dm["d_inner"], dm["n"], dm["dt_rank"]
        hd, std = dm["hd"], dm["init_std"]
        nq, nkv = dm["pairs"] * 2 * hd, dm["kv_pairs"] * 2 * hd
        keys = iter(jax.random.split(key, 16 * len(dm["layers"]) + 1))

        def normal(shape, scale=std):
            return scale * jax.random.normal(next(keys), shape, F32)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, F32, lo, hi)

        def lam_and_out():
            return dict(
                lq1=normal((hd,), 0.1), lk1=normal((hd,), 0.1),
                lq2=normal((hd,), 0.1), lk2=normal((hd,), 0.1),
                sub_norm=jnp.ones((2 * hd,), F32),
                o=normal((nq, d)), o_b=jnp.zeros((d,), F32))

        layers = {}
        for l in dm["layers"]:
            kind = kind_of(l, dm["half"])
            p = dict(ln1_w=jnp.ones((d,), F32), ln1_b=jnp.zeros((d,), F32),
                     ln2_w=jnp.ones((d,), F32), ln2_b=jnp.zeros((d,), F32),
                     mlp_up=normal((d, 2 * dm["ff"])),
                     mlp_down=normal((dm["ff"], d)))
            if kind == "mamba":
                bound = 1.0 / math.sqrt(dm["k"])
                # dt_bias is the inverse softplus of a log-uniform dt.
                dt = jnp.maximum(jnp.exp(uniform(
                    (di,), math.log(0.001), math.log(0.1))), 1e-4)
                p.update(
                    in_proj=normal((d, 2 * di)),
                    conv_w=uniform((dm["k"], di), -bound, bound),
                    conv_b=uniform((di,), -bound, bound),
                    x_proj=normal((di, r + 2 * n)),
                    dt_proj=uniform((r, di), -r ** -0.5, r ** -0.5),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    A_log=jnp.log(jnp.broadcast_to(
                        jnp.arange(1, n + 1, dtype=F32), (di, n))),
                    D=jnp.ones((di,), F32),
                    out_proj=normal((di, d)))
            elif kind in ("window", "full"):
                p.update(qkv=normal((d, nq + 2 * nkv)),
                         qkv_b=jnp.zeros((nq + 2 * nkv,), F32),
                         **lam_and_out())
            elif kind == "gmu":
                p.update(gmu_in=normal((d, di)), gmu_out=normal((di, d)))
            else:
                p.update(q=normal((d, nq)), q_b=jnp.zeros((nq,), F32),
                         **lam_and_out())
            layers[layer_name(l)] = p
        params = {"embed": normal((dm["vocab"], d)), "layers": layers,
                  "norm_f_w": jnp.ones((d,), F32),
                  "norm_f_b": jnp.zeros((d,), F32)}
        return params, {}

    def apply(params, state, x, *, train: bool = False,
              rng: Optional[jax.Array] = None, compute_dtype=None):
        del train, rng  # no dropout, no state
        if not jnp.issubdtype(x.dtype, jnp.integer) or x.ndim != 2:
            raise ValueError(f"sambay takes token ids i32[B,T], got "
                             f"{x.dtype}{list(x.shape)}")
        cd = compute_dtype or F32
        h = params["embed"][x].astype(cd)
        mem: dict = {}
        for l in dm["layers"]:
            block = functools.partial(_block, kind_of(l, dm["half"]), l,
                                      dm=dm, cd=cd)
            if dm["remat"] == "block":
                block = jax.checkpoint(block)
            h, mem = block(params["layers"][layer_name(l)], h, mem)
        with jax.named_scope("lm_head"):
            h = layer_norm(h, params["norm_f_w"], params["norm_f_b"],
                           dm["eps"], cd)
            logits = lax.dot_general(
                h, params["embed"].astype(cd), (((2,), (1,)), ((), ())),
                preferred_element_type=F32)
        return logits, state

    # seq_len is the training context the CLI's synthetic data takes; the
    # model itself runs at any length (0: the file gives none).
    return init, apply, (dm["vocab"], int(config.get("seq_len", 0)))


# -- python -m ddp_tpu.models.sambay -------------------------------------------------

# The widths of the smoke's small run; the layers held, their kinds and
# the published depth stay the configuration file's.
_TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=8,
             num_key_value_heads=4, sliding_window=32, vocab_size=256,
             vocab_held=[0, 256], seq_len=256)


def _self_check(config_file: str) -> None:
    """One training step (``make_train_step``, SGD, bf16 compute, two
    sequences) of the stage the file holds: at a tiny width on any
    backend and, on a TPU, at the file's own widths and sequence length.
    Checks the logits' shape, that the loss starts near ``ln V``, that
    loss and every updated parameter are finite and, at the file's widths,
    that every attention core took the kernel pair; prints the step
    program's tallies.  Raises otherwise."""
    import json
    import time

    import numpy as np

    from ..optim.sgd import SGDConfig
    from ..parallel.mesh import make_mesh
    from . import ModelDef
    from ..train.step import init_train_state, make_train_step, shard_batch
    from ..utils.platform import device_line, enable_compile_cache

    enable_compile_cache()
    mesh = make_mesh(1)
    print(device_line(mesh), flush=True)
    with open(config_file) as f:
        published = json.load(f)
    runs = [("tiny", dict(published, **_TINY))]
    if jax.default_backend() == "tpu":
        runs.append(("published widths", published))
    for tag, config in runs:
        init, apply, (vocab, t) = build(config)
        ids = np.asarray(jax.random.randint(jax.random.key(1), (2, t), 0,
                                            vocab), np.int32)
        shape = jax.eval_shape(
            lambda p, x: apply(p, {}, x, compute_dtype=jnp.bfloat16)[0],
            jax.eval_shape(lambda: init(jax.random.key(0))[0]), ids).shape
        if shape != (2, t, vocab):
            raise RuntimeError(f"sambay ({tag}): logits {shape}, expected "
                               f"{(2, t, vocab)}")
        # From here the tallies are the step program's own.
        for tally in (TRACED, selscan.TRACED):
            for k in tally:
                tally[k] = 0
        step = make_train_step(ModelDef("sambay", init, apply, (vocab, t)),
                               SGDConfig(lr=0.01, momentum=0.9),
                               lambda s: 0.01, mesh,
                               compute_dtype=jnp.bfloat16)
        state = init_train_state(*init(jax.random.key(0)))
        targets = np.concatenate([ids[:, 1:], np.full((2, 1), -1, np.int32)],
                                 axis=1)
        t0 = time.monotonic()
        state, loss = step(state, shard_batch(
            {"image": ids, "label": targets}, mesh), jax.random.key(2))
        loss = float(loss)
        finite = all(bool(jnp.isfinite(leaf).all())
                     for leaf in jax.tree_util.tree_leaves(state.params))
        n = sum(leaf.size for leaf in jax.tree_util.tree_leaves(state.params))
        print(f"sambay: {tag}: layers {dims(config)['layers']} T={t} "
              f"V={vocab} parameters={n} logits={list(shape)} loss={loss:.4f}"
              f" (ln V = {math.log(vocab):.4f}) finite={finite} first step "
              f"{time.monotonic() - t0:.1f}s with its compile (smoke timing)"
              f" traced={TRACED} scans through={selscan.TRACED}",
              flush=True)
        if not (finite and math.isfinite(loss)
                and abs(loss - math.log(vocab)) < 1.0):
            raise RuntimeError(f"sambay ({tag}): loss {loss}, finite "
                               f"parameters {finite}")
        if tag != "tiny" and TRACED["core_xla"]:
            raise RuntimeError(f"sambay ({tag}): {TRACED['core_xla']} "
                               "attention cores took the XLA loop on a TPU")
        del state
    print(f"sambay: ok steps={len(runs)} "
          + " ".join(f"[{tag}]" for tag, _ in runs), flush=True)


if __name__ == "__main__":
    import sys

    # Through the module as ``get_model`` imports it, not this second copy
    # of it: ``TRACED`` is the one the blocks add to.
    from ddp_tpu.models import sambay
    sambay._self_check(sys.argv[1])
