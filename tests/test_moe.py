"""The expert layer's routed half over the live prefix of the row buffer
(models/moe.py:routed_products: a tile's gather, products and scatter-add
a trip), at a small size on the CPU: against the form that gathers,
multiplies and scatters the whole buffer (``moe_probe.former``, the
probe's yardstick too); that the step holds no array of the buffer's rows;
``row_plan``'s count of live tiles; the live-tile counter from the step to
the ``--prom`` table; and that the backward's products, gathers and
scatters stay inside their scopes."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import get_model, moe, moe_probe  # noqa: E402

F32 = jnp.float32
# Widths that no two matrices share, so that a product's shapes say whose
# it is: hidden, expert, shared expert.
D, H, SHARED = 24, 40, 56
ROUTER, FIRST, COUNT, TOP_K, TILE = 16, 4, 4, 4, 8
B, T = 2, 32
DM = dict(router=ROUTER, first=FIRST, count=COUNT, top_k=TOP_K,
          norm_topk=True, scale=2.5)
FORMS = {"relu2": moe.RELU2, "swiglu": moe.SWIGLU}
# The router's bias that makes a load (held: experts 4..7), and the
# buffer's headroom under it.
LOADS = {
    "even": (jnp.zeros((ROUTER,)), 5),
    # Every token to expert 5 and to three experts held elsewhere.
    "one_expert": (jnp.zeros((ROUTER,)).at[5].set(10.0).at[:3].set(5.0), 5),
    "none_here": (jnp.zeros((ROUTER,)).at[:4].set(10.0), 5),
    # Every token to all four held experts, and room for a fifth of them.
    "full_buffer": (jnp.zeros((ROUTER,)).at[4:8].set(10.0), 0.25),
}


def layer_operands(form, e_bias):
    keys = iter(jax.random.split(jax.random.key(0), 16))

    def normal(shape, std=0.3):
        return std * jax.random.normal(next(keys), shape, F32)

    p = {"router": normal((D, ROUTER), 1.0)}
    for routed, shared in zip(form.routed, form.shared):
        down = routed == "down"
        p[routed] = normal((COUNT, H, D) if down else (COUNT, D, H))
        p[shared] = normal((SHARED, D) if down else (D, SHARED))
    st = {"e_bias": e_bias,
          "assignments": jnp.zeros((COUNT,), jnp.int32),
          **{name: jnp.zeros((), jnp.int32)
             for name in ("dropped", "live_tiles", "buffer_tiles")}}
    return p, st, normal((B, T, D), 1.0), normal((B, T, D), 1.0)


def layer_and_grads(form, cd, p, st, x, cot):
    """``(y, new state), (dp, dx)`` of the layer as the module now stands
    (the caller may have patched ``routed_products``)."""
    def f(p, x):
        y, new = moe.expert_layer(p, st, x.astype(cd), DM, cd, train=True,
                                  form=form)
        return jnp.sum(y.astype(F32) * cot), (y, new)

    (_, out), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(p, x)
    return out, grads


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# -- (a) the live loop against the whole buffer ---------------------------------

@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_live_loop_is_the_whole_buffer_form(form, load, monkeypatch):
    form = FORMS[form]
    e_bias, headroom = LOADS[load]
    monkeypatch.setattr(moe, "MOE_ROW_TILE", TILE)
    monkeypatch.setattr(moe, "MOE_LOAD_HEADROOM", headroom)
    tiles = moe.buffer_tiles(B * T, DM, TILE)
    p, st, x, cot = layer_operands(form, e_bias)
    for cd, near in ((F32, 1e-6), (jnp.bfloat16, 2e-2)):
        (y, new), grads = layer_and_grads(form, cd, p, st, x, cot)
        with monkeypatch.context() as mp:
            mp.setattr(moe, "routed_products", moe_probe.former)
            (y_ref, new_ref), grads_ref = layer_and_grads(
                form, cd, p, st, x, cot)
        # A live tile is the same product of the same rows and a dead one
        # adds nothing; a product may now reach its float32 weighting
        # unrounded, a token's float32 sum runs in another order, and the
        # rows' cotangent adds up in float32, not in ``cd``.
        assert y.dtype == y_ref.dtype and rel(y, y_ref) < near
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            np.array_equal, new, new_ref))
        flat, flat_ref = (jax.tree_util.tree_leaves_with_path(g)
                          for g in (grads, grads_ref))
        for (path, g), (_, g_ref) in zip(flat, flat_ref):
            assert g.dtype == g_ref.dtype == F32
            assert rel(g, g_ref) < near, (jax.tree_util.keystr(path), cd)
        live = int(new["live_tiles"])
        assert int(new["buffer_tiles"]) == tiles
        if load == "none_here":
            # Nothing routed here: the shared expert alone, and nothing
            # reaches an expert's matrices.
            shared = moe.shared_expert(p, x.astype(cd).reshape(-1, D), cd,
                                       form)
            assert live == 0 == int(new["assignments"].sum())
            assert np.array_equal(y.reshape(-1, D),
                                  shared.astype(F32).astype(cd))
            assert all(not np.asarray(grads[0][name]).any()
                       for name in form.routed)
        elif load == "full_buffer":
            assert live == tiles and int(new["dropped"]) > 0
        elif load == "one_expert":
            assert live == B * T // TILE and int(new["dropped"]) == 0
        else:
            assert 0 < live < tiles and int(new["dropped"]) == 0
            assert any(np.asarray(grads[0][name]).any()
                       for name in form.routed)


# -- (a') no array of the buffer's rows ------------------------------------------

def _layer_texts(form, cd):
    """The lowered and the compiled text of the layer's value and gradient
    at the module's widths, in which ``(tiles, TILE, D)`` and
    ``(tiles * TILE, D)`` are no other array's shapes."""
    p, st, x, cot = layer_operands(form, LOADS["even"][0])

    def f(p, x):
        y, _ = moe.expert_layer(p, st, x.astype(cd), DM, cd, train=True,
                                form=form)
        return jnp.sum(y.astype(F32) * cot)

    lowered = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(p, x)
    return lowered.as_text(), lowered.compile().as_text()


@pytest.mark.parametrize("cd", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_step_holds_no_array_of_the_buffers_rows(form, cd, monkeypatch):
    """Forward and backward: no array of the buffer's rows or of their
    products, as the program asks for it or as XLA compiles it; every
    gather and scatter of rows of the tokens' width is a loop's, a tile at
    a time, and carries ``moe_route``."""
    monkeypatch.setattr(moe, "MOE_ROW_TILE", TILE)
    tiles = moe.buffer_tiles(B * T, DM, TILE)
    assert len({tiles * TILE, B * T, D, H, SHARED, ROUTER}) == 6
    lowered, compiled = _layer_texts(FORMS[form], jnp.dtype(cd))
    assert not re.search(rf"\b{tiles}x{TILE}x{D}x|\b{tiles * TILE}x{D}x",
                         lowered)
    assert not re.search(rf"\[{tiles},{TILE},{D}\]|\[{tiles * TILE},{D}\]",
                         compiled)
    seen = {"gather": 0, "scatter": 0}
    for ln in compiled.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (gather|scatter)\(", ln)
        if m and str(D) in m.group(1).split(","):
            op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
            dims = [int(n) for n in m.group(1).split(",")]
            # A gather reads a tile's rows; a scatter adds into the
            # accumulator of the tokens' rows.
            assert (TILE in dims if m.group(2) == "gather"
                    else dims == [B * T, D]), ln
            assert "/while/body/" in op_name, ln
            assert op_name.rsplit("/", 2)[-2] == "moe_route", ln
            seen[m.group(2)] += 1
    # Forward: the rows in, the results out.  Backward: the rows and their
    # cotangent in, the rows' cotangent out.
    assert seen == {"gather": 3, "scatter": 2}


def test_accumulator_copies_reads_the_loop_bodies():
    """The probe's check that a loop updates its accumulator in place
    finds a copy of the accumulator's shape inside a loop body, and only
    there."""
    text = """HloModule m

%body.1 (p: (s32[], f32[64,24])) -> (s32[], f32[64,24]) {
  %a = f32[64,24]{1,0} get-tuple-element(%p), index=1
  %c = f32[64,24]{1,0} copy(%a)
  %d = f32[8,24]{1,0} copy(%e)
  ROOT %t = (s32[], f32[64,24]{1,0}) tuple(%i, %c)
}

ENTRY %main (x: f32[64,24]) -> f32[64,24] {
  %x2 = f32[64,24]{1,0} copy(%x)
  %w = (s32[], f32[64,24]{1,0}) while(%t0), condition=%cond.1, body=%body.1
}
"""
    found = moe_probe.accumulator_copies(text, "f32[64,24]")
    assert len(found) == 1 and "%c = " in found[0]
    assert moe_probe.accumulator_copies(text.replace("body=%body.1", "body=%b2"),
                                  "f32[64,24]") == []
    _, compiled = _layer_texts(moe.SWIGLU, jnp.bfloat16)
    assert "while(" in compiled
    assert moe_probe.accumulator_copies(compiled, f"f32[{B * T},{D}]") == []


# -- (b) row_plan's live tiles --------------------------------------------------

@pytest.mark.parametrize("load", ["even", "one_expert", "none_here",
                                  "overflow"])
def test_row_plan_counts_the_tiles_that_hold_a_row(load):
    count, tile, tiles = 4, 8, 12
    key = {"even": np.arange(40) % 5,               # 4 = held elsewhere
           "one_expert": np.full(40, 2),
           "none_here": np.full(40, 4),
           "overflow": np.arange(200) % 4}[load].astype(np.int32)
    _sizes, src, _tile_expert, dropped, live = map(np.asarray, moe.row_plan(
        jnp.asarray(key), count, tile, tiles))
    holds = (src.reshape(tiles, tile) < key.size).any(axis=1)
    assert int(live) == int(holds.sum())
    assert holds[:int(live)].all()
    assert (src[int(live) * tile:] == key.size).all()
    assert int(live) == {"even": 4, "one_expert": 5, "none_here": 0,
                         "overflow": tiles}[load]
    assert (int(dropped) > 0) == (load == "overflow")


# -- (c) the counter, from the step to the --prom table -------------------------

def _tiny_config(pattern="E"):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b_ep16.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "tests", "tiny",
                           "train_lm.json")) as f:
        config = overlay(config, json.load(f)["config"])
    return dict(config, hybrid_override_pattern=pattern,
                num_hidden_layers=len(pattern))


def _live_of(model, params, state, ids, train):
    _, new = jax.jit(lambda p, s, i: model.apply(
        p, s, i, train=train, compute_dtype=None))(params, state, ids)
    return {k: int(v["live_tiles"]) for k, v in new.items()
            if isinstance(v, dict) and "live_tiles" in v}, new


@pytest.mark.parametrize("case", ["train_step", "evaluation",
                                  "two_replicas", "prom"])
def test_live_tiles_counter(case, tmp_path, capsys):
    model = get_model("nemotron_h", _tiny_config())
    params, state = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (4, 64), 0, 256)
    live, new = _live_of(model, params, state, ids, train=True)
    assert list(live) == ["layer_00"] and live["layer_00"] > 0
    tiles = int(new["layer_00"]["buffer_tiles"])
    assert tiles == moe.buffer_tiles(
        ids.size, {"top_k": 6, "count": 4, "router": 16}, moe.MOE_ROW_TILE)
    if case == "train_step":
        # A second step adds its own live tiles to the first's.
        twice, _ = _live_of(model, params, new, ids[::-1], train=True)
        once, _ = _live_of(model, params, state, ids[::-1], train=True)
        assert twice["layer_00"] == live["layer_00"] + once["layer_00"]
    elif case == "evaluation":
        same, kept = _live_of(model, params, new, ids, train=False)
        assert same == live
        assert int(kept["layer_00"]["buffer_tiles"]) == tiles
    elif case == "two_replicas":
        import functools

        from ddp_tpu.optim.schedule import triangular_lr
        from ddp_tpu.optim.sgd import SGDConfig
        from ddp_tpu.parallel.mesh import make_mesh
        from ddp_tpu.train.step import init_train_state, make_train_step
        sched = functools.partial(triangular_lr, base_lr=0.1, num_epochs=2,
                                  steps_per_epoch=8, peak_frac=0.3)
        step = make_train_step(model, SGDConfig(), sched, make_mesh(2))
        # (Before the step, which donates its state.)
        halves = [_live_of(model, params, state, ids[i:i + 2], train=True)
                  for i in (0, 2)]
        out = step(init_train_state(params, state),
                   {"image": ids, "label": ids}, jax.random.key(0))
        stats = jax.tree_util.tree_leaves(
            out, is_leaf=lambda v: isinstance(v, dict)
            and "live_tiles" in v)
        got = next(v for v in stats if isinstance(v, dict)
                   and "live_tiles" in v)
        # Each replica routes its own half of the batch through a buffer
        # of its own; the state carries the replicas' sum.
        assert int(got["live_tiles"]) == sum(
            h[0]["layer_00"] for h in halves)
        assert int(got["buffer_tiles"]) == sum(
            int(h[1]["layer_00"]["buffer_tiles"]) for h in halves)
    else:
        from ddp_tpu.obs.__main__ import main
        from ddp_tpu.obs.registry import MetricsRegistry, parse_exposition
        from ddp_tpu.obs.routing import RoutingCounters
        from ddp_tpu.train.trainer import _counters
        registry = MetricsRegistry()
        routing = RoutingCounters(
            registry, baseline=jax.device_get(_counters(state)))
        routing.update(jax.device_get(_counters(new)))
        assert routing.totals["layer_00"]["live_tiles"] == live["layer_00"]
        share = live["layer_00"] / tiles
        samples = {(name, dict(labels)["layer"]): v
                   for fam in parse_exposition(
                       registry.exposition()).values()
                   for (name, labels), v in fam["samples"].items()
                   if "live_tile" in name}
        assert samples == {
            ("ddp_moe_live_tiles_total", "layer_00"): live["layer_00"],
            ("ddp_moe_live_tile_share", "layer_00"): pytest.approx(share)}
        prom = tmp_path / "run.prom"
        prom.write_text(registry.exposition())
        assert main(["--prom", str(prom)]) == 0
        out = capsys.readouterr().out
        assert "live tiles" in out and f"{share:.1%}" in out
        assert re.search(rf"layer_00 .* {live['layer_00']} ", out)


def test_a_checkpoint_without_the_counters_resumes_at_zero():
    """A model state written before PR 38 lacks ``live_tiles`` and
    ``buffer_tiles``: the Trainer's restore starts them at zero, and fills
    nothing that is not an integer counter."""
    from ddp_tpu.train.trainer import _with_new_counters
    _, state = get_model("nemotron_h", _tiny_config()).init(
        jax.random.key(0))
    old = {k: {n: v for n, v in leaf.items()
               if n not in ("live_tiles", "buffer_tiles")}
           for k, leaf in state.items()}
    old["layer_00"]["dropped"] = jnp.asarray(7, jnp.int32)
    filled = _with_new_counters(old, state)
    assert jax.tree_util.tree_structure(filled) == \
        jax.tree_util.tree_structure(state)
    assert int(filled["layer_00"]["dropped"]) == 7
    assert int(filled["layer_00"]["live_tiles"]) == 0
    lacks_bias = {"layer_00": {k: v for k, v in old["layer_00"].items()
                               if k != "e_bias"}}
    assert "e_bias" not in _with_new_counters(lacks_bias,
                                              state)["layer_00"]


# -- (d) the backward's products inside the scope --------------------------------

@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_product_with_an_experts_matrix_is_in_the_scope(
        form, monkeypatch):
    """Forward and backward: every ``dot`` of the compiled step of the
    layer that has the experts' width among its dimensions carries
    ``moe_experts`` in its ``op_name`` (a backward rule is traced outside
    the layer's ``with``: the rules open the scope themselves), and the
    shared expert's carry ``moe_shared``."""
    form = FORMS[form]
    monkeypatch.setattr(moe, "MOE_ROW_TILE", TILE)
    p, st, x, cot = layer_operands(form, LOADS["even"][0])

    def f(p, x):
        y, _ = moe.expert_layer(p, st, x, DM, F32, train=True, form=form)
        return jnp.sum(y * cot)

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(p, x).compile(
    ).as_text()
    dims_of, dots = {}, []
    for ln in text.splitlines():
        m = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", ln)
        if m:
            dims_of[m.group(1)] = {int(n) for n in m.group(2).split(",")
                                   if n}
            if re.search(r"\] dot\(|\]\{[\d,]*\} dot\(", ln):
                dots.append((m.group(1), ln))
    seen = {"moe_experts": 0, "moe_shared": 0}
    for name, ln in dots:
        operands = re.search(r" dot\((%[\w.\-]+), (%[\w.\-]+)\)", ln)
        dims = dims_of[name].union(*(dims_of[o] for o in operands.groups()))
        scope = ("moe_experts" if H in dims
                 else "moe_shared" if SHARED in dims else None)
        if scope:
            op_name = re.search(r'op_name="([^"]*)"', ln)
            assert op_name and scope in op_name.group(1), ln
            seen[scope] += 1
    # Each of an expert's matrices is multiplied at least once forward and
    # twice backward (its rows' cotangent, its own gradient).
    n = len(form.routed)
    assert seen["moe_experts"] >= 3 * n and seen["moe_shared"] >= 2 * n
