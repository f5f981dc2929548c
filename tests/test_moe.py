"""The expert layer's products over the live prefix of the row buffer
(models/moe.py:tile_products), at a small size on the CPU: against the
form that computes every tile, written here; ``row_plan``'s count of live
tiles; the live-tile counter from the step to the ``--prom`` table; and
that the backward's products stay inside the ``moe_experts`` scope."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import overlay  # noqa: E402
from ddp_tpu.models import get_model, moe  # noqa: E402

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


def every_tile(form, cd, rows, stacks, tile_expert, live):
    """The layer's products before PR 38: every tile of the buffer, live
    or not, through ``lax.map``."""
    del live
    low = tuple(s.astype(cd) for s in stacks)
    return lax.map(
        lambda a: form.fn(jnp.dot, a[0], lambda j: low[j][a[1]]),
        (rows, tile_expert))


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
    (the caller may have patched ``tile_products``)."""
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


# -- (a) the live loop against the every-tile form ------------------------------

@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_live_loop_is_the_every_tile_form(form, load, monkeypatch):
    form = FORMS[form]
    e_bias, headroom = LOADS[load]
    monkeypatch.setattr(moe, "MOE_ROW_TILE", TILE)
    monkeypatch.setattr(moe, "MOE_LOAD_HEADROOM", headroom)
    tiles = moe.buffer_tiles(B * T, DM, TILE)
    p, st, x, cot = layer_operands(form, e_bias)
    for cd, near in ((F32, 1e-6), (jnp.bfloat16, 2e-2)):
        (y, new), grads = layer_and_grads(form, cd, p, st, x, cot)
        with monkeypatch.context() as mp:
            mp.setattr(moe, "tile_products", every_tile)
            (y_ref, new_ref), grads_ref = layer_and_grads(
                form, cd, p, st, x, cot)
        # Bit for bit: a live tile is the same product of the same rows,
        # a dead one the zeros either form makes of zero rows.
        assert y.dtype == y_ref.dtype and np.array_equal(y, y_ref)
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
