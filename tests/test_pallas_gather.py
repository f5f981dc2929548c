"""The row gather (ops/gather.py): the table's layout, the Pallas kernel in
interpret mode, and the compiled program's text.

The CPU test mesh holds the table in the layout the chip holds and gathers
with the XLA gather; the ``pallas`` cases run the kernel itself in
interpret mode — same values as ``rows[idx]`` — so the TPU fast path is not
tested only by construction.  The last tests compile the scanned gather for
a described v5e chip (no chip attached) and read its text.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ddp_tpu.ops import gather as gather_mod
from ddp_tpu.ops.gather import RowTable, gather_rows, whole_table_writes
from ddp_tpu.parallel.mesh import DATA_AXIS, make_mesh


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Take the TPU branch of ``gather_rows`` on the CPU backend: the
    kernel runs in Pallas' interpret mode."""
    from jax.experimental import pallas as pl

    orig = pl.pallas_call

    def interp(*args, **kw):
        kw["interpret"] = True
        return orig(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", interp)
    monkeypatch.setattr(gather_mod, "_use_pallas", lambda: True)


def _rows(shape, dtype=np.uint8, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        return rng.integers(0, 256, shape, dtype=dtype)
    return rng.random(shape).astype(dtype)


@pytest.mark.parametrize("row_shape,stored", [
    ((32, 32, 3), (24, 128)),   # CIFAR: 3,072 = 24 x 128
    ((256,), (2, 128)),
    ((128,), (1, 128)),
    ((5, 5, 3), (5, 5, 3)),     # 75 elements: no lane layout, kept as is
    ((100,), (100,)),
])
def test_row_table_layout(row_shape, stored):
    """``from_rows`` stores ``[M, D/128, 128]`` where D % 128 == 0 and the
    rows as they are where it is not; either way it remembers the row
    shape, the host reshape is a view, and the table is ONE pytree leaf
    that ``jit`` and ``device_put`` take like a bare array."""
    rows = _rows((12,) + row_shape)
    table = RowTable.from_rows(rows)
    assert table.row_shape == row_shape
    assert table.data.shape == (12,) + stored
    assert np.shares_memory(table.data, rows)
    np.testing.assert_array_equal(table.data.reshape(rows.shape), rows)
    leaves, treedef = jax.tree_util.tree_flatten(table)
    assert len(leaves) == 1 and leaves[0] is table.data
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.row_shape == row_shape
    on_dev = jax.device_put(table, jax.devices()[0])
    assert isinstance(on_dev, RowTable) and on_dev.row_shape == row_shape
    assert isinstance(on_dev.data, jax.Array)
    out = jax.jit(lambda t: t)(on_dev)
    assert isinstance(out, RowTable) and out.data.shape == table.data.shape


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("row_shape,dtype", [
    ((32, 32, 3), np.uint8), ((256,), np.uint8), ((5, 5, 3), np.uint8),
    ((32, 32, 3), np.float32),
])
def test_gather_rows_equals_indexing(kernel, row_shape, dtype, request):
    """``gather_rows`` on the stored layout == ``rows[idx]`` bit for bit,
    out-of-range indices clamped as XLA's gather clamps them."""
    if kernel == "pallas":
        request.getfixturevalue("pallas_interpret")
    rows = _rows((40,) + row_shape, dtype, seed=1)
    idx = np.random.default_rng(2).integers(0, 40, 9).astype(np.int32)
    idx[:3] = -1, 40, 1000
    out = jax.jit(gather_rows)(RowTable.from_rows(jnp.asarray(rows)), idx)
    assert out.shape == (9,) + row_shape and out.dtype == rows.dtype
    np.testing.assert_array_equal(np.asarray(out),
                                  rows[np.clip(idx, 0, 39)])


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("row_shape", [(32, 32, 3), (5, 5, 3)])
def test_gather_rows_under_shard_map(kernel, row_shape, request):
    """The resident epoch's arrangement: table replicated (``P()`` covers
    the whole RowTable), indices sharded on ``data``, inside a scan."""
    if kernel == "pallas":
        request.getfixturevalue("pallas_interpret")
    mesh = make_mesh(2)
    rows = _rows((40,) + row_shape, seed=3)
    idx = np.random.default_rng(4).integers(-2, 44, (3, 8)).astype(np.int32)
    table = jax.device_put(RowTable.from_rows(rows),
                           NamedSharding(mesh, P()))

    def body(table, idx_matrix):
        return jax.lax.scan(
            lambda _, idx_row: (None, gather_rows(table, idx_row)),
            None, idx_matrix)[1]

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, DATA_AXIS)),
        out_specs=P(None, DATA_AXIS),
        # Interpret mode evaluates the index_map outside the vma rules
        # (the compiled kernel's declared vma: the chip's self-check).
        check_vma=kernel != "pallas"))(
            table, jax.device_put(idx, NamedSharding(mesh,
                                                     P(None, DATA_AXIS))))
    np.testing.assert_array_equal(np.asarray(out),
                                  rows[np.clip(idx, 0, 39)])


def test_pallas_kernel_takes_the_table_as_stored(pallas_interpret):
    """No reshape of the table on the Pallas path: the only operations of
    the traced gather with a table-sized result are none at all."""
    table = RowTable.from_rows(_rows((40, 32, 32, 3)))
    jaxpr = jax.make_jaxpr(gather_rows)(table, np.arange(8, dtype=np.int32))
    table_sized = [e for e in jaxpr.jaxpr.eqns
                   for v in e.outvars if v.aval.shape[:1] == (40,)]
    assert table_sized == [], table_sized


_HLO = """\
HloModule jit_epoch, is_scheduled=true
%body (arg: (s32[], u8[604388,24,128])) -> (s32[], u8[604388,24,128]) {
  %arg = (s32[]{:T(128)}, u8[604388,24,128]{2,1,0:T(8,128)(4,1)}) parameter(0)
  %gte.1 = u8[604388,24,128]{2,1,0:T(8,128)(4,1)} get-tuple-element(%arg), index=1
  %gather.4 = u8[512,24,128]{2,1,0:T(8,128)(4,1)S(1)} custom-call(%idx, %gte.1), custom_call_target="tpu_custom_call"
  %labels.2 = s32[604388]{0:T(1024)} copy(%l)
  ROOT %tuple.11 = (s32[]{:T(128)}, u8[604388,24,128]{2,1,0:T(8,128)(4,1)}) tuple(%add.2, %gte.1)
}
ENTRY %main (t: u8[604388,24,128]) -> s32[] {
  %t = u8[604388,24,128]{2,1,0:T(8,128)(4,1)} parameter(0), sharding={replicated}
  %while = (s32[]{:T(128)}, u8[604388,24,128]{2,1,0:T(8,128)(4,1)}) while(%tuple.9), condition=%cond, body=%body
%WRITE%
}
"""


@pytest.mark.parametrize("line,flagged", [
    ("", False),  # carriers only: parameter, get-tuple-element, tuple, while
    ("  %copy.258 = u8[604388,24,128]{2,1,0:T(8,128)(4,1)} copy(%bitcast.3)",
     True),
    ("  %fusion.7 = u8[604388,3072]{1,0:T(8,128)(4,1)} fusion(%t), "
     "kind=kLoop, calls=%fused", True),
    ("  ROOT %r = (s32[], u8[604388,32,32,3]{3,2,1,0}) fusion(%t), "
     "kind=kLoop, calls=%f", True),
    ("  %bc = u8[604388,3072]{1,0} bitcast(%t)", False),
    ("  %half = u8[604388,12,128]{2,1,0} copy(%x)", False),  # under a row
    ("  %other = u8[604387,24,128]{2,1,0} copy(%x)", False),  # other rows
])
def test_whole_table_writes_rule(line, flagged):
    """``trace_reduce.table_seconds``' rule on a compiled module's text:
    a result with the table's rows first and a row's elements behind it,
    written by an instruction that does more than carry it."""
    found = whole_table_writes(_HLO.replace("%WRITE%", line), 604388, 3072)
    assert (found == [line.strip()]) if flagged else (found == [])


# A described chip: the TPU's compiler runs here with no chip attached
# (nothing executes).  Described inside the fixture and in this file only:
# one process at a time may hold the TPU's library.
@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # What is compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows,batch", [(604388, 512), (50000, 3072)])
def test_scanned_gather_compiles_for_v5e_without_copying_the_table(
        one_chip, monkeypatch, rows, batch):
    """The benchmark's two table sizes, at real size, through the TPU's
    compiler: the Mosaic kernel is in the program, no instruction writes
    the whole table, the program needs no temporary at all, and the
    table's tiled layout pads nothing (the HBM guard counts ``nbytes``)."""
    monkeypatch.setattr(gather_mod, "_use_pallas", lambda: True)

    def scanned(table, idx_matrix):
        def body(_, idx_row):
            return None, gather_rows(table, idx_row).sum(
                axis=(1, 2, 3), dtype=jnp.int32)
        return jax.lax.scan(body, None, idx_matrix)[1]

    table = RowTable(jax.ShapeDtypeStruct((rows, 24, 128), jnp.uint8,
                                          sharding=one_chip), (32, 32, 3))
    idx = jax.ShapeDtypeStruct((8, batch), jnp.int32, sharding=one_chip)
    compiled = jax.jit(scanned).lower(table, idx).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert whole_table_writes(text, rows, 3072) == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == rows * 3072 + 8 * batch * 4
    assert mem.temp_size_in_bytes < rows * 3072 // 100


def test_attention_kernels_compile_for_v5e_at_the_cells_shape(
        one_chip, monkeypatch):
    """The token cell's attention (ops/attention.py; here because the
    described chip's fixture lives in this file only): 4 pairs x 16 heads
    x 8,192 x 128 in bf16 at the module's block constants, forward and
    backward, through Mosaic: both kernels are in the program, within the
    VMEM limit the module sets, and nothing the size of a head's scores
    is left for HBM."""
    import math

    from ddp_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    p, r, t, hd = 4, 16, 8192, 128
    assert attention.kernel_applies(t, hd, 2)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def out_and_grads(q, k, v, do):
        o, pull = jax.vjp(lambda q, k, v: attention.causal_gqa(
            q, k, v, 1.0 / math.sqrt(hd)), q, k, v)
        return (o,) + pull(do)

    compiled = jax.jit(out_and_grads).lower(
        spec(p, r, t, hd), spec(p, t, hd), spec(p, t, hd),
        spec(p, r, t, hd)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "causal_gqa_fwd" in text and "causal_gqa_bwd" in text
    # One head's float32 scores are 268 MB; the program's temporaries are
    # the log-sum-exp, ``di`` and padding: a few MB.
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("window", [None, 512], ids=["global", "window"])
def test_two_map_attention_kernels_compile_for_v5e_at_the_cells_shape(
        one_chip, monkeypatch, window):
    """The second token cell's differential attention cores
    (ops/attention.py:diff_attention): 2 sequences x 8,192 tokens, 20
    query pairs over 10 key-value pairs, two 64-wide maps beside a
    128-wide value in bf16 at the module's block constants, with and
    without the cell's window, norm and all, forward and backward, through
    Mosaic: both kernels are in the program and HBM holds the two float32
    results, the statistics and nothing the size of a map's scores."""
    from ddp_tpu.ops import attention
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    bsz, t, pairs, kvp = attention.DIFF_SELF_CHECK_SHAPES[0]
    assert attention.kernel_applies(t, 64, 2, 128)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def out_and_grads(q, k, v, lam, weight, do):
        o, pull = jax.vjp(lambda *a: attention.diff_attention(
            *a, 0.125, 1e-5, window), q, k, v, lam, weight)
        return (o,) + pull(do)

    compiled = jax.jit(out_and_grads).lower(
        spec(bsz, t, pairs * 128), spec(bsz, t, kvp * 128),
        spec(bsz, t, kvp * 128), spec(dtype=jnp.float32),
        spec(128, dtype=jnp.float32), spec(bsz, t, pairs * 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "diff_attention_fwd" in text and "diff_attention_bwd" in text
    # ``A1 V`` and ``A2 V`` in float32 are 168 MB each; one map's float32
    # scores for one pair would be 268 MB more.
    assert compiled.memory_analysis().temp_size_in_bytes < 640 * 2**20


def test_scan_kernels_compile_for_v5e_at_the_cells_shape(one_chip,
                                                         monkeypatch):
    """The token cell's Mamba-2 scan (ops/ssd.py; here because the
    described chip's fixture lives in this file only): 2 sequences of
    8,192 tokens, 64 heads of 64 in 8 groups, state 128, chunks of 128 in
    bf16 at the module's constants, forward and backward, through Mosaic:
    both kernels (the gate and the norm inside them) are in the program,
    within the VMEM limit the module sets, and what is left for HBM is the
    saved entering states and float32 ``y``, not a ``[Q,Q]`` matrix a head
    a chunk (1.07 GB each)."""
    from ddp_tpu.ops import ssd
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    bsz, t, h, p, g, n, q = 2, 8192, 64, 64, 8, 128, 128
    assert ssd.kernel_applies(t, h, p, g, n, q, 2)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    args = (spec((bsz, t, h, p)), spec((bsz, t, h), f32), spec((h,), f32),
            spec((bsz, t, g, n)), spec((bsz, t, g, n)), spec((h,), f32),
            spec((bsz, t, h * p)), spec((h * p,), f32))
    compiled = jax.jit(ssd._vjp_of(
        lambda *a: ssd.ssd_scan(*a, q, 1e-5))).lower(
            args, spec((bsz, t, h * p))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "ssd_fwd" in text and "ssd_bwd" in text
    # The entering states are 537 MB, float32 y 268 MB, the rows' layout
    # of dt and its cumulative sum and their cotangents a few MB each.
    assert compiled.memory_analysis().temp_size_in_bytes < 1100 * 2**20


def test_selscan_kernels_compile_for_v5e_at_the_cells_shape(one_chip,
                                                            monkeypatch):
    """The second token cell's Mamba-1 selective scan (ops/selscan.py; here
    because the described chip's fixture lives in this file only): 2
    sequences of 8,192 tokens, 5,120 channels, state 16 in bf16 at the
    module's constants, forward and backward with a cotangent on both
    results, through Mosaic: both kernels are in the program, within the
    VMEM limit the module sets, and what is left for HBM is the states
    entering the blocks (42 MB), the two results and the partial sums,
    not a block's states (84 MB a block of 128 tokens in the XLA form)."""
    from ddp_tpu.ops import selscan
    monkeypatch.setattr(selscan, "_use_pallas", lambda: True)
    bsz, t, ch, n = 2, 8192, 5120, 16
    assert selscan.kernel_applies(t, ch, n, 2)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    args = (spec((bsz, t, ch)), spec((bsz, t, ch)), spec((bsz, t, ch), f32),
            spec((ch,), f32), spec((n, ch), f32), spec((bsz, t, n)),
            spec((bsz, t, n)), spec((ch,), f32))
    compiled = jax.jit(selscan._vjp_of(selscan.selscan)).lower(
        args, (spec((bsz, t, ch)), spec((bsz, t, ch)))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "selscan_fwd" in text and "selscan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
