"""The reduction from a trace to numbers: the arithmetic on hand-made
traces, then the whole on the small trace recorded on the chip."""
import json
import os

import pytest

from benchmark import trace_reduce as tr
from conftest import ROOT

DEV = tr.DEVICE_PLANE_PREFIX + "0"


def _trace(events, host=()):
    planes = [{"name": DEV, "lines": [{"name": tr.OP_LINE,
                                       "events": list(events)}]}]
    if host:
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "main", "events": list(host)}]})
    return {"planes": planes}


def test_busy_is_a_union_not_a_sum():
    # A loop of 100 ns holding two operations of 30 ns, then an operation
    # that overlaps the loop's end by 10 ns: 120 ns busy, not 190.
    t = _trace([["while.1", 0, 100], ["fusion.1", 10, 30],
                ["fusion.2", 50, 30], ["copy.1", 90, 30]])
    r = tr.reduce(t)
    assert r["busy_s"] == pytest.approx(120e-9)
    assert r["window_s"] == pytest.approx(120e-9)
    # Self time: the loop keeps what its body does not cover.
    assert r["ops"]["while.1"] == pytest.approx(30e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(30e-9)
    assert r["ops"]["copy.1"] == pytest.approx(30e-9)


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.length([(0, 3), (5, 8)]) == 6


def test_idle_gap_is_named_after_the_span_that_covers_it():
    ms = 1_000_000
    ops = [["fusion.1", 0, 10 * ms],
           ["fusion.2", 14 * ms, 10 * ms],      # 4 ms gap before
           ["fusion.3", 24 * ms + 5_000, 1 * ms],  # 5 us: turn-around
           ["fusion.4", 30 * ms, 1 * ms]]       # ~5 ms gap, no span
    # The sync annotation starts at trace time 1 ms = host time 100.0 s.
    host = [["bench:clock_sync", 1 * ms, 100]]
    spans = [{"phase": "data_wait", "start_s": 100.0095, "dur_s": 0.004,
              "overlap": False, "step": 1},
             {"phase": "host_augment", "start_s": 100.009, "dur_s": 0.01,
              "overlap": True, "step": 1}]  # producer side: never a label
    r = tr.reduce(_trace(ops, host), host_spans=spans,
                  sync_name="bench:clock_sync", sync_host_s=100.0)
    gaps = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    assert gaps["host:data_wait"] == pytest.approx(0.004)
    assert gaps["host:unattributed"] == pytest.approx(0.005 - 5e-6)
    assert gaps["device:between_ops"] == pytest.approx(5e-6)
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])


def test_operations_that_write_a_whole_table():
    # As the chip names them (my chip run, PR 22): the relayout inside
    # the scan, the gather that only reads the table, an index array with
    # the table's row count, and an activation larger than the table.
    relayout = ("%copy.258 = u8[604388,24,128]{2,1,0:T(8,128)(4,1)} copy("
                "u8[604388,24,128]{0,2,1:T(8,128)(4,1)} %get-tuple-element.9)")
    gather = ("%closed_call.20 = u8[512,24,128]{2,1,0:T(8,128)(4,1)S(1)} "
              "custom-call(s32[512]{0:T(512)S(1)} %bitcast.178, "
              "u8[604388,24,128]{2,1,0:T(8,128)(4,1)} %copy.258), "
              'custom_call_target="tpu_custom_call"')
    index = "%iota.3 = s32[604388]{0:T(1024)} iota(), iota_dimension=0"
    act = ("%fusion.36 = bf16[3072,32,32,64]{3,0,2,1:T(8,128)(2,1)} fusion("
           "bf16[3072,32,32,64]{3,0,2,1} %p), kind=kOutput, "
           "calls=%fused_computation.36")
    assert tr.result_shapes(relayout) == [
        ("u8[604388,24,128]", (604388, 24, 128))]
    assert tr.result_shapes(gather) == [("u8[512,24,128]", (512, 24, 128))]
    r = tr.reduce(_trace([[relayout, 0, 80], [gather, 80, 5],
                          [index, 85, 5], [act, 90, 10]]))
    assert tr.table_seconds(r, rows=604388, row_elems=3072) == \
        pytest.approx(80e-9)
    assert tr.table_seconds(r, rows=50000, row_elems=3072) == 0.0


def test_collectives_and_their_exposed_part():
    # all-reduce of 40 ns, of which 10 ns run beside a fusion.
    t = _trace([["fusion.1", 0, 50], ["all-reduce.1", 40, 40],
                ["fusion.2", 90, 10]])
    r = tr.reduce(t)
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["collective_exposed_s"] == pytest.approx(30e-9)
    assert tr.is_collective("%all-reduce-start.3")
    assert not tr.is_collective("fusion.all-reduce")


def test_two_devices_are_averaged():
    t = _trace([["fusion.1", 0, 100]])
    t["planes"].append({"name": tr.DEVICE_PLANE_PREFIX + "1", "lines": [
        {"name": tr.OP_LINE, "events": [["fusion.1", 0, 50]]}]})
    r = tr.reduce(t)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["window_s"] == pytest.approx(100e-9)


def test_no_device_operation_is_nothing():
    assert tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}) is None


def test_conv_keys_from_hlo_text():
    hlo = """HloModule m

%fused_computation.1 (p: bf16[8,4,4,16]) -> bf16[8,4,4,16] {
  %p = bf16[8,4,4,16]{3,0,2,1} parameter(0)
  ROOT %convolution.3 = bf16[8,4,4,16]{3,0,2,1} convolution(%p, %p), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (p: bf16[8,4,4,16]) -> bf16[8,2,2,16] {
  %p = bf16[8,4,4,16]{3,0,2,1} parameter(0)
  ROOT %reduce-window.1 = bf16[8,2,2,16]{3,0,2,1} reduce-window(%p, %c), window={size=1x2x2x1 stride=1x2x2x1}
}

ENTRY %main (a: bf16[8,4,4,16]) -> bf16[8,2,2,16] {
  %a = bf16[8,4,4,16]{3,0,2,1} parameter(0)
  %fusion.1 = bf16[8,4,4,16]{3,0,2,1} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="x"}
  ROOT %fusion.2 = bf16[8,2,2,16]{3,0,2,1} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2
}
"""
    keys = tr.conv_keys_from_hlo([hlo])
    # As the trace names them: operands with their shapes, no metadata.
    conv = ("%fusion.1 = bf16[8,4,4,16]{3,0,2,1} fusion(bf16[8,4,4,16]"
            "{3,0,2,1} %a), kind=kOutput, calls=%fused_computation.1")
    pool = ("%fusion.2 = bf16[8,2,2,16]{3,0,2,1} fusion(bf16[8,4,4,16]"
            "{3,0,2,1} %fusion.1), kind=kOutput, calls=%fused_computation.2")
    assert tr.op_key(conv) in keys
    assert tr.op_key(pool) not in keys  # kOutput, and no convolution
    r = tr.reduce(_trace([[conv, 0, 70], [pool, 70, 30]]), hlo_texts=[hlo])
    assert tr.conv_seconds(r) == pytest.approx(70e-9)
    assert tr.short_label(conv) == "fusion.1 kOutput bf16[8,4,4,16]"


def test_recorded_trace_from_the_chip():
    data = os.path.join(ROOT, "benchmark", "testdata")
    with open(os.path.join(data, "vgg_resident_v5e_100ms.json")) as f:
        trace = json.load(f)
    with open(os.path.join(data, "vgg_resident_v5e_hlo_excerpt.txt")) as f:
        hlo = f.read()
    r = tr.reduce(trace, hlo_texts=[hlo])
    # Read once by hand from the trace (tools/record_trace.py) and pinned.
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.099709858, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.099694543, rel=1e-9)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert tr.conv_seconds(r) == pytest.approx(0.075032974, rel=1e-9)
    assert len(r["conv_ops"]) == 28 and len(r["ops"]) == 551
    assert r["collective_s"] == 0.0
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "fusion.488 kOutput bf16[3072,16,16,256]"
    assert r["breakdown"]["idle_gaps"] == [
        ["device:between_ops", pytest.approx(1.5315e-05, rel=1e-6)]]
    assert tr.find_event(trace, "bench:clock_sync") is not None
    # The table's copies in these 100 ms (read by hand from the trace):
    # at the call's start copy.91 (582,531 ns) and reshape_reshape.14
    # (1,398,988 ns), and copy.118 inside the scan, once a step (twice
    # here: 1,372,524 ns).  The gather (closed_call.20) reads the table
    # and writes a batch: not one of them.
    assert tr.table_seconds(r, rows=50000, row_elems=3072) == pytest.approx(
        (582531 + 1398988 + 1372524) * 1e-9, rel=1e-9)

