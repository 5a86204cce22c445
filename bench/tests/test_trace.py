"""The trace reduction on a small recorded trace: union of busy intervals,
idle share, gap attribution, time by operation."""

import os

import pytest

from benchlib import opkinds, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.json")


@pytest.fixture(scope="module")
def tr():
    return trace.events_from_json(DATA)


def test_union_merges_overlapping_and_touching():
    assert trace.union_intervals([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [[0, 2.5], [3, 4]]


def test_busy_is_the_union_not_the_sum(tr):
    ops = tr.devices[0]
    lo, hi = trace.window_of(tr)
    assert (lo, hi) == pytest.approx((0.990, 1.130))
    # copy.1 runs inside fusion.2: 0.010+0.030 | 0.010 | 0.010+0.005
    assert trace.busy_seconds(ops, lo, hi) == pytest.approx(0.065)
    assert sum(o.dur for o in ops) == pytest.approx(0.075)


def test_idle_gaps_cover_the_rest_of_the_window(tr):
    lo, hi = trace.window_of(tr)
    gaps = trace.idle_gaps(tr.devices[0], lo, hi)
    assert gaps == [pytest.approx(g) for g in
                    ([0.990, 1.000], [1.040, 1.050], [1.060, 1.100],
                     [1.115, 1.130])]
    idle = sum(b - a for a, b in gaps)
    assert idle + trace.busy_seconds(tr.devices[0], lo, hi) == \
        pytest.approx(hi - lo)


def test_gap_attribution_goes_to_the_span_open_then(tr):
    lo, hi = trace.window_of(tr)
    spans = [s for s in tr.spans if s[0] != "bench.window"]
    by = trace.attribute_gaps(trace.idle_gaps(tr.devices[0], lo, hi), spans)
    assert by["bench.device_put"] == pytest.approx(0.008)
    assert by["bench.dispatch"] == pytest.approx(0.030)
    assert by["bench.next_batch"] == pytest.approx(0.004)
    assert by["bench.wait"] == pytest.approx(0.006 + 0.009)
    assert by["other"] == pytest.approx(0.010 + 0.002 + 0.006)
    assert sum(by.values()) == pytest.approx(0.075)


def test_summary_has_the_result_lines_parts(tr):
    s = trace.summarize(tr)
    assert s["window_s"] == pytest.approx(0.140)
    assert s["busy_s"] == pytest.approx(0.065)
    assert s["device_ops"][0][0].startswith("fusion.2__fusion__f32_4096")
    assert s["device_ops"][0][1] == pytest.approx(0.030)
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["idle_gaps"][0][0] == "bench.dispatch"


def test_ops_outside_the_window_are_clipped(tr):
    assert trace.busy_seconds(tr.devices[0], 1.005, 1.015) == \
        pytest.approx(0.010)


def test_op_kinds_by_opcode_and_shapes(tr):
    ops = tr.devices[0]
    sparse = [opkinds.is_sparse_op(o, 4096, 64) for o in ops]
    assert sparse == [True, True, True, False, True, False]
    # without the batch's id count only table-sized shapes are found
    assert opkinds.is_sparse_op(ops[0], 4096)
    assert not opkinds.is_sparse_op(ops[3], 4096)
    assert opkinds.is_collective(ops[5]) and not opkinds.is_kernel(ops[5])
    lo, hi = trace.window_of(tr)
    took = trace.seconds_matching(
        ops, lo, hi, lambda o: opkinds.is_sparse_op(o, 4096, 64))
    assert took == pytest.approx(0.050)


@pytest.mark.parametrize("chips, slots, ids", [(1, 4096, 64),
                                                (4, 16384, 256)])
def test_a_sharded_table_is_found_by_one_chips_share(tr, chips, slots, ids):
    """Over four chips the trace shows the shard's rows (4096 of 16384)
    and the shard's ids (64 of 256): the same ops are the sparse work."""
    from types import SimpleNamespace
    run = SimpleNamespace(trace=tr, traced_steps=2, chips=chips,
                          trace_summary=trace.summarize(tr),
                          config={"num_slots": slots},
                          info={"rows_per_step": ids})
    assert opkinds.sparse_seconds_per_step(run) == pytest.approx(0.025)


def test_an_op_event_named_by_its_hlo_line_is_parsed():
    text = ("%fusion.9 = f32[67108864,10]{0,1:T(8,128)} fusion(f32[67108864"
            ",10]{0,1:T(8,128)} %state__emb___0_.1, s32[425984]{0:T(1024)S(1"
            ")} %copy-done.2, f32[425984,10]{0,1:T(8,128)} %gte.21), kind=kC"
            "ustom, calls=%fused_computation.9")
    name, opcode, shapes = trace.parse_hlo(text)
    assert (name, opcode) == ("fusion.9", "fusion")
    assert shapes == ("f32[67108864,10] <- f32[67108864,10],s32[425984],"
                      "f32[425984,10]")
    op = trace.Op(name, opcode, shapes, 0.0, 1.0)
    assert opkinds.is_sparse_op(op, 67108864)
    assert op.label.startswith("fusion.9__fusion__f32_67108864_10_f32_")
    t = ("%copy-start.3 = (s32[16384,26]{0,1:T(8,128)S(1)}, s32[16384,26]{0"
         ",1:T(8,128)}, u32[]{:S(2)}) copy-start(s32[16384,26]{0,1:T(8,128)}"
         " %batch__cat__.1)")
    assert trace.parse_hlo(t)[:2] == ("copy-start.3", "copy-start")
    k = ("%custom-call.7 = bf16[16,25,1024,64]{3,2,1,0} custom-call(bf16[16"
         ",25,1024,64]{3,2,1,0} %q), custom_call_target=\"tpu_custom_call\"")
    assert opkinds.is_kernel(trace.Op(*trace.parse_hlo(k), 0.0, 1.0))
    assert trace.parse_hlo("jit_step(123)") == ("jit_step(123)", "", "")


def test_label_names_the_op():
    op = trace.Op("fusion.9", "fusion", "f32[67108864,10]", 0.0, 1.0)
    assert op.label == "fusion.9__fusion__f32_67108864_10"
