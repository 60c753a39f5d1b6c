"""The trace reduction on hand-built event lists."""
import pytest

from chipbench.trace import (busy_seconds, idle_gaps, op_name, reduce_trace,
                             span_at, stage_seconds, union)


def test_union_merges_overlapping_and_touching():
    assert union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)]) == \
        [(0.0, 4.0), (5.0, 6.0)]


def test_busy_and_gaps_are_clipped_to_the_window():
    merged = union([(0, 2), (3, 4), (9, 12)])
    assert busy_seconds(merged, 1, 10) == pytest.approx(1 + 1 + 1)
    assert idle_gaps(merged, 1, 10) == [(2.0, 3.0), (4.0, 9.0)]
    assert idle_gaps([], 0, 5) == [(0, 5)]


def test_gap_goes_to_the_innermost_open_span():
    spans = [("run", 0.0, 10.0), ("submit", 2.0, 3.0), ("wait", 5.0, 9.0)]
    assert span_at(spans, 2.5) == "submit"
    assert span_at(spans, 6.0) == "wait"
    assert span_at(spans, 4.0) == "run"
    assert span_at(spans, 11.0) == "no span"


def test_reduce_trace_idle_share_ops_and_gaps():
    ops = [("fusion.1", 0.0, 1.0, {}), ("segment_agg.1", 1.0, 3.0, {}),
           ("fusion.1", 2.5, 3.5, {}), ("copy", 6.0, 7.0, {})]
    spans = [("run", 0.0, 5.0), ("wait", 5.0, 10.0)]
    red = reduce_trace(ops, spans, 0.0, 10.0)
    assert red["busy_s"] == pytest.approx(4.5)
    assert red["window_s"] == 10.0
    assert 100 * (1 - red["busy_s"] / red["window_s"]) == pytest.approx(55.0)
    assert red["device_ops"][0] == ["fusion.1", 2.0]
    assert red["idle_gaps"] == [["wait", 3.0], ["run", 2.5]]
    assert stage_seconds(ops, ("segment_agg",), 0.0, 2.0) == \
        pytest.approx(1.0)


def test_op_name_is_the_instruction_name():
    text = ('%segment_agg.1 = f32[8,194688]{1,0} custom-call(s32[1,400384] '
            '%pad.10), custom_call_target="tpu_custom_call"')
    assert op_name(text) == "segment_agg.1"
    fused = "%slice_reduce_fusion = (f32[9]) fusion(f32[8,9] %segment_agg.1)"
    assert op_name(fused) == "slice_reduce_fusion"


def test_busy_is_averaged_over_devices():
    ops = [("a", 0, 4, {"device": "/device:TPU:0"}),
           ("a", 0, 2, {"device": "/device:TPU:1"})]
    assert reduce_trace(ops, [], 0, 4, n_devices=2)["busy_s"] == 3.0
