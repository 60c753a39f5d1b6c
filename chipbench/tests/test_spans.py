"""The program's spans as the per-layer metrics read them: on hand-built
lists, and in traced runs of the harness on the CPU."""
import pytest

from chipbench.spans import (describe, idle_attributed, queue_waits,
                             span_seconds)


def sp(name, s, e, **meta):
    return (name, float(s), float(e), meta)


def test_span_seconds_clips_to_the_slice_and_adds_threads():
    spans = [sp("plan", 0, 2), sp("gather", 1, 4), sp("gather", 1, 4),
             sp("mix", 9, 12)]
    assert span_seconds(spans, ("plan",), 1, 10) == pytest.approx(1.0)
    # two threads gathering at once: both count
    assert span_seconds(spans, ("gather", "mix"), 0, 10) == \
        pytest.approx(3 + 3 + 1)
    assert span_seconds(spans, ("probe",), 0, 10) == 0.0


def test_a_gap_is_named_by_the_innermost_program_span_on_any_thread():
    spans = [sp("serve.batch", 0, 10, n=8), sp("gather", 2, 3, query=4),
             sp("mix", 5, 9, query=5), sp("plan", 5.5, 6, query=6)]
    ops = [("refine_tracks_multi.1", 0, 2, {}), ("fusion", 4, 5, {}),
           ("copy", 5.5, 5.7, {}), ("copy", 9.5, 10, {})]
    # idle [2, 4] (middle under gather), [5, 5.5] (mix), [5.7, 9.5] (the
    # middle 7.6 under mix), [10, 11] (no span)
    got = describe(ops, spans, [(5.6, 5.7)], 0, 11)
    assert got["idle_gaps"] == [["mix", pytest.approx(3.8)],
                                ["gather", 2.0], ["no span", 1.0],
                                ["mix", 0.5]]
    assert got["idle_s"] == {"mix": pytest.approx(4.3), "gather": 2.0,
                             "no span": 1.0}
    # an executable obtained inside the plan span, inside mix
    assert got["compiles"] == [["plan", pytest.approx(0.1)]]
    assert got["span_s"]["serve.batch"] == 10.0


def test_idle_attributed_counts_idle_time_under_layer_spans_only():
    ops = [("segment_agg.1", 0, 2, {}), ("segment_agg.1", 6, 8, {})]
    # idle: [2, 6] and [8, 10]; layer spans cover [2, 3] and [5, 7]
    spans = [sp("query", 0, 10, query=1), sp("finalize", 2, 3, query=1),
             sp("mix", 5, 7, query=1)]
    idle, explained = idle_attributed(ops, spans, 0, 10)
    assert idle == pytest.approx(6.0)
    assert explained == pytest.approx(2.0)
    # overlapping layer spans count once
    idle, explained = idle_attributed(
        ops, spans + [sp("gather", 2, 4, query=1)], 0, 10)
    assert explained == pytest.approx(3.0)
    assert idle_attributed([], [], 0, 4) == (4, 0.0)


def test_queue_wait_runs_from_submit_to_the_batch_that_planned_it():
    spans = [sp("submit", 0.0, 0.001, query=1),
             sp("submit", 0.5, 0.501, query=2),
             sp("serve.batch", 1.0, 3.0, n=2),
             sp("plan", 1.1, 1.2, query=1), sp("plan", 1.2, 1.3, query=2),
             # a fallback collect plans query 2 again: counted once
             sp("plan", 2.0, 2.1, query=2),
             sp("submit", 2.5, 2.501, query=3),
             sp("serve.batch", 3.5, 4.0, n=1), sp("plan", 3.6, 3.7, query=3)]
    assert sorted(queue_waits(spans, 0, 10)) == pytest.approx([0.5, 1.0, 1.0])
    # a batch that starts outside the slice is not read
    assert queue_waits(spans, 3.2, 10) == pytest.approx([1.0])


def test_traced_runs_report_the_span_metrics(small_run):
    C, B = "trips.tess.analyst", "trips.tess.served8"
    res = small_run(C, 7, trace=1)
    assert res["correct"]
    for name in ("plan_ms", "probe_ms", "stack_ms", "tail_ms",
                 "idle_attributed_pct"):
        assert name in res["metrics"], name
        assert res["metrics"][name]["value"] >= 0
    assert res["metrics"]["idle_attributed_pct"]["value"] <= 100
    assert "queue_wait_ms" not in res["metrics"]
    res = small_run(B, 2**33 + 5, trace=1)
    assert res["correct"]
    assert res["metrics"]["queue_wait_ms"]["value"] > 0
