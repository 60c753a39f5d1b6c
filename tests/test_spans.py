"""The query path's host spans (``repro.spans``) and the server's queue
counters: one query's spans nest under its ``query`` span and carry its
number, a coalesced batch opens one ``serve.batch`` span over per-query
``plan`` spans, the server counts how long each query waited in its
queue, and spans change no result, traced or not."""
import contextlib
import time

import numpy as np
import pytest

from repro import spans
from repro.core import BETWEEN, P, Session, fdb, group
from repro.serve import QueryServer

from profile_capture import captured_spans

AGG = (fdb("Obs").find(BETWEEN(P.hour, 8, 17))
       .aggregate(group(P.road_id).count("n").avg(m=P.speed)))
SELECT = fdb("Obs").find(BETWEEN(P.hour, 8, 9))


def _hours(lo):
    return (fdb("Obs").find(BETWEEN(P.hour, lo, lo + 3))
            .aggregate(group(P.road_id).count("n")))


def assert_identical(a, b):
    assert a.n == b.n and a.paths() == b.paths()
    for p in a.paths():
        assert a[p].values.dtype == b[p].values.dtype, p
        assert np.array_equal(a[p].values, b[p].values), p


@pytest.mark.parametrize("flow, tail", [(AGG, "finalize"),
                                        (SELECT, "gather")],
                         ids=["agg", "select"])
def test_session_run_spans_nest_under_one_query(catalog, tmp_path, flow,
                                                tail):
    session = Session(catalog=catalog, backend="jax")
    session.run(flow)                          # compiles outside the trace
    with captured_spans(tmp_path) as got:
        session.run(flow)
    roots = [s for s in got if s.name == "query"]
    assert len(roots) == 1
    root = roots[0]
    q = root.meta["query"]
    inner = [s for s in got if s is not root]
    names = {s.name for s in inner}
    assert {"plan", "prime", "probe", "stack", "dispatch", "sync", tail,
            "mix"} <= names, names
    assert all(root.holds(s) for s in inner)
    # the prefetch staged inside the backend is the one span without it
    assert all(s.meta.get("query") == q for s in inner
               if s.name != "prefetch")


def test_coalesced_batch_opens_one_batch_span(catalog, tmp_path):
    server = QueryServer(catalog=catalog, backend="jax", start=False,
                         cache=False)
    flows = [_hours(lo) for lo in (0, 6, 12)]
    for f in flows:
        server.submit(f)
    server.run_pending()                       # compiles outside the trace
    with captured_spans(tmp_path) as got:
        futs = [server.submit(f) for f in flows]
        server.run_pending()
    for f in futs:
        f.result(60)
    assert server.stats()["coalesced_queries"] == 2 * len(flows)
    batches = [s for s in got if s.name == "serve.batch"]
    assert len(batches) == 1 and batches[0].meta["n"] == len(flows)
    submitted = [s.meta["query"] for s in got if s.name == "submit"]
    plans = [s for s in got if s.name == "plan"]
    assert sorted(s.meta["query"] for s in plans) == sorted(submitted)
    assert len(set(submitted)) == len(flows)
    assert all(batches[0].holds(s) for s in plans)
    assert "query" not in {s.name for s in got}  # no single-query fallback


def test_queue_wait_counts_each_dequeued_query(catalog):
    server = QueryServer(catalog=catalog, backend="numpy", start=False,
                         cache=False)
    st = server.stats()
    assert st["dequeued"] == 0 and st["queue_wait_ms"] == 0.0
    futs = [server.submit(_hours(0)), server.submit(_hours(6))]
    time.sleep(0.05)
    server.run_pending()
    st = server.stats()
    assert st["dequeued"] == 2
    assert st["queue_wait_ms"] >= 2 * 50.0
    futs.append(server.submit(_hours(12)))
    time.sleep(0.02)
    server.run_pending()
    st2 = server.stats()
    assert st2["dequeued"] == 3
    assert st2["queue_wait_ms"] - st["queue_wait_ms"] >= 20.0
    for f in futs:
        f.result(60)
    # the scheduler thread counts what it takes off the queue too
    with QueryServer(catalog=catalog, backend="numpy", cache=False) as live:
        live.collect(_hours(0), timeout=60)
        st = live.stats()
        assert st["dequeued"] == 1 and st["queue_wait_ms"] > 0.0


def test_spans_change_no_result(catalog, tmp_path, monkeypatch):
    """The same query with every span a no-op, with real spans and no
    trace, and under an active trace: byte-identical answers."""
    session = Session(catalog=catalog, backend="jax")
    untraced = session.run(AGG)
    with captured_spans(tmp_path) as got:
        traced = session.run(AGG)
    assert any(s.name == "query" for s in got)
    monkeypatch.setattr(spans, "TraceAnnotation",
                        lambda name, **meta: contextlib.nullcontext())
    plain = session.run(AGG)
    assert_identical(traced.batch, plain.batch)
    assert_identical(untraced.batch, plain.batch)
