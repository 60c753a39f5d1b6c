"""Query-serving subsystem: multi-query seam parity (base loop-over-
queries oracle vs jax stacked dispatch), the coalesced launch contract
(Q compatible queries ⇒ ⌈shards/wave⌉ total device dispatches), server
admission/coalescing/fallback behavior, the TTL + LRU result cache with
fault injection, and the concurrency-safety satellites (thread-scoped
launch counters, DeviceCache priming under concurrent open/close)."""
import gc
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import BETWEEN, P, Session, fdb, group, proto
from repro.core.planner import plan_flow
from repro.exec import AdHocEngine, Catalog, JaxBackend, get_backend
from repro.exec.batched import FUSED_ENV
from repro.fdb import DOUBLE, INT, STRING, Schema, build_fdb
from repro.fdb.schema import Field, MESSAGE
from repro.geo import AreaTree, mercator as M
from repro.kernels import ops
from repro.serve import QueryServer, ResultCache, ServerBusy
from repro.tess import Tesseract

SIZES = [32, 31, 64, 65, 1, 0, 33]
RNG = np.random.default_rng(41)


# --------------------------------------------------------------- fixtures

def _dense_db(name):
    schema = Schema(name, [
        Field("road", INT, indexes=("tag",)),
        Field("hour", INT, indexes=("range",)),
        Field("city", STRING, indexes=("tag",)),
        Field("speed", DOUBLE),
    ])
    bounds = np.cumsum([0] + SIZES)
    recs = [{"road": int(RNG.integers(0, 12)),
             "hour": int(RNG.integers(0, 24)),
             "city": ["SF", "OAK", "SJ"][int(RNG.integers(0, 3))],
             "speed": float(RNG.normal(48, 9)),
             "_i": i}
            for i in range(sum(SIZES))]
    key = lambda r: int(np.searchsorted(bounds, r["_i"], "right") - 1)
    return build_fdb(name, schema, recs, num_shards=len(SIZES),
                     shard_key=key)


def _walks_db(name):
    schema = Schema(name, [
        Field("id", INT, indexes=("tag",)),
        Field("track", MESSAGE, fields=[
            Field("lat", DOUBLE, repeated=True),
            Field("lng", DOUBLE, repeated=True),
            Field("t", DOUBLE, repeated=True)],
            indexes=("spacetime",),
            index_params={"level": 6, "bucket_s": 900.0, "epoch": 0.0}),
    ])
    rng = np.random.default_rng(17)
    recs = []
    for i in range(sum(SIZES)):
        ln = 0 if i % 7 == 0 else int(rng.integers(1, 14))
        recs.append({"id": i, "track": {
            "lat": rng.uniform(37.2, 38.0, ln).tolist(),
            "lng": rng.uniform(-122.6, -121.8, ln).tolist(),
            "t": np.sort(rng.uniform(0.0, 3 * 86400.0, ln)).tolist()}})
    bounds = np.cumsum([0] + SIZES)
    key = lambda r: int(np.searchsorted(bounds, r["id"], "right") - 1)
    return build_fdb(name, schema, recs, num_shards=len(SIZES),
                     shard_key=key)


def _region(rng, d=2_000_000):
    ix, iy = M.latlng_to_xy(rng.uniform(37.2, 38.0),
                            rng.uniform(-122.6, -121.8))
    return AreaTree.from_box(int(ix) - d, int(iy) - d,
                             int(ix) + d, int(iy) + d, max_level=7)


@pytest.fixture(scope="module")
def walks_db():
    return _walks_db("ServeWalks")


@pytest.fixture(scope="module")
def dense_db():
    return _dense_db("ServeDense")


@pytest.fixture(scope="module")
def catalog(walks_db, dense_db):
    cat = Catalog(server_slots=16)
    cat.register(walks_db)
    cat.register(dense_db)
    return cat


def _tess_flows(n=5, seed=5):
    rng = np.random.default_rng(seed)
    flows = [fdb("ServeWalks").tesseract(
        Tesseract(_region(rng), 0.0, 2 * 86400.0)) for _ in range(n - 1)]
    flows.append(fdb("ServeWalks").tesseract(
        Tesseract(_region(rng), 0.0, 2 * 86400.0)
        .then(_region(rng), 0.0, 3 * 86400.0)))
    return flows


def assert_identical(a, b):
    assert a.n == b.n
    assert a.paths() == b.paths()
    for p in a.paths():
        ca, cb = a[p], b[p]
        assert ca.values.dtype == cb.values.dtype, p
        assert np.array_equal(ca.values, cb.values), p
        assert ca.vocab == cb.vocab, p


def _server(catalog, backend="jax", **kw):
    srv = QueryServer(catalog=catalog, backend=backend, start=False, **kw)
    srv.engine.wave = 3
    return srv


# ------------------------------------------------- seam: multi-query ops

@pytest.mark.tesseract
def test_seam_multi_ops_match_base_oracle(catalog, walks_db):
    """probe_shards_multi / refine_tracks_multi / run_wave_fused_multi on
    the jax backend ≡ the base-class loop-over-queries oracle, per query,
    byte for byte (ordered and unordered constraint sets, varying probe
    and constraint counts)."""
    rng = np.random.default_rng(3)
    tesses = [Tesseract(_region(rng), 0.0, 2 * 86400.0)
              .also(_region(rng), 43200.0, 3 * 86400.0),
              Tesseract(_region(rng), 0.0, 86400.0),
              Tesseract(_region(rng), 0.0, 2 * 86400.0)
              .then(_region(rng), 0.0, 3 * 86400.0)]
    plans = [plan_flow(fdb("ServeWalks").tesseract(t), catalog)
             for t in tesses]
    shards = [walks_db.shards[s] for s in plans[0].shard_ids]
    probes_multi = [[[pr.run(sh) for pr in p.probes] for sh in shards]
                    for p in plans]
    refines = [p.refines[0] for p in plans]
    npb = get_backend("numpy")
    jxb = JaxBackend()
    jxb.prime_fdb(walks_db)

    fulls = [sh.all_bitmap() for sh in shards]
    want = npb.probe_shards_multi(fulls, probes_multi)
    got = jxb.probe_shards_multi(fulls, probes_multi)
    for wq, gq in zip(want, got):
        for w, g in zip(wq, gq):
            assert np.array_equal(np.asarray(w), np.asarray(g))

    batches = [sh.batch for sh in shards]
    cons_list = [list(r.constraints) for r in refines]
    edges_list = [list(r.edges) for r in refines]
    want = npb.refine_tracks_multi(batches, "track", cons_list,
                                   edges_list=edges_list)
    got = jxb.refine_tracks_multi(batches, "track", cons_list,
                                  edges_list=edges_list)
    for wq, gq in zip(want, got):
        for w, g in zip(wq, gq):
            assert np.array_equal(np.asarray(w), np.asarray(g))
    # first-hit tables are part of the parity surface
    wantf = npb.refine_tracks_multi(batches, "track", cons_list,
                                    with_first_hits=True)
    gotf = jxb.refine_tracks_multi(batches, "track", cons_list,
                                   with_first_hits=True)
    for (wm, wt), (gm, gt) in zip(wantf, gotf):
        for w, g in zip(wt, gt):
            assert np.array_equal(np.asarray(w), np.asarray(g))

    got = jxb.run_wave_fused_multi(shards, probes_multi, refines)
    assert got is not None
    want = npb.run_wave_fused_multi(shards, probes_multi, refines)
    for q, (w, g) in enumerate(zip(want, got)):
        assert g[0] == w[0], q
        for wi, gi in zip(w[1], g[1]):
            assert gi.dtype == np.int64
            assert np.array_equal(gi, wi), q
    # per query it equals the single-query fused path too
    for q in range(3):
        single = jxb.run_wave_fused(shards, probes_multi[q], refines[q],
                                    None)
        assert single[0] == got[q][0]
        for a, b in zip(single[1], got[q][1]):
            assert np.array_equal(a, b)


# ------------------------------------- coalesced launch contract + parity

@pytest.mark.tesseract
def test_coalesced_launch_contract_and_parity(catalog, walks_db, exec_pplan,
                                              monkeypatch):
    """Q coalesced compatible queries cost Σ_p ⌈shards_p/wave⌉ multi
    dispatches TOTAL — not Q×⌈shards/wave⌉ — and every query's rows are
    byte-identical to its single-query numpy-oracle result.  The serve
    tier merges per-query gathers on the host (partition-invariant), so
    no merge combine is launched at any P."""
    monkeypatch.setenv(FUSED_ENV, "1")
    flows = _tess_flows()
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    srv = _server(catalog, cache=False)
    futs = [srv.submit(f) for f in flows]
    srv.run_pending()                          # warm: prime + jit
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)
    futs = [srv.submit(f) for f in flows]
    ops.reset_launch_counts()
    srv.run_pending()
    waves = exec_pplan(walks_db.num_shards,
                       srv.engine.backend).wave_dispatches(3)
    assert dict(ops.launch_counts()) == {"run_wave_fused_multi": waves}
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)
    st = srv.stats()
    assert st["coalesced_queries"] == 2 * len(flows)
    assert st["fallback_queries"] == 0


@pytest.mark.tesseract
def test_dispatch_spans_count_refine_grid_steps(catalog, walks_db, tmp_path,
                                                monkeypatch):
    """A fused refine's ``dispatch`` span, single query and coalesced
    batch alike, carries the banded grid's steps beside the dense grid's
    they replace (``kernels.refine.grid_steps``)."""
    from profile_capture import captured_spans
    monkeypatch.setenv(FUSED_ENV, "1")
    flows = _tess_flows()
    session = Session(catalog=catalog, backend="jax")
    srv = _server(catalog, cache=False)

    def run():
        session.run(flows[0])
        futs = [srv.submit(f) for f in flows]
        srv.run_pending()
        for f in futs:
            f.result(60)

    run()                                      # compiles outside the trace
    with captured_spans(tmp_path) as got:
        run()
    dispatches = [s for s in got if s.name == "dispatch"]
    assert {"query", "n"} <= {k for s in dispatches for k in s.meta}
    for s in dispatches:
        steps, dense = s.meta["refine_steps"], s.meta["refine_dense"]
        assert 0 < int(steps) <= int(dense), s.meta
        if "n" in s.meta:
            assert int(s.meta["n"]) == len(flows)


def test_coalesced_agg_tail_parity(catalog, monkeypatch):
    """Aggregating flows coalesce too — the selection rides the multi
    dispatch, the group-by runs in the per-query host tail — and match
    the numpy oracle bit for bit (min/max included); record-parallel
    server ops (filter/map) coalesce too."""
    monkeypatch.setenv(FUSED_ENV, "1")
    flows = [fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .aggregate(group(P.road).count("n").avg(m=P.speed)),
             fdb("ServeDense").find(BETWEEN(P.hour, 0, 7))
             .aggregate(group(P.road).max(mx=P.speed).min(mn=P.speed)),
             fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .aggregate(group(P.city).count("n")),
             fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .filter(P.speed > 40.0)
             .aggregate(group(P.road).count("n")),
             fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .map(lambda p: proto(road=p.road, fast=p.speed > 50.0))
             .aggregate(group(P.fast).count("n"))]
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    srv = _server(catalog, cache=False)
    futs = [srv.submit(f) for f in flows]
    srv.run_pending()
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)
    assert srv.stats()["coalesced_queries"] == len(flows)


def test_incompatible_plans_fall_through(catalog, monkeypatch):
    """Plans outside the coalesced shape (a residual filter from an
    unindexed find() conjunct) are served through the single-query path —
    never an error — alongside coalesced peers."""
    monkeypatch.setenv(FUSED_ENV, "1")
    flows = [fdb("ServeDense").find(BETWEEN(P.hour, 8, 17)
                                    & (P.speed > 40.0))
             .aggregate(group(P.road).count("n")),      # residual
             fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .aggregate(group(P.road).count("n")),      # coalesceable
             fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
             .sort_desc(P.speed).limit(10)]             # coalesceable
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    srv = _server(catalog, cache=False)
    futs = [srv.submit(f) for f in flows]
    srv.run_pending()
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)
    assert srv.stats()["fallback_queries"] >= 1


def test_numpy_backend_server_parity(catalog):
    """The server is backend-agnostic: a numpy-backed server coalesces
    through the base-class oracle ops and stays byte-identical."""
    flows = _tess_flows(3, seed=9)
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    srv = _server(catalog, backend="numpy", cache=False)
    futs = [srv.submit(f) for f in flows]
    srv.run_pending()
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)


# ----------------------------------------------------- admission + server

def test_admission_bounds_and_recovery(catalog):
    srv = _server(catalog, backend="numpy", cache=False, max_pending=2)
    f1 = srv.submit(fdb("ServeDense").find(BETWEEN(P.hour, 8, 17)))
    srv.submit(fdb("ServeDense").find(BETWEEN(P.hour, 0, 7)))
    with pytest.raises(ServerBusy):
        srv.submit(fdb("ServeDense").find(BETWEEN(P.hour, 9, 10)))
    assert srv.stats()["rejected"] == 1
    srv.run_pending()                          # queue drains
    assert f1.result(60).batch.n >= 0
    f4 = srv.submit(fdb("ServeDense").find(BETWEEN(P.hour, 9, 10)))
    srv.run_pending()
    assert f4.result(60) is not None


def test_live_scheduler_threaded_submits(catalog):
    """Futures resolve through the running scheduler thread with many
    concurrent submitters; close() drains and joins."""
    flows = _tess_flows(6, seed=13)
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    with QueryServer(catalog=catalog, backend="jax", cache=False,
                     tick_s=0.005) as srv:
        srv.engine.wave = 3
        with ThreadPoolExecutor(max_workers=6) as pool:
            futs = list(pool.map(srv.submit, flows))
        for f, o in zip(futs, oracle):
            assert_identical(f.result(60).batch, o.batch)
        assert srv.stats()["served"] == len(flows)
    with pytest.raises(RuntimeError):
        srv.submit(flows[0])


def test_planning_error_delivered_via_future(catalog):
    srv = _server(catalog, backend="numpy", cache=False)
    fut = srv.submit(fdb("NoSuchDb").find(BETWEEN(P.hour, 0, 1)))
    srv.run_pending()
    with pytest.raises(Exception):
        fut.result(10)


def test_session_serve_integration(catalog):
    sess = Session(catalog=catalog, backend="numpy")
    srv = sess.serve(start=False, cache=False)
    try:
        fut = srv.submit(sess.fdb("ServeDense").find(BETWEEN(P.hour, 8, 17)))
        srv.run_pending()
        assert fut.result(60).batch.n > 0
    finally:
        srv.close()


# ------------------------------------------------------------ result cache

def test_result_cache_hit_skips_recompute(catalog, monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")
    flow = _tess_flows(2, seed=21)[0]
    srv = _server(catalog, cache=ResultCache())
    f1 = srv.submit(flow); srv.run_pending()
    r1 = f1.result(60)
    ops.reset_launch_counts()
    f2 = srv.submit(flow); srv.run_pending()
    assert f2.result(60) is r1                 # same object, no recompute
    assert ops.launch_counts().get("run_wave_fused", 0) == 0
    assert ops.launch_counts().get("run_wave_fused_multi", 0) == 0
    assert srv.stats()["cache_hits"] == 1


def test_result_cache_ttl_and_injectable_clock(catalog):
    clock = [0.0]
    cache = ResultCache(ttl_s={"result": 10.0, "postings": 5.0},
                        clock=lambda: clock[0])
    srv = _server(catalog, backend="numpy", cache=cache)
    flow = fdb("ServeDense").find(BETWEEN(P.hour, 8, 17))
    f1 = srv.submit(flow); srv.run_pending(); r1 = f1.result(60)
    clock[0] = 9.0                             # still live
    f2 = srv.submit(flow); srv.run_pending()
    assert f2.result(60) is r1
    clock[0] = 20.0                            # expired
    f3 = srv.submit(flow); srv.run_pending()
    r3 = f3.result(60)
    assert r3 is not r1
    assert_identical(r3.batch, r1.batch)


def test_result_cache_lru_byte_budget():
    clock = [0.0]
    cache = ResultCache(max_bytes=3000, clock=lambda: clock[0])
    a1 = np.zeros(250, dtype=np.float64)       # 2000 bytes
    cache.put("result", b"k1", a1, nbytes=a1.nbytes)
    cache.put("result", b"k2", np.zeros(100), nbytes=800)
    assert cache.get("result", b"k1") is a1    # k1 now most-recent
    cache.put("result", b"k3", np.zeros(100), nbytes=800)   # evicts k2
    assert cache.get("result", b"k2") is None
    assert cache.get("result", b"k1") is a1
    assert cache.stats()["evictions"] == 1
    assert cache.stats()["nbytes"] <= 3000


def test_result_cache_key_isolation(catalog, dense_db):
    """Different plans → different keys; an uncanonicalizable plan is
    simply uncacheable (None key), never a false share."""
    cache = ResultCache()
    p1 = plan_flow(fdb("ServeDense").find(BETWEEN(P.hour, 8, 17)), catalog)
    p2 = plan_flow(fdb("ServeDense").find(BETWEEN(P.hour, 8, 18)), catalog)
    k1 = cache.key_for(dense_db, p1)
    k2 = cache.key_for(dense_db, p2)
    assert k1 is not None and k2 is not None and k1 != k2
    assert cache.key_for(dense_db, p1) == k1   # deterministic
    class Weird:
        pass
    p1b = plan_flow(fdb("ServeDense").find(BETWEEN(P.hour, 8, 17)),
                    catalog)
    p1b.mixer_ops = list(p1b.mixer_ops) + [lambda x: x]    # opaque
    assert cache.key_for(dense_db, p1b) is None


def test_broken_cache_never_fails_a_query(catalog, monkeypatch):
    """Fault injection: a cache whose every method raises degrades the
    server to recomputation — every query still answers correctly."""
    monkeypatch.setenv(FUSED_ENV, "1")

    class BrokenCache:
        def key_for(self, *a, **k): raise RuntimeError("cache down")
        def get(self, *a, **k): raise RuntimeError("cache down")
        def put(self, *a, **k): raise RuntimeError("cache down")
        def stats(self): raise RuntimeError("cache down")

    flows = _tess_flows(3, seed=29)
    np_eng = AdHocEngine(catalog, num_servers=2, backend="numpy", wave=3)
    oracle = [np_eng.collect(f) for f in flows]
    srv = _server(catalog, cache=BrokenCache())
    futs = [srv.submit(f) for f in flows]
    srv.run_pending()
    for f, o in zip(futs, oracle):
        assert_identical(f.result(60).batch, o.batch)
    assert srv.stats()["cache_errors"] > 0


# --------------------------------------------- concurrency-safety satellites

def test_launch_counter_two_threads():
    """record_launch is concurrency-safe: the aggregate view sums both
    threads exactly; scope="thread" sees only the calling thread's own
    launches."""
    ops.reset_launch_counts()
    n = 5000
    per_thread = {}
    barrier = threading.Barrier(2)

    def worker(tid):
        barrier.wait()
        for _ in range(n):
            ops.record_launch("probe_x")
        per_thread[tid] = ops.launch_counts(scope="thread")

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert ops.launch_counts()["probe_x"] == 2 * n     # no lost updates
    assert per_thread[0]["probe_x"] == n
    assert per_thread[1]["probe_x"] == n
    # the main thread recorded nothing
    assert ops.launch_counts(scope="thread").get("probe_x", 0) == 0
    ops.reset_launch_counts()
    assert ops.launch_counts() == {}
    assert ops.launch_counts(scope="thread") == {}
    with pytest.raises(ValueError):
        ops.launch_counts(scope="bogus")


def test_device_cache_concurrent_prime_and_release():
    """Concurrent prime_fdb of the SAME FDb from many threads yields one
    consistent buffer census; concurrent open/close of distinct FDbs
    refcounts correctly (shared-shard snapshots keep buffers alive until
    the last reference dies)."""
    db = _dense_db("ServePrimeRace")
    be = JaxBackend()
    counts = []

    def prime():
        counts.append(be.prime_fdb(db))

    ts = [threading.Thread(target=prime) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    expect = db.num_shards * 5                 # bitmap + 4 column buffers
    assert len(be.device_cache) == expect
    assert sum(1 for c in counts if c > 0) == 1    # exactly one real prime

    # churn: concurrent open/close of short-lived FDbs never corrupts the
    # census and everything evicts once dead
    def churn(i):
        d = _dense_db(f"ServeChurn{i}")
        be.prime_fdb(d)
        assert be.device_cache.get(d.shards[0].batch["speed"].values) \
            is not None

    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(churn, range(8)))
    gc.collect()
    time.sleep(0.05)
    gc.collect()
    assert len(be.device_cache) == expect      # only the live db remains
    del db
    gc.collect()
    assert len(be.device_cache) == 0
