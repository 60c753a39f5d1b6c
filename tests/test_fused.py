"""Fused per-wave device pipeline: byte parity of ``run_wave_fused``
(numpy loop-over-stages oracle vs the jax single-dispatch pipeline, on
ragged/empty/word-boundary shards, with and without the segment-agg
tail), the one-fused-dispatch-per-wave launch contract, the async
prefetch ordering evidence, the keyed stacked-buffer cache, the
``postings_bitmap`` lowering of ``SpaceTimeIndex.lookup``, and parity of
every fallback path that must decline fusion."""
import gc
import math

import numpy as np
import pytest

from repro.core import BETWEEN, P, group, fdb
from repro.core.planner import plan_flow
from repro.exec import AdHocEngine, Catalog, JaxBackend, get_backend
from repro.exec.batched import (FUSED_ENV, FusedAggPlan, fused_agg_plan,
                                fused_enabled)
from repro.fdb import Schema, build_fdb, DOUBLE, INT, STRING
from repro.fdb.schema import Field, MESSAGE
from repro.geo import AreaTree, mercator as M
from repro.kernels import ops
from repro.tess import Tesseract

from profile_capture import captured_spans

RNG = np.random.default_rng(23)

#: word-boundary shard sizes — 32-bit bitmap words must not leak pad docs
SIZES = [32, 31, 64, 65, 1, 0, 33]


# --------------------------------------------------------------- fixtures

def _dense_db(name="FusedAgg"):
    """Word-boundary shard sizes incl. an empty shard, dense columns only
    (the fused agg tail requires them)."""
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
    db = build_fdb(name, schema, recs, num_shards=len(SIZES),
                   shard_key=key)
    assert [s.n for s in db.shards] == SIZES
    return db


def _walks_db(name="FusedWalks"):
    """Ragged spacetime tracks, empty tracks and an empty shard included."""
    schema = Schema(name, [
        Field("id", INT, indexes=("tag",)),
        Field("track", MESSAGE, fields=[
            Field("lat", DOUBLE, repeated=True),
            Field("lng", DOUBLE, repeated=True),
            Field("t", DOUBLE, repeated=True)],
            indexes=("spacetime",),
            index_params={"level": 6, "bucket_s": 900.0, "epoch": 0.0}),
    ])
    rng = np.random.default_rng(7)
    recs = []
    for i in range(sum(SIZES)):
        ln = 0 if i % 7 == 0 else int(rng.integers(1, 14))
        recs.append({"id": i, "track": {
            "lat": rng.uniform(37.2, 38.0, ln).tolist(),
            "lng": rng.uniform(-122.6, -121.8, ln).tolist(),
            "t": np.sort(rng.uniform(0.0, 3 * 86400.0, ln)).tolist()}})
    bounds = np.cumsum([0] + SIZES)
    key = lambda r: int(np.searchsorted(bounds, r["id"], "right") - 1)
    db = build_fdb(name, schema, recs, num_shards=len(SIZES),
                   shard_key=key)
    assert [s.n for s in db.shards] == SIZES
    return db


def _region(rng, d=2_000_000):
    ix, iy = M.latlng_to_xy(rng.uniform(37.2, 38.0),
                            rng.uniform(-122.6, -121.8))
    return AreaTree.from_box(int(ix) - d, int(iy) - d,
                             int(ix) + d, int(iy) + d, max_level=7)


@pytest.fixture(scope="module")
def dense_db():
    return _dense_db()


@pytest.fixture(scope="module")
def walks_db():
    return _walks_db()


@pytest.fixture(scope="module")
def dense_catalog(dense_db):
    cat = Catalog(server_slots=16)
    cat.register(dense_db)
    return cat


@pytest.fixture(scope="module")
def walks_catalog(walks_db):
    cat = Catalog(server_slots=16)
    cat.register(walks_db)
    return cat


AGG_FLOW = (fdb("FusedAgg").find(BETWEEN(P.hour, 8, 17))
            .aggregate(group(P.road).count("n").avg(m=P.speed)
                       .std_dev(s=P.speed)))

MINMAX_FLOW = (fdb("FusedAgg").find(BETWEEN(P.hour, 8, 17))
               .aggregate(group(P.road).count("n").min(mn=P.speed)
                          .max(mx=P.speed).avg(m=P.speed)))


def _tess(rng):
    return Tesseract(_region(rng), 0.0, 2 * 86400.0).also(
        _region(rng), 43200.0, 3 * 86400.0)


def assert_identical(a, b):
    assert a.n == b.n
    assert a.paths() == b.paths()
    for p in a.paths():
        ca, cb = a[p], b[p]
        assert ca.values.dtype == cb.values.dtype, p
        assert np.array_equal(ca.values, cb.values), p
        assert ca.vocab == cb.vocab, p


# ------------------------------------------------ direct op parity (oracle)

def _agg_call_args(catalog, db, flow=AGG_FLOW):
    """(shards, probes, fused_agg) for a direct run_wave_fused call."""
    plan = plan_flow(flow, catalog)
    shards = [db.shards[s] for s in plan.shard_ids]
    probes = [[p.run(sh) for p in plan.probes] for sh in shards]
    agg = fused_agg_plan(plan, shards)
    assert isinstance(agg, FusedAggPlan)       # eligibility, not a fluke
    return shards, probes, agg


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_run_wave_fused_agg_parity(dense_catalog, dense_db, impl):
    """jax fused pipeline ≡ numpy loop-over-stages oracle: candidate
    counts and selected ids bit-exact; segment partials bit-exact on the
    reference impl, allclose on interpret (f32 value staging)."""
    shards, probes, agg = _agg_call_args(dense_catalog, dense_db)
    npb = get_backend("numpy")
    jxb = JaxBackend(impl=impl)
    jxb.prime_fdb(dense_db)
    want = npb.run_wave_fused(shards, probes, None, agg)
    got = jxb.run_wave_fused(shards, probes, None, agg)
    assert got is not None
    exact = impl == "reference"
    _assert_fused_equal(want, got, exact=exact)


def _assert_fused_equal(want, got, exact=True):
    wn, wids, wseg = want
    gn, gids, gseg = got
    assert gn == wn
    for gi, wi in zip(gids, wids):
        assert gi.dtype == np.int64
        assert np.array_equal(gi, wi)
    if wseg is None:
        assert gseg is None
        return
    assert len(gseg) == len(wseg)
    for (wu, wslots), (gu, gslots) in zip(wseg, gseg):
        assert np.array_equal(gu, wu)
        assert len(gslots) == len(wslots)
        # slots are (count, sum, sumsq[, min, max]) — min/max planes only
        # on slots a min/max agg reads
        for wslot, gslot in zip(wslots, gslots):
            assert len(gslot) == len(wslot)
            assert np.array_equal(gslot[0], wslot[0])  # counts always exact
            for k, (wa, ga) in enumerate(zip(wslot[1:], gslot[1:]), 1):
                if exact:
                    assert np.array_equal(ga, wa), k
                else:
                    assert np.allclose(ga, wa, rtol=1e-4), k


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_run_wave_fused_minmax_parity(dense_catalog, dense_db, impl):
    """min/max lowered into the fused agg tail: the extra segment min/max
    planes match the host oracle — bit-exact on the reference impl (f64
    segment reductions are order-independent), allclose on interpret
    (the monotone f64→f32 value cast commutes with min/max)."""
    shards, probes, agg = _agg_call_args(dense_catalog, dense_db,
                                         MINMAX_FLOW)
    assert agg.minmax == (True,)               # speed slot carries min/max
    npb = get_backend("numpy")
    jxb = JaxBackend(impl=impl)
    jxb.prime_fdb(dense_db)
    want = npb.run_wave_fused(shards, probes, None, agg)
    got = jxb.run_wave_fused(shards, probes, None, agg)
    assert got is not None
    # min/max planes actually present: 5-wide slots on the flagged slot
    assert all(len(slot) == 5 for _u, slots in want[2] if slots
               for slot in slots)
    _assert_fused_equal(want, got, exact=impl == "reference")


def test_fused_launch_contract_minmax(dense_catalog, dense_db, exec_pplan,
                                      monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")
    """A min/max group-by no longer declines fusion: whole query in
    ⌈shards_p/wave⌉ fused dispatches per partition (+ one merge combine
    when P>1), result identical to the numpy host path."""
    a = AdHocEngine(dense_catalog, num_servers=2, backend="numpy",
                    wave=3).collect(MINMAX_FLOW)
    eng = AdHocEngine(dense_catalog, num_servers=2, backend="jax", wave=3)
    eng.collect(MINMAX_FLOW)                   # warm
    ops.reset_launch_counts()
    b = eng.collect(MINMAX_FLOW)
    pp = exec_pplan(dense_db.num_shards, eng.backend)
    want = {"run_wave_fused": pp.wave_dispatches(3)}
    if pp.merge_combines():
        want["merge_partials"] = pp.merge_combines()
    assert dict(ops.launch_counts()) == want
    assert_identical(a.batch, b.batch)


@pytest.mark.tesseract
@pytest.mark.parametrize("ordered", [False, True])
def test_run_wave_fused_refine_parity(walks_catalog, walks_db, ordered):
    """Fused probe→refine→compact ≡ oracle on ragged/empty tracks, with
    unordered and ordered (first-hit edge) constraint sets."""
    rng = np.random.default_rng(3)
    tess = Tesseract(_region(rng), 0.0, 2 * 86400.0)
    tess = (tess.then if ordered else tess.also)(
        _region(rng), 43200.0, 3 * 86400.0)
    plan = plan_flow(fdb("FusedWalks").tesseract(tess), walks_catalog)
    assert len(plan.refines) == 1
    if ordered:
        assert plan.refines[0].edges == [(0, 1)]
    shards = [walks_db.shards[s] for s in plan.shard_ids]
    probes = [[p.run(sh) for p in plan.probes] for sh in shards]
    npb = get_backend("numpy")
    jxb = JaxBackend()
    jxb.prime_fdb(walks_db)
    want = npb.run_wave_fused(shards, probes, plan.refines[0], None)
    got = jxb.run_wave_fused(shards, probes, plan.refines[0], None)
    assert got is not None
    _assert_fused_equal(want, got)
    assert sum(len(i) for i in got[1]) > 0     # the query actually selects


def test_run_wave_fused_declines_to_legacy_path(walks_db):
    """The fused override returns None — engine falls back to the
    per-primitive path — when the refine exceeds the kernel's packed
    constraint budget (>30), and when every track in the wave is empty
    (the legacy path's host shortcut already covers that)."""
    rng = np.random.default_rng(4)
    jxb = JaxBackend()
    jxb.prime_fdb(walks_db)
    cat = Catalog(); cat.register(walks_db)
    # 31 constraints exceed the refine kernel's packed-constraint budget
    many = _tess(rng)
    for _ in range(29):
        many = many.also(_region(rng), 0.0, 86400.0)
    plan = plan_flow(fdb("FusedWalks").tesseract(many), cat)
    assert len(plan.refines[0].constraints) == 31
    shards = [walks_db.shards[s] for s in plan.shard_ids]
    probes = [[p.run(sh) for p in plan.probes] for sh in shards]
    assert jxb.run_wave_fused(shards, probes, plan.refines[0], None) is None
    # all-empty tracks → zero-width point stack → decline (p_max == 0)
    schema = walks_db.schema
    recs = [{"id": i, "track": {"lat": [], "lng": [], "t": []}}
            for i in range(12)]
    empty_db = build_fdb("FusedEmptyTracks", schema, recs, num_shards=3)
    cat2 = Catalog(); cat2.register(empty_db)
    plan2 = plan_flow(fdb("FusedEmptyTracks").tesseract(
        _tess(np.random.default_rng(1))), cat2)
    jxb.prime_fdb(empty_db)
    shards2 = [empty_db.shards[s] for s in plan2.shard_ids]
    probes2 = [[p.run(sh) for p in plan2.probes] for sh in shards2]
    assert jxb.run_wave_fused(shards2, probes2, plan2.refines[0],
                              None) is None
    # the engine still answers (empty) through the fallback
    res = AdHocEngine(cat2, num_servers=2, backend=jxb, wave=3).collect(
        fdb("FusedEmptyTracks").tesseract(_tess(np.random.default_rng(1))))
    assert res.batch.n == 0


# ------------------------------------------------- engine launch contract

def test_fused_launch_contract_agg(dense_catalog, dense_db, exec_pplan,
                                   monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")   # fused on even on the fused=0 CI leg
    """One fused dispatch per wave is the WHOLE query: launch counts are
    exactly {run_wave_fused: Σ_p ⌈shards_p/wave⌉} plus one merge combine
    when P>1 — no per-primitive launches."""
    for wave in (3, 1):                        # wave=1 covers empty waves
        eng = AdHocEngine(dense_catalog, num_servers=2, backend="jax",
                          wave=wave)
        eng.collect(AGG_FLOW)                  # warm: prime + jit caches
        ops.reset_launch_counts()
        res = eng.collect(AGG_FLOW)
        assert res.batch.n > 0
        pp = exec_pplan(dense_db.num_shards, eng.backend)
        want = {"run_wave_fused": pp.wave_dispatches(wave)}
        if pp.merge_combines():
            want["merge_partials"] = pp.merge_combines()
        assert dict(ops.launch_counts()) == want


@pytest.mark.tesseract
def test_fused_launch_contract_refine(walks_catalog, walks_db, exec_pplan,
                                      monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")
    """Tesseract selection rides the same single dispatch: zero batched
    per-primitive refine/compact launches (and no merge combine — the
    selection path concatenates, it doesn't aggregate)."""
    flow = fdb("FusedWalks").tesseract(_tess(np.random.default_rng(11)))
    wave = 3
    eng = AdHocEngine(walks_catalog, num_servers=2, backend="jax",
                      wave=wave)
    eng.collect(flow)                          # warm
    ops.reset_launch_counts()
    eng.collect(flow)
    lc = ops.launch_counts()
    waves = exec_pplan(walks_db.num_shards,
                       eng.backend).wave_dispatches(wave)
    assert lc.get("run_wave_fused") == waves
    assert lc.get("merge_partials", 0) == 0
    assert lc.get("bitmap_intersect_batched", 0) == 0
    assert lc.get("refine_tracks_batched", 0) == 0
    assert lc.get("refine_tracks", 0) == 0
    assert lc.get("compact_batched", 0) == 0


def test_fused_env_kill_switch(dense_catalog, monkeypatch):
    """REPRO_EXEC_FUSED=0 restores the legacy per-primitive wave path,
    byte-identically."""
    monkeypatch.setenv(FUSED_ENV, "1")
    fused = AdHocEngine(dense_catalog, num_servers=2, backend="jax",
                        wave=3).collect(AGG_FLOW)
    monkeypatch.setenv(FUSED_ENV, "0")
    assert not fused_enabled()
    legacy = AdHocEngine(dense_catalog, num_servers=2, backend="jax",
                         wave=3).collect(AGG_FLOW)
    ops.reset_launch_counts()
    AdHocEngine(dense_catalog, num_servers=2, backend="jax",
                wave=3).collect(AGG_FLOW)
    assert ops.launch_counts().get("run_wave_fused", 0) == 0
    assert_identical(fused.batch, legacy.batch)


# ----------------------------------------------- prefetch + keyed caching

def test_prefetch_stages_next_wave_before_wave_done(dense_catalog,
                                                    monkeypatch,
                                                    exec_pplan, tmp_path):
    monkeypatch.setenv(FUSED_ENV, "1")
    """The fused dispatch hands wave k+1's buffers to the device while
    wave k computes: in a profile of one query, a ``warpflow.prefetch``
    span ends before wave k's ``warpflow.sync`` span (its outputs' copy
    to the host) starts, for every non-final wave.  Prefetch runs within
    each execution partition, so the expected counts follow the
    PartitionPlan: Σ_p waves_p syncs, Σ_p max(waves_p − 1, 0) prefetches
    (a single-wave partition stages nothing ahead)."""
    be = JaxBackend()
    be.prime_fdb(dense_catalog.get("FusedAgg"))
    eng = AdHocEngine(dense_catalog, num_servers=1, backend=be, wave=3)
    eng.collect(AGG_FLOW)                      # warm
    with captured_spans(tmp_path) as got:
        eng.collect(AGG_FLOW)
    ev = [s for s in got if s.name in ("prefetch", "sync")]
    kinds = [s.name for s in ev]
    pp = exec_pplan(dense_catalog.get("FusedAgg").num_shards, be)
    part_waves = [math.ceil(s / 3) for s in pp.sizes() if s]
    assert kinds.count("sync") == sum(part_waves)
    assert kinds.count("prefetch") == sum(w - 1 for w in part_waves)
    # wave k's prefetch-of-(k+1) precedes wave k's own sync
    if part_waves and part_waves[0] > 1:
        assert kinds[0] == "prefetch" and kinds[1] == "sync"
    for i, s in enumerate(ev):
        if s.name == "prefetch":
            assert ev[i + 1].name == "sync"
            assert s.end <= ev[i + 1].start


def test_keyed_cache_reused_and_separate(dense_catalog, dense_db,
                                         monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")
    """Stacked wave buffers are cached under composite keys: reused on
    the next query (keyed_hits grows), kept OUT of the per-column buffer
    count the priming contract asserts on."""
    be = JaxBackend()
    n_buffers = be.prime_fdb(dense_db)
    assert n_buffers == len(be.device_cache) == dense_db.num_shards * 5
    eng = AdHocEngine(dense_catalog, num_servers=2, backend=be, wave=3)
    eng.collect(AGG_FLOW)
    stats = be.device_cache.stats()
    assert stats["keyed"] > 0                  # stacks were cached
    assert stats["buffers"] == len(be.device_cache) == n_buffers
    before = stats["keyed_hits"]
    eng.collect(AGG_FLOW)
    assert be.device_cache.stats()["keyed_hits"] > before


def test_keyed_cache_evicted_with_fdb(monkeypatch):
    monkeypatch.setenv(FUSED_ENV, "1")
    """Dropping the FDb drops its keyed stacks along with its buffers."""
    db = _dense_db("FusedEvict")
    cat = Catalog(); cat.register(db)
    be = JaxBackend()
    be.prime_fdb(db)
    flow = (fdb("FusedEvict").find(BETWEEN(P.hour, 8, 17))
            .aggregate(group(P.road).count("n").avg(m=P.speed)))
    AdHocEngine(cat, num_servers=2, backend=be, wave=3).collect(flow)
    assert be.device_cache.stats()["keyed"] > 0
    del cat, db, flow
    gc.collect()
    assert len(be.device_cache) == 0
    assert be.device_cache.stats()["keyed"] == 0


# ------------------------------------------- postings_bitmap behind the seam

@pytest.mark.tesseract
def test_postings_bitmap_lookup_parity(walks_db):
    """SpaceTimeIndex.lookup(backend=jax) ≡ host math, including the
    empty-window / out-of-range short circuits."""
    jxb = JaxBackend()
    jxb.prime_fdb(walks_db)
    rng = np.random.default_rng(9)
    windows = [(0.0, 86400.0), (43200.0, 3 * 86400.0),
               (5.0, 1.0),                     # inverted → empty
               (-1e12, -1e11), (1e15, 2e15)]   # outside representable
    checked = 0
    for sh in walks_db.shards:
        ix = sh.indexes[("track", "spacetime")]
        for _ in range(3):
            reg = _region(rng)
            for t0, t1 in windows:
                host = ix.lookup(reg, t0, t1)
                dev = ix.lookup(reg, t0, t1, backend=jxb)
                assert dev.dtype == np.uint32
                assert np.array_equal(host, dev), (sh.n, t0, t1)
                checked += int(host.any())
    assert checked > 0                         # some probes actually hit


# ---------------------------------------------------- fallback-path parity

@pytest.mark.parametrize("case", ["residual", "approx", "sortlimit"])
def test_fallback_paths_match_numpy(dense_catalog, case, monkeypatch):
    """Queries the fused pipeline must decline (residual filter, agg
    kinds outside count/sum/avg/std_dev/min/max, sort+limit tail) still
    match the numpy oracle with fusion enabled."""
    monkeypatch.setenv(FUSED_ENV, "1")
    assert fused_enabled()
    base = fdb("FusedAgg").find(BETWEEN(P.hour, 8, 17))
    if case == "residual":
        q = (base.filter(P.speed > 40.0)
             .aggregate(group(P.road).count("n").avg(m=P.speed)))
    elif case == "approx":
        q = base.aggregate(group(P.road).approx_distinct(d=P.hour))
    else:
        q = base.sort_desc(P.speed).limit(20)
    a = AdHocEngine(dense_catalog, num_servers=2, backend="numpy",
                    wave=3).collect(q)
    b = AdHocEngine(dense_catalog, num_servers=2, backend="jax",
                    wave=3).collect(q)
    assert_identical(a.batch, b.batch)
