"""Batched multi-shard execution: stacked-wave kernels vs the per-shard
oracle (byte parity on ragged shard sizes incl. empty shards), the
⌈shards/wave⌉ kernel-launch contract, and device-resident columns."""
import math

import numpy as np
import pytest

from repro.core import BETWEEN, P, group, fdb, proto
from repro.exec import (AdHocEngine, FlumeEngine, Catalog, JaxBackend,
                        get_backend, partition_waves, run_wave_task,
                        wave_size)
from repro.exec.processors import aggregate_produce, aggregate_produce_batched
from repro.exec.task import run_shard_task
from repro.core.planner import plan_flow
from repro.fdb import Schema, build_fdb, DOUBLE, INT, STRING
from repro.fdb.schema import Field
from repro.fdb.index import bitmap_from_ids, bitmap_full
from repro.kernels import ops

RNG = np.random.default_rng(11)


# --------------------------------------------------------------- fixtures

def _ragged_db(num_shards=7, empty_shard=5, rows=900):
    """Skewed shard sizes (≈5:2:1…) with one completely empty shard."""
    schema = Schema("Ragged", [
        Field("road", INT, indexes=("tag",)),
        Field("hour", INT, indexes=("range",)),
        Field("city", STRING, indexes=("tag",)),
        Field("speed", DOUBLE),
    ])
    choices = [s for s in range(num_shards) if s != empty_shard]
    weights = np.linspace(5, 1, len(choices))
    weights /= weights.sum()
    recs = [{"road": int(RNG.integers(0, 40)),
             "hour": int(RNG.integers(0, 24)),
             "city": ["SF", "OAK", "SJ"][int(RNG.integers(0, 3))],
             "speed": float(RNG.normal(48, 9)),
             "_sh": int(RNG.choice(choices, p=weights))}
            for _ in range(rows)]
    db = build_fdb("Ragged", schema, recs, num_shards=num_shards,
                   shard_key=lambda r: r["_sh"])
    sizes = [s.n for s in db.shards]
    assert sizes[empty_shard] == 0 and len(set(sizes)) > 2
    return db


@pytest.fixture(scope="module")
def ragged_catalog():
    cat = Catalog(server_slots=16)
    cat.register(_ragged_db())
    return cat


def assert_identical(a, b):
    assert a.n == b.n
    assert a.paths() == b.paths()
    for p in a.paths():
        ca, cb = a[p], b[p]
        assert ca.values.dtype == cb.values.dtype, p
        assert np.array_equal(ca.values, cb.values), p
        assert ca.vocab == cb.vocab, p


# ------------------------------------------------- backend primitive parity

@pytest.mark.parametrize("bname", ["numpy", "jax"])
def test_probe_shards_matches_per_shard(bname):
    be = get_backend(bname)
    oracle = get_backend("numpy")
    sizes = [0, 1, 31, 700, 64, 4097]
    fulls = [bitmap_full(n) for n in sizes]
    probes = [[bitmap_from_ids(
        RNG.choice(n, size=max(1, n // 2), replace=False), n)
        for _ in range(k)] if n else []
        for k, n in zip([2, 0, 1, 3, 2, 1], sizes)]
    got = be.probe_shards(fulls, probes)
    for bm, f, ps, n in zip(got, fulls, probes, sizes):
        want = oracle.intersect_bitmaps(f, ps)
        assert bm.dtype == np.uint32
        assert np.array_equal(bm, want), n


@pytest.mark.parametrize("bname", ["numpy", "jax"])
def test_compact_masks_ragged_parity(bname):
    be = get_backend(bname)
    oracle = get_backend("numpy")
    masks = [RNG.random(n) < d
             for n, d in [(0, 0.0), (1, 1.0), (317, 0.4), (5000, 0.01),
                          (64, 0.0)]]
    got = be.compact_masks(masks)
    for ids, m in zip(got, masks):
        want = oracle.compact_mask(m)
        assert ids.dtype == np.int64
        assert np.array_equal(ids, want)


@pytest.mark.parametrize("bname", ["numpy", "jax"])
def test_segment_aggregate_batched_parity(bname):
    be = get_backend(bname)
    oracle = get_backend("numpy")
    shards = [(0, 1), (1000, 7), (1, 1), (333, 12)]
    codes = [RNG.integers(-1, g, n) for n, g in shards]
    vals = [RNG.normal(50.0, 9.0, n) for n, _ in shards]
    groups = [g for _, g in shards]
    got = be.segment_aggregate_batched(codes, vals, groups)
    for (cg, sg, s2g), c, v, g in zip(got, codes, vals, groups):
        cn, sn, s2n = oracle.segment_aggregate(c, v, g)
        assert np.array_equal(cg, cn)
        assert np.array_equal(sg, sn)          # bit-equal f64 accumulation
        assert np.array_equal(s2g, s2n)


def test_aggregate_produce_batched_matches_per_shard(ragged_catalog):
    db = ragged_catalog.get("Ragged")
    flow = fdb("Ragged").aggregate(
        group(P.road).count("n").avg(m=P.speed).std_dev(s=P.speed))
    plan = plan_flow(flow, ragged_catalog)
    spec = plan.mixer_ops[0].spec
    batches = [s.batch for s in db.shards]
    for bname in ("numpy", "jax"):
        be = get_backend(bname)
        batched = aggregate_produce_batched(batches, spec, be)
        single = [aggregate_produce(b, spec, be) for b in batches]
        for pb, ps in zip(batched, single):
            assert pb.groups == ps.groups


# -------------------------------------------------- wave runner vs per-shard

QUERIES = [
    fdb("Ragged").find(BETWEEN(P.hour, 8, 17))
        .aggregate(group(P.road).count("n").avg(m=P.speed)
                   .std_dev(s=P.speed)),
    fdb("Ragged").find(BETWEEN(P.hour, 6, 20) & (P.speed > 40.0))
        .sort_desc(P.speed).limit(25),
    fdb("Ragged").find(P.city == "SF")
        .map(lambda p: proto(road=p.road, fast=p.speed > 50.0)),
    fdb("Ragged").aggregate(group(P.city).min(lo=P.speed).max(hi=P.speed)
                            .sum(tot=P.speed)),
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_wave_task_matches_shard_tasks(ragged_catalog, qi):
    db = ragged_catalog.get("Ragged")
    plan = plan_flow(QUERIES[qi], ragged_catalog)
    for bname in ("numpy", "jax"):
        be = get_backend(bname)
        be.prime_fdb(db)
        parts, failed = run_wave_task(db, plan, plan.shard_ids, None,
                                      ragged_catalog, backend=be)
        assert failed == []
        singles = [run_shard_task(db, plan, sid, None, ragged_catalog,
                                  backend=be) for sid in plan.shard_ids]
        for pw, psh in zip(parts, singles):
            assert pw.shard_id == psh.shard_id
            assert pw.rows_scanned == psh.rows_scanned
            assert pw.rows_selected == psh.rows_selected
            if psh.agg is not None:
                assert pw.agg.groups == psh.agg.groups
            else:
                assert_identical(pw.batch, psh.batch)


@pytest.mark.parametrize("qi", range(len(QUERIES)))
@pytest.mark.parametrize("wave", [1, 3, 16])
def test_engine_parity_on_ragged_shards(ragged_catalog, qi, wave):
    rn = AdHocEngine(ragged_catalog, num_servers=4, backend="numpy",
                     wave=wave).collect(QUERIES[qi])
    rj = AdHocEngine(ragged_catalog, num_servers=4, backend="jax",
                     wave=wave).collect(QUERIES[qi])
    assert_identical(rn.batch, rj.batch)
    assert rn.profile.rows_scanned == rj.profile.rows_scanned
    assert rn.profile.rows_selected == rj.profile.rows_selected


def _crash_first_wave(monkeypatch, exc):
    import repro.exec.flume as flume_mod
    real = flume_mod.run_wave_task

    def flaky(db, plan, sids, *a, **kw):
        if 0 in list(sids):
            raise exc
        return real(db, plan, sids, *a, **kw)

    monkeypatch.setattr(flume_mod, "run_wave_task", flaky)


def test_flume_wave_error_does_not_abort_siblings(ragged_catalog, tmp_path,
                                                  monkeypatch):
    """A wave lost to a machine failure must not discard completed waves'
    checkpoints; its shards fall through to the per-shard machinery."""
    from repro.exec.failures import TaskFailure
    _crash_first_wave(monkeypatch, TaskFailure("injected wave crash"))
    q = QUERIES[0]
    fl = FlumeEngine(ragged_catalog, ckpt_dir=str(tmp_path), max_workers=4,
                     backend="numpy", wave=3)
    res = fl.collect(q)
    ref = AdHocEngine(ragged_catalog, num_servers=4,
                      backend="numpy").collect(q)
    assert_identical(ref.batch, res.batch)
    # 4 shards via surviving waves + 3 via the per-shard fallback
    assert fl.stats["tasks_run"] == 7


def test_flume_wave_bug_raises_after_siblings_commit(ragged_catalog,
                                                     tmp_path, monkeypatch):
    """Any other wave error is a bug, not a lost machine: it surfaces
    (never a silent per-shard re-run), after the sibling waves commit
    their checkpoints, so a rerun recovers them."""
    _crash_first_wave(monkeypatch, ValueError("injected wave bug"))
    fl = FlumeEngine(ragged_catalog, ckpt_dir=str(tmp_path), max_workers=4,
                     backend="numpy", wave=3)
    with pytest.raises(ValueError, match="injected wave bug"):
        fl.collect(QUERIES[0])
    assert fl.stats["tasks_run"] == 4


def test_flume_wave_path_parity(ragged_catalog, tmp_path):
    q = QUERIES[0]
    ref = AdHocEngine(ragged_catalog, num_servers=4,
                      backend="numpy").collect(q)
    fl = FlumeEngine(ragged_catalog, ckpt_dir=str(tmp_path), max_workers=4,
                     backend="jax", wave=3)
    res = fl.collect(q)
    assert_identical(ref.batch, res.batch)
    assert fl.stats["tasks_run"] == 7          # one checkpoint per shard
    again = fl.collect(q)                      # recovery from wave ckpts
    assert_identical(ref.batch, again.batch)
    assert fl.stats["tasks_skipped"] >= 7


# ------------------------------------------------- launch-count contract

def test_launch_count_is_ceil_shards_over_wave(ragged_catalog, exec_pplan,
                                               monkeypatch):
    """Per query the jax path dispatches ⌈shards_p/wave⌉ stacked launches
    per primitive per partition — not one per shard.  Pinned to the legacy
    per-primitive path; the fused single-dispatch contract is in
    tests/test_fused.py (the legacy path carries no raw segment states, so
    no merge combine fires at any P)."""
    monkeypatch.setenv("REPRO_EXEC_FUSED", "0")
    db = ragged_catalog.get("Ragged")
    n_shards = db.num_shards
    wave = 3
    eng = AdHocEngine(ragged_catalog, num_servers=2, backend="jax",
                      wave=wave)
    q = (fdb("Ragged").find(BETWEEN(P.hour, 8, 17))
         .aggregate(group(P.road).count("n").avg(m=P.speed)))
    eng.collect(q)                             # warm: prime + plan caches
    ops.reset_launch_counts()
    eng.collect(q)
    lc = ops.launch_counts()
    waves = exec_pplan(n_shards, eng.backend).wave_dispatches(wave)
    assert lc.get("bitmap_intersect_batched") == waves
    assert lc.get("compact_batched") == waves            # selection compact
    assert lc.get("segment_agg") == waves                # one value column
    # nothing fell back to per-shard dispatch
    assert lc.get("bitmap_intersect", 0) == 0
    assert lc.get("compact", 0) == 0
    # and the whole query is O(waves), not O(shards)
    assert sum(lc.values()) == 3 * waves < 3 * n_shards


def test_wave_size_resolution(ragged_catalog, monkeypatch):
    monkeypatch.delenv("REPRO_EXEC_WAVE", raising=False)
    assert wave_size() == 8
    assert wave_size(3) == 3
    monkeypatch.setenv("REPRO_EXEC_WAVE", "5")
    assert wave_size() == 5
    assert wave_size(2) == 2                   # explicit arg wins over env
    assert partition_waves(range(7), 3) == [[0, 1, 2], [3, 4, 5], [6]]
    # backend default: wide waves only when batched ops amortize launches;
    # the loop-over-shards numpy backend keeps per-shard parallelism
    monkeypatch.delenv("REPRO_EXEC_WAVE")
    assert AdHocEngine(ragged_catalog, backend="jax").wave == 8
    assert AdHocEngine(ragged_catalog, backend="numpy").wave == 1
    assert AdHocEngine(ragged_catalog, backend="numpy", wave=4).wave == 4


# ------------------------------------------------- device-resident columns

def test_device_cache_primed_once_and_hit(ragged_catalog, monkeypatch):
    # legacy path: the fused agg pipeline reads its own stacked buffers
    # and never issues the per-column gathers this test counts as hits
    monkeypatch.setenv("REPRO_EXEC_FUSED", "0")
    db = ragged_catalog.get("Ragged")
    be = JaxBackend()
    n_buffers = be.prime_fdb(db)
    # every shard: 4 dense columns + valid-doc bitmap (empty shard incl.)
    assert n_buffers == len(be.device_cache) == db.num_shards * 5
    assert be.prime_fdb(db) == 0               # idempotent per FDb open
    before = be.device_cache.hits
    eng = AdHocEngine(ragged_catalog, num_servers=2, backend=be)
    res = eng.collect(fdb("Ragged").find(BETWEEN(P.hour, 8, 17))
                      .aggregate(group(P.road).count("n")))
    assert res.batch.n > 0
    assert be.device_cache.hits > before       # gathers hit resident bufs
    stats = be.device_cache.stats()
    assert stats["buffers"] == n_buffers and stats["nbytes"] > 0


def test_device_cache_evicts_collected_fdb():
    db = _ragged_db(num_shards=3, empty_shard=2, rows=60)
    be = JaxBackend()
    assert be.prime_fdb(db) == len(be.device_cache) > 0
    del db                                     # finalizer drops buffers
    assert len(be.device_cache) == 0


def test_device_cache_refcounts_shared_shards():
    """StreamingFDb snapshots share flushed Shards: buffers must survive
    until the *last* FDb referencing them is collected, and stay usable."""
    from repro.fdb.fdb import FDb
    db1 = _ragged_db(num_shards=3, empty_shard=2, rows=60)
    db2 = FDb("RaggedView", db1.schema, db1.shards)     # shares Shards
    be = JaxBackend()
    n = be.prime_fdb(db1)
    assert n == len(be.device_cache) > 0
    assert be.prime_fdb(db2) == 0              # same buffers, new refs
    shard = db1.shards[0]
    del db1                                    # db2 still references all
    assert len(be.device_cache) == n
    assert be.device_cache.get(shard.batch["speed"].values) is not None
    del db2
    assert len(be.device_cache) == 0


def test_device_gather_parity_with_host(ragged_catalog):
    db = ragged_catalog.get("Ragged")
    be = JaxBackend()
    be.prime_fdb(db)
    shard = db.shards[0]
    ids = np.sort(RNG.choice(shard.n, size=shard.n // 2, replace=False))
    paths = shard.batch.paths()
    dev = be.gather_columns(shard.batch, paths, ids)
    host = shard.batch.select_paths(paths).gather(ids)
    assert_identical(dev, host)
