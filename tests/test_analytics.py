"""Dwell/count reduction analytics, distinct_approx, to_dataset, and the
consolidated ExecConfig: edge-case semantics on handcrafted tracks (empty
tracks, tied timestamps, dwell exactly at threshold, k = 0 / k > hits),
numpy ≡ jax byte parity at word-boundary shard sizes with and without the
fused path, the launch contract (reductions ride the existing wave
dispatches), partition invariance of the HyperLogLog lowering, and the
time-to-trained-model hand-off."""
import numpy as np
import pytest

from repro.core import P, fdb, group, proto
from repro.exec import AdHocEngine, Catalog, ExecConfig, get_backend
from repro.fdb import build_fdb
from repro.fdb.schema import Field, Schema, DOUBLE, INT, STRING, MESSAGE
from repro.geo import AreaTree, mercator as M
from repro.kernels import ops
from repro.tess import Tesseract

pytestmark = pytest.mark.tesseract


# ------------------------------------------------------------ handcrafted db

PA, PB = (37.40, -122.40), (37.60, -122.20)


def _pt_region(latlng, d=100_000):
    ix, iy = M.latlng_to_xy(*latlng)
    return AreaTree.from_box(int(ix) - d, int(iy) - d,
                             int(ix) + d, int(iy) + d, max_level=7)


def _track(*pts):
    return {"lat": [p[0][0] for p in pts], "lng": [p[0][1] for p in pts],
            "t": [float(p[1]) for p in pts]}


def _track_schema(name="Visits") -> Schema:
    return Schema(name, [
        Field("id", INT, indexes=("tag",)),
        Field("track", MESSAGE, fields=[
            Field("lat", DOUBLE, repeated=True),
            Field("lng", DOUBLE, repeated=True),
            Field("t", DOUBLE, repeated=True)],
            indexes=("spacetime",),
            index_params={"level": 6, "bucket_s": 900.0, "epoch": 0.0}),
    ])


#: every reduction edge case in one fixture: id → (track, A-hits, A-span)
_CASES = [
    _track(),                                             # 0: empty track
    _track((PA, 100.0)),                                  # 1: single A hit
    _track((PA, 100.0), (PA, 200.0), (PA, 300.0)),        # 2: 3 hits, span 200
    _track((PA, 100.0), (PA, 100.0), (PA, 100.0)),        # 3: tied ts, span 0
    _track((PA, 100.0), (PA, 400.0)),                     # 4: span exactly 300
    _track((PB, 100.0)),                                  # 5: B only
    _track((PA, 100.0), (PB, 200.0)),                     # 6: A and B
]


@pytest.fixture(scope="module")
def visits_db():
    recs = [{"id": i, "track": tr} for i, tr in enumerate(_CASES)]
    sizes = [4, 0, 3]                 # incl. an empty shard
    bounds = np.cumsum([0] + sizes)
    key = lambda r: int(np.searchsorted(bounds, r["id"], "right") - 1)
    db = build_fdb("Visits", _track_schema(), recs,
                   num_shards=len(sizes), shard_key=key)
    assert [s.n for s in db.shards] == sizes
    return db


def _select(db, tess, backend, fused, wave=2, partitions=None):
    cat = Catalog(server_slots=4)
    cat.register(db)
    eng = AdHocEngine(cat, backend=backend, wave=wave,
                      partitions=partitions,
                      config=ExecConfig(fused=fused))
    res = eng.collect(fdb(db.name).tesseract(tess).map(
        lambda p: proto(id=p.id)))
    return sorted(res.batch["id"].values.tolist())


#: (tesseract builder, expected ids) — handcrafted reduction verdicts
_SCENARIOS = [
    # count ≥ 2 distinct window hits (id4 has 2, id2/3 have 3)
    (lambda A, B: Tesseract(A, 0.0, 1000.0).at_least(2), [2, 3, 4]),
    # k > hits: nothing reaches 4
    (lambda A, B: Tesseract(A, 0.0, 1000.0).at_least(4), []),
    # k = 0 alone is vacuous: every doc passes, empty track included
    (lambda A, B: Tesseract(A, 0.0, 1000.0).at_least(0),
     [0, 1, 2, 3, 4, 5, 6]),
    # k = 0 on A composed with a real B constraint: verdict is B's
    (lambda A, B: Tesseract(A, 0.0, 1000.0).at_least(0)
     .also(B, 0.0, 1000.0), [5, 6]),
    # dwell exactly at the threshold is inclusive (id4 span == 300)
    (lambda A, B: Tesseract(A, 0.0, 1000.0).dwell(300.0), [4]),
    # just past the exact span: id4 drops
    (lambda A, B: Tesseract(A, 0.0, 1000.0).dwell(300.5), []),
    # dwell 0 still requires a hit: tied timestamps (span 0) pass,
    # empty/B-only tracks don't
    (lambda A, B: Tesseract(A, 0.0, 1000.0).dwell(0.0), [1, 2, 3, 4, 6]),
    # dwell + count compose on one constraint
    (lambda A, B: Tesseract(A, 0.0, 1000.0).at_least(3).dwell(150.0), [2]),
]


@pytest.mark.parametrize("case", range(len(_SCENARIOS)))
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("fused", [True, False])
def test_reduction_semantics(visits_db, case, backend, fused):
    """Handcrafted count/dwell verdicts hold on both backends, fused and
    legacy per-primitive paths alike."""
    build, want = _SCENARIOS[case]
    got = _select(visits_db, build(_pt_region(PA), _pt_region(PB)),
                  backend, fused)
    assert got == want, (case, backend, fused)


def test_reduction_partition_invariance(visits_db):
    """P = 2 splits the shard axis; reduction verdicts are unchanged."""
    tess = Tesseract(_pt_region(PA), 0.0, 1000.0).at_least(2).also(
        _pt_region(PB), 0.0, 1000.0).dwell(0.0)
    for backend in ("numpy", "jax"):
        base = _select(visits_db, tess, backend, True, partitions=1)
        assert _select(visits_db, tess, backend, True,
                       partitions=2) == base


# ------------------------------------------- word-boundary analytics parity

RNG = np.random.default_rng(29)


def _walks(n, rng, empty_every=7):
    recs = []
    for i in range(n):
        ln = 0 if (empty_every and i % empty_every == 0) \
            else int(rng.integers(1, 14))
        lat = rng.uniform(37.2, 38.0, ln)
        lng = rng.uniform(-122.6, -121.8, ln)
        t = np.sort(rng.uniform(0.0, 3 * 86400.0, ln))
        recs.append({"id": i, "track": {"lat": lat.tolist(),
                                        "lng": lng.tolist(),
                                        "t": t.tolist()}})
    return recs


def _region(rng, d=2_000_000):
    ix, iy = M.latlng_to_xy(rng.uniform(37.2, 38.0),
                            rng.uniform(-122.6, -121.8))
    return AreaTree.from_box(int(ix) - d, int(iy) - d,
                             int(ix) + d, int(iy) + d, max_level=7)


@pytest.fixture(scope="module")
def walks_db():
    sizes = [32, 31, 64, 65, 1, 0, 33]    # 32-bit word boundaries + empty
    recs = _walks(sum(sizes), RNG)
    bounds = np.cumsum([0] + sizes)
    key = lambda r: int(np.searchsorted(bounds, r["id"], "right") - 1)
    db = build_fdb("Walks", _track_schema("Walks"), recs,
                   num_shards=len(sizes), shard_key=key)
    assert [s.n for s in db.shards] == sizes
    return db


def test_analytics_tables_batched_parity(walks_db):
    """Wave-stacked analytics (mask + first/last/count tables) byte-equal
    across backends at word-boundary shard sizes, with candidates."""
    rng = np.random.default_rng(3)
    cons = [(_region(rng), 0.0, 2 * 86400.0),
            (_region(rng), 43200.0, 3 * 86400.0)]
    batches = [s.batch for s in walks_db.shards]
    cands = [rng.random(b.n) < 0.8 for b in batches]
    outs = {}
    for bname in ("numpy", "jax"):
        be = get_backend(bname)
        be.prime_fdb(walks_db)
        outs[bname] = be.refine_tracks_batched(
            batches, "track", cons, cands, min_counts=(2, 1),
            dwells=(None, 600.0), with_analytics=True)
    for part in range(4):                 # masks, firsts, lasts, counts
        for a, b in zip(outs["numpy"][part], outs["jax"][part]):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b), part
    masks = outs["numpy"][0]
    assert any(m.any() for m in masks)    # non-vacuous evidence


def test_reduction_launch_contract(walks_db, exec_pplan):
    """Count/dwell reductions ride the existing fused wave dispatches —
    zero extra launches versus a plain trip query."""
    cat = Catalog(server_slots=4)
    cat.register(walks_db)
    rng = np.random.default_rng(7)
    tess = Tesseract(_region(rng), 0.0, 2 * 86400.0).at_least(2).also(
        _region(rng), 43200.0, 3 * 86400.0).dwell(600.0)
    flow = fdb("Walks").tesseract(tess).map(lambda p: proto(id=p.id))
    wave = 3
    eng = AdHocEngine(cat, backend="jax", wave=wave,
                      config=ExecConfig(fused=True))
    eng.collect(flow)                     # warm (jit compile)
    ops.reset_launch_counts()
    eng.collect(flow)
    lc = ops.launch_counts()
    waves = exec_pplan(walks_db.num_shards,
                       eng.backend).wave_dispatches(wave)
    assert lc.get("run_wave_fused") == waves
    assert lc.get("refine_tracks_batched", 0) == 0
    assert lc.get("refine_tracks", 0) == 0


# ------------------------------------------------- Tesseract label plumbing

def test_labels_and_before():
    A, B = _pt_region(PA), _pt_region(PB)
    by_label = (Tesseract(A, 0.0, 1000.0, label="home")
                .also(B, 0.0, 1000.0, label="work").before("home", "work"))
    by_index = (Tesseract(A, 0.0, 1000.0)
                .also(B, 0.0, 1000.0).before(0, 1))
    assert by_label.order_edges == by_index.order_edges == ((0, 1),)
    # selectors also resolve for reductions, by label or index
    t = (Tesseract(A, 0.0, 1000.0, label="home")
         .also(B, 0.0, 1000.0, label="work")
         .at_least(2, "home").dwell(60.0, 1))
    assert t.min_counts == (2, 1)
    assert t.dwells == (None, 60.0)
    with pytest.raises(ValueError):
        Tesseract(A, 0.0, 1000.0, label="home").before("home", "gym")


# --------------------------------------------------- distinct_approx (HLL)

@pytest.fixture(scope="module")
def events_db():
    schema = Schema("Events", [
        Field("id", INT, indexes=("tag",)),
        Field("day", INT, indexes=("tag",)),
        Field("city", STRING, indexes=("tag",)),
    ])
    rng = np.random.default_rng(41)
    cities = ["SF", "Berkeley", "Oakland", "Fremont", "LA"]
    recs = [{"id": int(i), "day": int(rng.integers(0, 3)),
             "city": cities[int(rng.integers(0, len(cities)))]}
            for i in range(600)]
    return recs, build_fdb("Events", schema, recs, num_shards=7)


def test_distinct_approx_matches_hll_oracle(events_db):
    """Grouped approx_distinct through the segment-max lowering equals a
    per-group HyperLogLog built directly from the raw values."""
    from repro.core.sketches import HyperLogLog
    recs, db = events_db
    cat = Catalog(server_slots=4)
    cat.register(db)
    res = AdHocEngine(cat, backend="numpy").collect(
        fdb("Events").aggregate(group(P.day).approx_distinct(
            "n_cities", expr=P.city)))
    got = {int(d): float(v) for d, v in zip(res.batch["day"].values,
                                            res.batch["n_cities"].values)}
    for day in sorted(got):
        strs = [r["city"] for r in recs if r["day"] == day]
        want = HyperLogLog().add(np.arange(len(strs)),
                                 vocab=strs).estimate()
        assert got[day] == pytest.approx(want, abs=1e-9)


def test_distinct_approx_partition_and_backend_invariant(events_db):
    """Flow.distinct_approx: identical estimate at P = 1/2/4 on both
    backends (register max is commutative + idempotent)."""
    _, db = events_db
    cat = Catalog(server_slots=4)
    cat.register(db)
    flow = fdb("Events").distinct_approx(P.id, name="n_ids")
    ests = set()
    for backend in ("numpy", "jax"):
        for parts in (1, 2, 4):
            eng = AdHocEngine(cat, backend=backend, wave=3,
                              partitions=parts)
            res = eng.collect(flow)
            assert res.batch.n == 1
            ests.add(float(res.batch["n_ids"].values[0]))
    assert len(ests) == 1
    est = ests.pop()
    assert abs(est - 600) / 600 < 0.1


# --------------------------------------------- to_dataset → trained model

def test_to_dataset_trains_end_to_end():
    schema = Schema("Obs", [
        Field("id", INT, indexes=("tag",)),
        Field("x", DOUBLE),
        Field("y", DOUBLE),
        Field("split", INT, indexes=("tag",)),
    ])
    rng = np.random.default_rng(5)
    x = rng.uniform(-2.0, 2.0, 400)
    y = 3.0 * x + 1.0 + rng.normal(0.0, 0.05, x.size)
    recs = [{"id": int(i), "x": float(a), "y": float(b),
             "split": int(i % 4 != 0)}
            for i, (a, b) in enumerate(zip(x, y))]
    cat = Catalog(server_slots=4)
    cat.register(build_fdb("Obs", schema, recs, num_shards=5))
    eng = AdHocEngine(cat, backend="numpy")

    ds = (fdb("Obs").find(P.split == 1)
          .to_dataset(features={"x": P.x}, target=P.y, engine=eng))
    assert len(ds) == sum(1 for r in recs if r["split"] == 1)
    assert ds.feature_names == ["x"] and ds.num_features == 1

    tr, te = ds.split(frac=0.8, seed=0)
    assert len(tr) + len(te) == len(ds) and len(te) > 0
    fb, tb = next(iter(tr.batches(32)))
    assert fb.shape == (32, 1) and tb.shape == (32,)

    model, losses = ds.fit(hidden=16, depth=1, steps=200, lr=5e-2,
                           batch=128)
    assert losses[-1] < losses[0] * 0.5        # actually learned
    pred = model.as_column_model(["x"]).apply_columns(
        {"x": np.array([0.0, 1.0])})
    assert pred[0] == pytest.approx(1.0, abs=0.5)
    assert pred[1] == pytest.approx(4.0, abs=0.5)

    # sequence-of-fields form infers names from the field refs
    ds2 = fdb("Obs").to_dataset(features=[P.x], target=P.y, engine=eng)
    assert ds2.feature_names == ["x"] and len(ds2) == len(recs)


# ------------------------------------------------------------- ExecConfig

def test_exec_config_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_EXEC_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_EXEC_WAVE", raising=False)
    monkeypatch.delenv("REPRO_EXEC_FUSED", raising=False)
    # defaults
    cfg = ExecConfig()
    assert type(cfg.resolve_backend()).__name__ == "NumpyBackend"
    assert cfg.resolved_fused() is True
    # env fallback when the field is unset
    monkeypatch.setenv("REPRO_EXEC_FUSED", "0")
    monkeypatch.setenv("REPRO_EXEC_WAVE", "5")
    assert ExecConfig().resolved_fused() is False
    assert ExecConfig().resolve_wave() == 5
    # explicit field beats the env
    assert ExecConfig(fused=True).resolved_fused() is True
    assert ExecConfig(wave=2).resolve_wave() == 2
    # legacy kwargs fill only unset fields
    filled = ExecConfig(wave=4).fill(wave=9, backend="jax")
    assert filled.wave == 4 and filled.backend == "jax"


def test_exec_config_engine_shims(events_db, monkeypatch):
    """Engines accept config=, legacy kwargs keep working, and an
    explicit fused=True overrides REPRO_EXEC_FUSED=0."""
    _, db = events_db
    cat = Catalog(server_slots=4)
    cat.register(db)
    flow = fdb("Events").find(P.day == 1).map(lambda p: proto(id=p.id))
    want = sorted(AdHocEngine(cat, backend="numpy").collect(
        flow).batch["id"].values.tolist())

    eng = AdHocEngine(cat, config=ExecConfig(backend="jax", wave=2,
                                             partitions=2))
    assert eng.wave == 2 and eng.partitions == 2
    assert sorted(eng.collect(flow).batch["id"].values.tolist()) == want

    monkeypatch.setenv("REPRO_EXEC_FUSED", "0")
    eng2 = AdHocEngine(cat, config=ExecConfig(backend="jax", fused=True))
    eng2.collect(flow)                    # warm
    ops.reset_launch_counts()
    eng2.collect(flow)
    assert ops.launch_counts().get("run_wave_fused", 0) > 0

    # legacy kwarg form still resolves identically
    eng3 = AdHocEngine(cat, backend="jax", wave=2)
    assert eng3.wave == 2
    assert sorted(eng3.collect(flow).batch["id"].values.tolist()) == want
