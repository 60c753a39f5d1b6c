"""Pallas kernels: interpret-mode vs pure-jnp oracle, shape/dtype sweeps."""
import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # optional dep: fall back to shim
    from _hypothesis_shim import given, settings, st

from repro.kernels import ops

RNG = np.random.default_rng(0)


# ------------------------------------------------------------- bitset

@pytest.mark.parametrize("w", [1, 31, 32, 100, 4096, 4097, 20_000])
@pytest.mark.parametrize("op", ["and", "or", "andnot"])
def test_bitset_binary(w, op):
    a = jnp.asarray(RNG.integers(0, 2**32, w, dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, w, dtype=np.uint32))
    got = ops.bitmap_binary(a, b, op, impl="interpret")
    want = ops.bitmap_binary(a, b, op, impl="reference")
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("k,w", [(1, 64), (3, 1000), (5, 8192)])
def test_bitmap_intersect(k, w):
    stack = jnp.asarray(RNG.integers(0, 2**32, (k, w), dtype=np.uint32))
    bm, cnt = ops.bitmap_intersect(stack, impl="interpret")
    bm_r, cnt_r = ops.bitmap_intersect(stack, impl="reference")
    assert (np.asarray(bm) == np.asarray(bm_r)).all()
    assert int(cnt) == int(cnt_r)


@pytest.mark.parametrize("s,k,w", [(1, 1, 64), (3, 2, 1000), (6, 4, 4097),
                                   (2, 1, 513)])
def test_bitmap_intersect_batched(s, k, w):
    """Wave-stacked AND: interpret ≡ reference ≡ per-shard intersect."""
    stack = jnp.asarray(RNG.integers(0, 2**32, (s, k, w), dtype=np.uint32))
    bm_i, cnt_i = ops.bitmap_intersect_batched(stack, impl="interpret")
    bm_r, cnt_r = ops.bitmap_intersect_batched(stack, impl="reference")
    assert (np.asarray(bm_i) == np.asarray(bm_r)).all()
    assert (np.asarray(cnt_i) == np.asarray(cnt_r)).all()
    for i in range(s):
        bm1, cnt1 = ops.bitmap_intersect(stack[i], impl="reference")
        assert (np.asarray(bm1) == np.asarray(bm_r)[i]).all()
        assert int(cnt1) == int(np.asarray(cnt_r)[i])


# ------------------------------------------------------------ compact

@pytest.mark.parametrize("n", [8, 100, 4096, 9_999])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_compact(n, density):
    m = jnp.asarray(RNG.random(n) < density)
    gi, gc = ops.compact(m, impl="interpret")
    ri, rc = ops.compact(m, impl="reference")
    assert int(gc) == int(rc) == int(np.asarray(m).sum())
    k = int(gc)
    assert (np.asarray(gi)[:k] == np.asarray(ri)[:k]).all()
    assert (np.asarray(gi)[k:] == -1).all()


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_compact_empty_mask(impl):
    # zero-size masks happen per shard whenever an index probe admits no
    # candidates (common for selective Tesseract queries)
    idx, cnt = ops.compact(jnp.zeros((0,), jnp.bool_), impl=impl)
    assert int(cnt) == 0
    assert np.asarray(idx).shape == (0,)


@given(st.integers(1, 2000), st.integers(0, 2**31))
@settings(max_examples=30, deadline=None)
def test_compact_property(n, seed):
    rng = np.random.default_rng(seed)
    m = rng.random(n) < rng.random()
    idx, cnt = ops.compact(jnp.asarray(m), impl="interpret")
    idx = np.asarray(idx)
    # indices are exactly the set positions, ascending
    assert (idx[:int(cnt)] == np.nonzero(m)[0]).all()


@pytest.mark.parametrize("s,n", [(1, 8), (4, 317), (3, 9000), (2, 4096)])
@pytest.mark.parametrize("density", [0.0, 0.35, 1.0])
def test_compact_batched(s, n, density):
    """Wave-stacked compaction: the carry resets per shard, so each row
    compacts exactly like an independent single-shard launch."""
    m = jnp.asarray(RNG.random((s, n)) < density)
    gi, gc = ops.compact_batched(m, impl="interpret")
    ri, rc = ops.compact_batched(m, impl="reference")
    assert (np.asarray(gi) == np.asarray(ri)).all()
    assert (np.asarray(gc) == np.asarray(rc)).all()
    for i in range(s):
        want = np.nonzero(np.asarray(m)[i])[0]
        cnt = int(np.asarray(gc)[i])
        assert cnt == want.size
        assert (np.asarray(gi)[i][:cnt] == want).all()
        assert (np.asarray(gi)[i][cnt:] == -1).all()


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_compact_batched_empty(impl):
    idx, cnt = ops.compact_batched(jnp.zeros((3, 0), jnp.bool_), impl=impl)
    assert np.asarray(idx).shape == (3, 0)
    assert (np.asarray(cnt) == 0).all()


# --------------------------------------------------------- segment_agg

@pytest.mark.parametrize("n,g", [(64, 3), (1000, 130), (5000, 257)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_segment_agg(n, g, dtype):
    gid = jnp.asarray(RNG.integers(-1, g, n, dtype=np.int32))
    v = jnp.asarray(RNG.normal(size=n).astype(dtype))
    got = ops.segment_agg(gid, v, g, impl="interpret")
    want = ops.segment_agg(gid, v, g, impl="reference")
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_segment_agg_vs_host_groupby(world):
    speeds = np.array([o["speed"] for o in world["obs"]], np.float32)
    roads = np.array([o["road_id"] for o in world["obs"]], np.int32)
    cnt, s, s2 = ops.segment_agg(jnp.asarray(roads), jnp.asarray(speeds),
                                 300, impl="interpret")
    for rid in (0, 7, 123):
        sel = speeds[roads == rid]
        assert int(np.asarray(cnt)[rid]) == sel.size
        np.testing.assert_allclose(np.asarray(s)[rid], sel.sum(),
                                   rtol=1e-4)


# -------------------------------------------------------- track refine

def _track_lens(rng, n_docs, lens, empty_every=0):
    """Per-doc track lengths: uniform below ``lens`` when it is a number,
    else a named layout the banded refine schedule must walk right —
    ``long``: one track over ≥ 3 point blocks; ``empty_run``: a whole doc
    block of empty tracks; ``empty``: no points at all."""
    out = rng.integers(0, lens if isinstance(lens, int) else 12, n_docs)
    if empty_every:
        out[::empty_every] = 0                   # force empty tracks
    if lens == "long":
        out[n_docs // 2 + 1] = 1_400
    elif lens == "empty_run":
        out[120:270] = 0
    elif lens == "empty":
        out[:] = 0
    return out


def _constraints(rng, n_constraints):
    from repro.geo import mercator as M
    from repro.geo.areatree import AreaTree
    cons = []
    for _ in range(n_constraints):
        ix, iy = M.latlng_to_xy(rng.uniform(37.6, 37.9),
                                rng.uniform(-122.6, -122.2))
        d = int(rng.integers(3_000, 2_000_000))
        cons.append((AreaTree.from_box(int(ix) - d, int(iy) - d,
                                       int(ix) + d, int(iy) + d,
                                       max_level=7),
                     float(rng.uniform(0, 5e4)),
                     float(rng.uniform(5e4, 1e5))))
    return cons


def _refine_case(rng, n_docs, lens, n_constraints, *, empty_every=0):
    """Random ragged tracks (:func:`_track_lens`) + constraints in packed
    kernel form."""
    from repro.exec.refine import pack_constraints, pack_track_points
    splits = np.zeros(n_docs + 1, np.int64)
    np.cumsum(_track_lens(rng, n_docs, lens, empty_every), out=splits[1:])
    p = int(splits[-1])
    lat = rng.uniform(37.6, 37.9, p)
    lng = rng.uniform(-122.6, -122.2, p)
    t = rng.uniform(0.0, 1e5, p)
    cons = _constraints(rng, n_constraints)
    pts, rows = pack_track_points(lat, lng, t, splits)
    return ((lat, lng, t, splits), cons,
            jnp.asarray(pts), jnp.asarray(rows),
            jnp.asarray(pack_constraints(cons)))


def _wave(cases):
    """Shard cases → wave stacks (pts [S, 4, P], rows [S, P]) zero / −1
    padded to the longest shard, as the backend stacks them."""
    p_max = max(c[2].shape[1] for c in cases)
    pts = np.zeros((len(cases), 4, p_max), np.uint32)
    rows = np.full((len(cases), p_max), -1, np.int32)
    for i, case in enumerate(cases):
        p = case[2].shape[1]
        pts[i, :, :p] = np.asarray(case[2])
        rows[i, :p] = np.asarray(case[3])
    return jnp.asarray(pts), jnp.asarray(rows)


def _refine_brute(track, cons, n_docs):
    from repro.geo import mercator as M
    lat, lng, t, splits = track
    keys = M.latlng_to_morton(lat, lng)
    out = np.ones(n_docs, dtype=bool)
    row_of = np.repeat(np.arange(n_docs), np.diff(splits))
    for region, t0, t1 in cons:
        hit = region.contains(keys) & (t >= t0) & (t <= t1)
        doc = np.zeros(n_docs, dtype=bool)
        np.logical_or.at(doc, row_of, hit)
        out &= doc
    return out


@pytest.mark.parametrize("n_docs,lens,c", [(1, 5, 1), (31, 10, 2),
                                           (128, 8, 1), (300, 12, 3),
                                           (300, "long", 2), (640, 40, 2),
                                           (400, "empty_run", 1)])
def test_refine_tracks(n_docs, lens, c):
    """Interpret ≡ reference ≡ brute-force numpy on ragged tracks (empty
    tracks included, doc counts off word boundaries; a track over three
    point blocks, five doc blocks of several point blocks each, an empty
    doc block)."""
    rng = np.random.default_rng(n_docs * 7 + c)
    track, cons, pts, rows, cov = _refine_case(rng, n_docs, lens, c,
                                               empty_every=5)
    want = _refine_brute(track, cons, n_docs)
    got_i = np.asarray(ops.refine_tracks(pts, rows, cov, n_docs,
                                         impl="interpret"))
    got_r = np.asarray(ops.refine_tracks(pts, rows, cov, n_docs,
                                         impl="reference"))
    assert np.array_equal(got_i, want)
    assert np.array_equal(got_r, want)


_WAVES = {"ragged": [(0, 10), (1, 10), (64, 10), (33, 10)],
          # shards differ in P and doc count, one of them with no points
          "banded": [(300, "long"), (130, "empty"), (640, 40),
                     (400, "empty_run")]}


@pytest.mark.parametrize("impl,wave", [
    pytest.param(impl, wave, id=impl if wave == "ragged"
                 else f"{impl}-{wave}")
    for wave in _WAVES for impl in ("interpret", "reference")])
def test_refine_tracks_batched(impl, wave):
    """Wave-stacked refine: ragged shard sizes (incl. an all-empty-track
    shard) padded into one launch ≡ per-shard refine."""
    rng = np.random.default_rng(3)
    shards = _WAVES[wave]
    cases = [_refine_case(rng, n, lens, 2, empty_every=3)
             for n, lens in shards]
    cov = cases[-1][4]           # same constraints for every shard
    cons = cases[-1][1]
    n_max = max(n for n, _ in shards)
    pts, rows = _wave(cases)
    got = np.asarray(ops.refine_tracks_batched(pts, rows, cov, n_max,
                                               impl=impl))
    assert got.shape == (len(cases), n_max)
    for i, (case, (n, _)) in enumerate(zip(cases, shards)):
        want = _refine_brute(case[0], cons, n)
        assert np.array_equal(got[i, :n], want), i
        assert not got[i, n:].any()              # padding never hits
    assert got.any()                             # non-vacuous evidence


@pytest.mark.parametrize("n_docs,lens,c", [(1, 5, 1), (31, 10, 2),
                                           (300, 12, 3), (300, "long", 2),
                                           (640, 40, 3),
                                           (400, "empty_run", 2)])
def test_refine_tracks_first_hits(n_docs, lens, c):
    """The first-hit (hi, lo) word tables: interpret ≡ reference ≡ the
    numpy host oracle's packed uint64 min, sentinel where a constraint
    never hits — and the mask output is unchanged by requesting them."""
    from repro.exec.refine import refine_tracks_host
    rng = np.random.default_rng(n_docs * 13 + c)
    track, cons, pts, rows, cov = _refine_case(rng, n_docs, lens, c,
                                               empty_every=4)
    lat, lng, t, splits = track
    _, want_table = refine_tracks_host(lat, lng, t, splits, n_docs, cons,
                                       with_first_hits=True)
    plain = np.asarray(ops.refine_tracks(pts, rows, cov, n_docs,
                                         impl="reference"))
    for impl in ("interpret", "reference"):
        m, hi, lo = ops.refine_tracks(pts, rows, cov, n_docs, impl=impl,
                                      with_first_hits=True)
        m, hi, lo = np.asarray(m), np.asarray(hi), np.asarray(lo)
        got = ((hi.astype(np.uint64) << np.uint64(32))
               | lo.astype(np.uint64)).T
        assert np.array_equal(m, plain), impl
        assert np.array_equal(got, want_table), impl
        # batched single-shard path agrees word for word
        mb, hib, lob = ops.refine_tracks_batched(
            pts[None], rows[None], cov, n_docs, impl=impl,
            with_first_hits=True)
        assert np.array_equal(np.asarray(mb)[0], m), impl
        assert np.array_equal(np.asarray(hib)[0], hi), impl
        assert np.array_equal(np.asarray(lob)[0], lo), impl


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_refine_tracks_first_hits_empty_inputs(impl):
    """Zero docs / zero points / empty shards return all-sentinel tables
    of the right shape."""
    from repro.exec.refine import FIRST_HIT_NONE, pack_constraints
    from repro.geo.areatree import AreaTree
    cov = jnp.asarray(pack_constraints([(AreaTree.empty(), 0.0, 1.0),
                                        (AreaTree.everything(), 0.0, 1.0)]))
    pts0 = jnp.zeros((4, 0), jnp.uint32)
    rows0 = jnp.zeros((0,), jnp.int32)
    m, hi, lo = ops.refine_tracks(pts0, rows0, cov, 5, impl=impl,
                                  with_first_hits=True)
    table = ((np.asarray(hi).astype(np.uint64) << np.uint64(32))
             | np.asarray(lo).astype(np.uint64))
    assert table.shape == (2, 5) and (table == FIRST_HIT_NONE).all()
    assert not np.asarray(m).any()
    mb, hib, lob = ops.refine_tracks_batched(
        jnp.zeros((0, 4, 0), jnp.uint32), jnp.zeros((0, 0), jnp.int32),
        cov, 5, impl=impl, with_first_hits=True)
    assert np.asarray(mb).shape == (0, 5)
    assert np.asarray(hib).shape == (0, 2, 5)


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_refine_tracks_empty_inputs(impl):
    """Zero docs, zero points, empty cover region."""
    from repro.exec.refine import pack_constraints
    from repro.geo.areatree import AreaTree
    cov = jnp.asarray(pack_constraints([(AreaTree.empty(), 0.0, 1.0)]))
    pts0 = jnp.zeros((4, 0), jnp.uint32)
    rows0 = jnp.zeros((0,), jnp.int32)
    assert np.asarray(ops.refine_tracks(pts0, rows0, cov, 0,
                                        impl=impl)).shape == (0,)
    got = np.asarray(ops.refine_tracks(pts0, rows0, cov, 7, impl=impl))
    assert got.shape == (7,) and not got.any()
    # points exist but the cover is empty → nothing can match
    rng = np.random.default_rng(0)
    _, _, pts, rows, _ = _refine_case(rng, 16, 6, 1)
    assert not np.asarray(ops.refine_tracks(pts, rows, cov, 16,
                                            impl=impl)).any()



@pytest.mark.parametrize("tables", ["first_hits", "analytics"])
def test_refine_tracks_multi(tables):
    """Q = 3 coalesced queries over one wave whose shards differ in P and
    doc count (a track over three point blocks, an empty doc block, a
    shard with no points): every output table of the kernel ≡ the jnp
    oracle's, and the verdicts under ordering edges (and count / dwell
    reductions) ≡ the numpy host oracle's."""
    from repro.exec.refine import pack_constraints_multi, refine_tracks_host
    from repro.kernels import fused
    rng = np.random.default_rng(14)
    shards = _WAVES["banded"]
    cases = [_refine_case(rng, n, lens, 1, empty_every=7)
             for n, lens in shards]
    n_max = max(n for n, _ in shards)
    pts, rows = _wave(cases)
    cons_list = [_constraints(rng, k) for k in (2, 3, 1)]
    cov = jnp.asarray(pack_constraints_multi(cons_list))
    edges = (((0, 1),), ((2, 0), (0, 1)), ())
    if tables == "analytics":
        mcs, dws = ((2, 1), (1, 1, 3), (1,)), ((None, 600.0), (), ())
    else:
        mcs, dws = ((), (), ()), ((), (), ())
    flag = {f"with_{tables}": True}
    got = ops.refine_tracks_multi(pts, rows, cov, n_max, impl="interpret",
                                  **flag)
    want = ops.refine_tracks_multi(pts, rows, cov, n_max, impl="reference",
                                   **flag)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))
    verdict = {impl: np.asarray(fused._refine_multi_stage(
        impl, pts, rows, cov, n_max, edges, mcs, dws))
        for impl in ("interpret", "reference")}
    assert np.array_equal(verdict["interpret"], verdict["reference"])
    for q, cons in enumerate(cons_list):
        for i, (case, (n, _)) in enumerate(zip(cases, shards)):
            lat, lng, t, splits = case[0]
            want_q = refine_tracks_host(
                lat, lng, t, splits, n, cons, edges=edges[q],
                min_counts=mcs[q] or None, dwells=dws[q] or None)
            assert np.array_equal(verdict["interpret"][q, i, :n],
                                  want_q), (q, i)
    assert verdict["interpret"].any()            # non-vacuous evidence


def _csr_rows(rng, n_docs):
    """rows of a random CSR layout: mostly short tracks, runs of empty
    ones and now and then a track over several point blocks."""
    lens = rng.geometric(0.1, n_docs) - 1
    lens[rng.random(n_docs) < 0.02] = rng.integers(500, 2_000)
    for _ in range(rng.integers(0, 3)):
        a = int(rng.integers(0, n_docs))
        lens[a:a + int(rng.integers(1, 300))] = 0
    return np.repeat(np.arange(n_docs, dtype=np.int32), lens)


@pytest.mark.parametrize("seed", range(6))
def test_refine_band_schedule(seed):
    """The banded walk on random CSR wave layouts: within the static
    bound, doc blocks nondecreasing, every doc block initialised once on
    the first step of its run, and every overlapping (doc block, point
    block) pair computed exactly once — no other."""
    from repro.kernels import refine as R
    tb, db = R.DEFAULT_POINT_BLOCK, R.DEFAULT_DOC_BLOCK
    rng = np.random.default_rng(seed)
    n_docs = [int(rng.integers(0, 900)) for _ in range(3)]
    shard_rows = [_csr_rows(rng, n) for n in n_docs]
    num_docs = max(n_docs) + int(rng.integers(0, 200))
    p = max(1, max(r.size for r in shard_rows))
    n_pb, n_db = -(-p // tb), -(-num_docs // db)
    rows = np.full((len(shard_rows), n_pb * tb), -1, np.int32)
    for i, r in enumerate(shard_rows):
        rows[i, :r.size] = r
    n_steps, dense = R.grid_steps(1, 1, p, num_docs)
    assert n_steps <= n_pb + n_db and dense == n_pb * n_db
    shift = R._step_shift(n_pb, n_db)
    steps = np.asarray(R._band_schedule(jnp.asarray(rows), n_steps, tb, db,
                                        n_db, shift))
    steps = steps.reshape(len(shard_rows), n_steps)
    for i, r in enumerate(shard_rows):
        g = steps[i] >> shift
        pb = (steps[i] >> 2) & ((1 << (shift - 2)) - 1)
        init = (steps[i] & R._INIT) != 0
        work = (steps[i] & R._COMPUTE) != 0
        assert (np.diff(g) >= 0).all()
        assert (pb < n_pb).all()
        assert np.array_equal(g[init], np.arange(n_db))
        assert init[0] and (init[1:] == (np.diff(g) > 0)).all()
        pairs = list(zip(g[work].tolist(), pb[work].tolist()))
        want = set(zip((r // db).tolist(), (np.arange(r.size) // tb)
                       .tolist()))
        assert len(pairs) == len(set(pairs)) and set(pairs) == want


def test_refine_grid_steps_at_benchmark_shape():
    """A sec6_trips shard (6,000 trips, ~115,400 points): the banded walk
    runs under 3 % of the dense doc-block × point-block grid."""
    from repro.kernels.refine import grid_steps
    banded, dense = grid_steps(8, 10, 115_400, 6_000)
    assert banded / dense < 0.03
    assert grid_steps(3, 2, 0, 6_000) == (0, 0)

# ------------------------------------------------------ flash attention

def _fa_case(b, hq, hkv, sq, skv, d, dtype=np.float32, **kw):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)).astype(dtype))
    k = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(dtype))
    v = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(dtype))
    got = ops.flash_attention(q, k, v, impl="interpret", block_q=64,
                              block_k=128, **kw)
    want = ops.flash_attention(q, k, v, impl="reference", **kw)
    tol = 2e-2 if dtype == np.dtype(np.float16) else 3e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [
    (2, 4, 2, 128, 128, 64),      # GQA causal
    (1, 2, 1, 256, 256, 64),
    (1, 8, 8, 64, 64, 128),       # MHA
    (1, 2, 1, 100, 200, 64),      # ragged + decode offset
    (1, 4, 2, 1, 384, 64),        # single-token decode
])
def test_flash_attention_shapes(shape):
    _fa_case(*shape)


def test_flash_attention_window_softcap():
    _fa_case(1, 2, 1, 256, 256, 64, window=64)
    _fa_case(1, 2, 2, 128, 128, 64, softcap=30.0)
    _fa_case(1, 2, 1, 192, 192, 64, window=50, softcap=20.0)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 1, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 1, 128, 64))).astype(jnp.bfloat16)
    got = ops.flash_attention(q, k, v, impl="interpret", block_q=64,
                              block_k=64)
    want = ops.flash_attention(q, k, v, impl="reference")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ------------------------------------------------------------ ssm scan

@pytest.mark.parametrize("b,l,d", [(2, 64, 32), (1, 500, 130),
                                   (3, 1024, 16), (1, 7, 260)])
def test_ssm_scan(b, l, d):
    a = jnp.asarray(RNG.uniform(0.5, 1.0, (b, l, d)).astype(np.float32))
    bx = jnp.asarray(RNG.normal(size=(b, l, d)).astype(np.float32))
    hg, hTg = ops.ssm_scan(a, bx, impl="interpret", chunk=128)
    hr, hTr = ops.ssm_scan(a, bx, impl="reference")
    np.testing.assert_allclose(np.asarray(hg), np.asarray(hr), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(hTg), np.asarray(hTr),
                               rtol=3e-4, atol=3e-4)


@given(st.integers(1, 40), st.integers(1, 6), st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_ssm_scan_property(l, b, seed):
    """h_t = a_t h_{t-1} + bx_t against a python loop."""
    rng = np.random.default_rng(seed)
    d = 8
    a = rng.uniform(0.2, 1.0, (b, l, d)).astype(np.float32)
    bx = rng.normal(size=(b, l, d)).astype(np.float32)
    hg, hT = ops.ssm_scan(jnp.asarray(a), jnp.asarray(bx),
                          impl="interpret", chunk=16)
    h = np.zeros((b, d), np.float32)
    for t in range(l):
        h = a[:, t] * h + bx[:, t]
        np.testing.assert_allclose(np.asarray(hg)[:, t], h, rtol=2e-3,
                                   atol=2e-3)
    np.testing.assert_allclose(np.asarray(hT), h, rtol=2e-3, atol=2e-3)
