"""Generated tables load through the program's FDb.load, give the same
array sizes for every seed, and the program's answers on them equal the
numpy backend's and the plain reference's."""
import numpy as np
import pytest

from chipbench.gen import ensure
from chipbench.reference import tables as ref_tables
from chipbench.reference import tesseract as ref_tess
from chipbench.reference import variability as ref_var

from .conftest import small_config, traffic


@pytest.fixture(scope="module")
def worlds(tmp_path_factory):
    from repro.core import Session
    from repro.exec import Catalog
    from repro.fdb import FDb
    root = tmp_path_factory.mktemp("data")
    out = {}
    for name in ("sec6_speedobs", "sec6_trips"):
        cfg = small_config(name)
        directory, _, reused = ensure(cfg, 2**31 + 17, str(root))
        assert not reused
        cat = Catalog()
        cat.register(FDb.load(directory))
        out[name] = (cfg, directory, cat, ref_tables.load(directory))
    return out


def _run(cat, flow, backend):
    from repro.core import Session
    return Session(catalog=cat, backend=backend).run(flow)


@pytest.mark.parametrize("name,query", [
    ("sec6_speedobs", "Q1"), ("sec6_trips", "Q6"), ("sec6_trips", "Q10")])
def test_answers_match_numpy_backend_and_reference(worlds, name, query):
    from chipbench.kinds import tesseract, variability
    cfg, _, cat, tables = worlds[name]
    mix = traffic("cov_analyst" if name == "sec6_speedobs"
                  else "tess_analyst")
    spec = next(q for q in mix["queries"] if q["name"] == query)
    kind = variability if spec["kind"] == "variability" else tesseract
    ref = ref_var if spec["kind"] == "variability" else ref_tess
    flow = kind.flow(spec, cfg)
    got_jax = kind.answer(_run(cat, flow, "jax"))
    got_np = kind.answer(_run(cat, flow, "numpy"))
    want = ref.expected(tables, spec, cfg)
    key = "road_id" if spec["kind"] == "variability" else "id"
    assert got_jax[key].size > 0
    for col in got_np:
        np.testing.assert_allclose(got_jax[col], got_np[col], rtol=1e-6)
    numbers = ref.compare(got_np, want)
    assert numbers.get("groups_mismatched", 0) == 0
    assert numbers.get("rows_mismatched", 0) == 0
    assert numbers.get("cov_gap_max", 0.0) < 1e-6


def test_reuses_files_of_the_same_seed(worlds, tmp_path):
    cfg, directory, _, _ = worlds["sec6_trips"]
    root = str(tmp_path)
    first = ensure(cfg, 5, root)
    again = ensure(cfg, 5, root)
    assert not first[2] and again[2] and first[0] == again[0]


@pytest.mark.parametrize("name", ["sec6_speedobs", "sec6_trips"])
def test_every_seed_gives_the_same_sizes(name, tmp_path):
    """Every seed holds the same rows in the same shards, in another
    order: the same postings and selections, so the same shapes."""
    cfg = small_config(name)

    def shards(seed):
        directory, _, _ = ensure(cfg, seed, str(tmp_path))
        out = []
        for i in range(cfg["shards"]):
            with np.load(f"{directory}/shard-{i:05d}.npz") as z:
                out.append({k: z[k] for k in z.files})
        return out
    key = "col/road_id/values" if name == "sec6_speedobs" \
        else "col/id/values"
    moved = False
    for a, b in zip(shards(1), shards(2**31 + 5)):
        assert sorted(a) == sorted(b)
        for k in a:
            assert a[k].shape == b[k].shape
        order_a, order_b = np.argsort(a[key]), np.argsort(b[key])
        assert np.array_equal(a[key][order_a], b[key][order_b])
        moved |= not np.array_equal(a[key], b[key])
        if name == "sec6_trips":
            lens = [np.diff(s["col/track.t/splits"]) for s in (a, b)]
            assert np.array_equal(lens[0][order_a], lens[1][order_b])
    assert moved
