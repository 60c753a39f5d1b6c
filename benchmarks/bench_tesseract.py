"""Tesseract trip-query benchmark (Q6–Q9): pruning ratio + backend parity.

For each trip query the report shows

  * wall time per backend (numpy oracle vs jax kernel dispatch),
  * **index-probe candidate counts vs. exact-refine counts** — how many
    trips the per-shard ``spacetime`` postings admit at (cell × bucket)
    granularity vs. how many survive the exact point-in-cover ×
    time-window pass — and the resulting pruning ratio,
  * a byte-level parity verdict between the backends' trip-id sets *and*
    between their per-shard candidate/refined counts (the
    ``refine_tracks`` op parity gate), and
  * the launch count on the jax path: the whole selection (probe →
    exact refine → compact) is ⌈shards/wave⌉ fused ``run_wave_fused``
    device dispatches per query — the per-shard host refine and the
    per-primitive launches are gone from the hot loop (with
    ``REPRO_EXEC_FUSED=0`` the evidence reverts to ⌈shards/wave⌉
    ``refine_tracks_batched`` launches, still zero per-shard ops).

Q8–Q9 are the *ordered* (A-then-B) variants of Q6–Q7: the same legs
sequenced with ``Tesseract.then()``.  Their parity verdict additionally
compares the per-(doc × constraint) **first-hit timestamp tables** across
backends byte-for-byte (the table the ordering DAG is resolved against),
and their launch evidence shows ordering rides the same fused wave
dispatches — no extra launches.

The pruning ratio is the subsystem's reason to exist: for selective
regions the index must prune ≥ 90 % of trips before the exact pass.
"""
from __future__ import annotations

import math

import numpy as np

from repro.data.synthetic import generate_world
from repro.exec import AdHocEngine, Catalog, get_backend
from repro.exec.batched import fused_enabled
from repro.fdb import build_fdb
from repro.kernels import ops
from repro.tess import tesseract_stats

from .queries import (ORDERED_TRIP_QUERIES, TRIP_QUERIES, q_tesseract,
                      tesseract_for, time_best)

__all__ = ["run"]


def _first_hit_parity(db, tess) -> bool:
    """Byte parity of the per-shard first-hit tables across backends."""
    cons = list(tess.constraints)
    batches = [sh.batch for sh in db.shards]
    _, tab_n = get_backend("numpy").refine_tracks_batched(
        batches, tess.field, cons, with_first_hits=True)
    _, tab_j = get_backend("jax").refine_tracks_batched(
        batches, tess.field, cons, with_first_hits=True)
    return all(np.array_equal(a, b) for a, b in zip(tab_n, tab_j))


def run(scale: float = 0.5, print_fn=print, raise_on_mismatch: bool = True):
    rows: list = []
    # floor the world size: below ~0.2 the synthetic week holds so few
    # trips that Q6–Q9 select nothing, which would turn the parity and
    # pruning evidence vacuous (the CI smoke runs --scale 0.05)
    scale = max(scale, 0.2)
    # trips-only catalog: skip the (dominant) ingest/index cost of the
    # road/observation datasets the trip queries never touch
    world = generate_world(scale=scale)
    cat = Catalog(server_slots=64)
    cat.register(build_fdb("Trips", world["trips_schema"], world["trips"],
                           num_shards=10))
    db = cat.get("Trips")
    engines = {b: AdHocEngine(cat, backend=b) for b in ("numpy", "jax")}
    all_parity = True
    all_queries = {**{q: (legs, False) for q, legs in TRIP_QUERIES.items()},
                   **{q: (legs, True)
                      for q, legs in ORDERED_TRIP_QUERIES.items()}}
    for qname, (legs, ordered) in all_queries.items():
        flow = q_tesseract(legs, ordered=ordered)
        tess = tesseract_for(legs, ordered=ordered)
        results, times = {}, {}
        for bname, eng in engines.items():
            res, ms = time_best(lambda e=eng: e.collect(flow), repeats=2)
            results[bname], times[bname] = res, ms
        ids = {b: np.sort(r.batch["id"].values)
               for b, r in results.items()}
        # refine-op byte parity: identical per-shard candidate/refined
        # counts across backends (kernel mask ≡ numpy oracle mask); for
        # ordered queries also the first-hit tables byte-for-byte
        stats = tesseract_stats(db, tess, backend="numpy")
        stats_j = tesseract_stats(db, tess, backend="jax")
        refine_parity = stats["per_shard"] == stats_j["per_shard"]
        if ordered:
            refine_parity &= _first_hit_parity(db, tess)
        # launch evidence: the whole selection (probe → refine → compact)
        # is ⌈shards/wave⌉ ``run_wave_fused`` dispatches per query — no
        # per-primitive or per-shard launches remain.  REPRO_EXEC_FUSED=0
        # restores the legacy contract: ⌈shards/wave⌉ batched refine
        # launches, still zero per-shard host refines.
        ops.reset_launch_counts()
        engines["jax"].collect(flow)
        lc = ops.launch_counts()
        waves = math.ceil(db.num_shards / engines["jax"].wave)
        if fused_enabled():
            refine_launches = lc.get("run_wave_fused", 0)
            fused = (refine_launches == waves
                     and lc.get("refine_tracks_batched", 0) == 0
                     and lc.get("refine_tracks", 0) == 0)
        else:
            refine_launches = lc.get("refine_tracks_batched", 0)
            fused = (refine_launches == waves
                     and lc.get("refine_tracks", 0) == 0)
        parity = bool(np.array_equal(ids["numpy"], ids["jax"])) \
            and results["numpy"].profile.rows_selected \
            == results["jax"].profile.rows_selected \
            and refine_parity and fused
        all_parity &= parity
        speedup = times["numpy"] / max(times["jax"], 1e-9)
        rows.append({
            "name": f"tesseract_{qname}",
            "us_per_call": round(times["jax"] * 1e3, 1),
            "parity": 1 if parity else 0,
            "derived": (f"numpy={times['numpy']:.1f}ms "
                        f"jax={times['jax']:.1f}ms "
                        f"speedup={speedup:.2f}x "
                        f"docs={stats['docs']} "
                        f"candidates={stats['candidates']} "
                        f"refined={stats['refined']} "
                        f"pruning={stats['pruning']:.3f} "
                        f"ordered={1 if ordered else 0} "
                        + ("fused_launches" if fused_enabled()
                           else "refine_launches")
                        + f"={refine_launches}/{waves}waves "
                        f"parity={'OK' if parity else 'MISMATCH'}")})
        print_fn(f"  {qname}: {rows[-1]['derived']}")
        if stats["pruning"] < 0.9:
            print_fn(f"  WARNING: {qname} pruning "
                     f"{stats['pruning']:.3f} < 0.90")
    rows.append({"name": "tesseract_parity_all",
                 "us_per_call": "",
                 "parity": 1 if all_parity else 0,
                 "derived": "OK" if all_parity else "MISMATCH"})
    print_fn(f"  parity across trip queries: "
             f"{'OK' if all_parity else 'MISMATCH'}")
    if not all_parity and raise_on_mismatch:
        raise AssertionError("tesseract backend parity violated")
    return rows
