"""Analytics benchmark: dwell/count trip queries + time-to-trained-model.

Q10–Q11 extend the Q6–Q9 trip-query family with the refine kernel's
reduction outputs — computed in the *same* one-hot compare pass, at zero
extra launches:

  * **Q10 (count)** — trips with ≥ 2 distinct SF window hits and a
    Berkeley hit (``Tesseract.at_least(2)``): the per-constraint hit
    *count* reduction,
  * **Q11 (dwell)** — trips that stayed inside the SF window at least 10
    simulated minutes (``Tesseract.dwell(600)``): the last-hit − first-hit
    span reduction.

Each row carries the same evidence as the Q6–Q9 suite: numpy-vs-jax trip
id parity, per-shard candidate/refined count parity, and the launch
contract — the reductions ride the existing ⌈shards/wave⌉ fused
dispatches (``REPRO_EXEC_FUSED=0`` reverts to ⌈shards/wave⌉ batched
refine launches, still zero per-shard ops).

The **time-to-trained-model** row closes the paper's §5 loop as a gated
number: ``Flow.to_dataset(features=..., target=...)`` streams
query-selected rows into an ``MLPRegressor`` and the row's wall time is
selection + training end to end, so a regression in either the query
path or the training hand-off trips the gate.
"""
from __future__ import annotations

import math

import numpy as np

from repro.core import P, BETWEEN, fdb, proto
from repro.exec import AdHocEngine, Catalog
from repro.exec.batched import fused_enabled
from repro.fdb import build_fdb
from repro.data.synthetic import generate_world
from repro.kernels import ops
from repro.tess import Tesseract, tesseract_stats

from .queries import TRIP_DAY, build_catalog, region_for, time_best

__all__ = ["run"]


def _win(h0: float, h1: float, day: int = TRIP_DAY):
    return day * 86400.0 + h0 * 3600.0, day * 86400.0 + h1 * 3600.0


def analytics_tesseracts():
    """Q10 (count) / Q11 (dwell) — the Q6 commute legs with reductions."""
    sf, bk = region_for(("SF",)), region_for(("Berkeley",))
    return {
        "Q10": (Tesseract(sf, *_win(6, 12), label="sf").at_least(2)
                .also(bk, *_win(6, 14), label="berkeley")),
        "Q11": (Tesseract(sf, *_win(6, 12), label="sf").dwell(600.0)
                .also(bk, *_win(6, 14), label="berkeley")),
    }


def run(scale: float = 0.5, print_fn=print, raise_on_mismatch: bool = True):
    rows: list = []
    # same floor as the tesseract suite: below ~0.2 the synthetic week is
    # too sparse for the reductions to select anything (vacuous evidence)
    trip_scale = max(scale, 0.2)
    world = generate_world(scale=trip_scale)
    cat = Catalog(server_slots=64)
    cat.register(build_fdb("Trips", world["trips_schema"], world["trips"],
                           num_shards=10))
    db = cat.get("Trips")
    engines = {b: AdHocEngine(cat, backend=b) for b in ("numpy", "jax")}
    all_parity = True

    for qname, tess in analytics_tesseracts().items():
        flow = fdb("Trips").tesseract(tess).map(lambda p: proto(id=p.id))
        results, times = {}, {}
        for bname, eng in engines.items():
            res, ms = time_best(lambda e=eng: e.collect(flow), repeats=2)
            results[bname], times[bname] = res, ms
        ids = {b: np.sort(r.batch["id"].values)
               for b, r in results.items()}
        stats = tesseract_stats(db, tess, backend="numpy")
        stats_j = tesseract_stats(db, tess, backend="jax")
        refine_parity = stats["per_shard"] == stats_j["per_shard"]
        # launch contract: the count/dwell reductions ride the existing
        # fused wave dispatches — same counts as a plain trip query
        ops.reset_launch_counts()
        engines["jax"].collect(flow)
        lc = ops.launch_counts()
        waves = math.ceil(db.num_shards / engines["jax"].wave)
        if fused_enabled():
            launches = lc.get("run_wave_fused", 0)
            contract = (launches == waves
                        and lc.get("refine_tracks_batched", 0) == 0
                        and lc.get("refine_tracks", 0) == 0)
        else:
            launches = lc.get("refine_tracks_batched", 0)
            contract = (launches == waves
                        and lc.get("refine_tracks", 0) == 0)
        parity = bool(np.array_equal(ids["numpy"], ids["jax"])) \
            and refine_parity and contract
        all_parity &= parity
        rows.append({
            "name": f"analytics_{qname}",
            "us_per_call": round(times["jax"] * 1e3, 1),
            "parity": 1 if parity else 0,
            "derived": (f"numpy={times['numpy']:.1f}ms "
                        f"jax={times['jax']:.1f}ms "
                        f"selected={ids['jax'].size} "
                        f"candidates={stats['candidates']} "
                        f"refined={stats['refined']} "
                        + ("fused_launches" if fused_enabled()
                           else "refine_launches")
                        + f"={launches}/{waves}waves "
                        f"parity={'OK' if parity else 'MISMATCH'}")})
        print_fn(f"  {qname}: {rows[-1]['derived']}")
        if ids["jax"].size == 0:
            print_fn(f"  WARNING: {qname} selected nothing — reduction "
                     f"evidence vacuous at scale {trip_scale}")

    # ---- time-to-trained-model (§5): query-selected rows → MLP train ----
    ttm_cat = build_catalog(scale=max(scale, 0.1), num_shards=12)
    roads_tbl = (fdb("Roads").collect(AdHocEngine(ttm_cat, backend="jax"))
                 .to_dict("id"))
    eng = AdHocEngine(ttm_cat, backend="jax")

    def ttm():
        ds = (fdb("SpeedObservations")
              .find(BETWEEN(P.month, 1, 4))
              .to_dataset(features={"hour": P.hour * 1.0,
                                    "dow": P.dow * 1.0,
                                    "sl": roads_tbl[P.road_id].speed_limit},
                          target=P.speed, engine=eng))
        model, losses = ds.fit(steps=60, lr=2e-3, batch=256)
        return ds, losses

    (ds, losses), ms = time_best(ttm, repeats=2)
    trained = bool(len(ds) > 0 and losses[-1] < losses[0])
    all_parity &= trained
    rows.append({
        "name": "analytics_time_to_trained_model",
        "us_per_call": round(ms * 1e3, 1),
        "parity": 1 if trained else 0,
        "derived": (f"rows={len(ds)} steps=60 "
                    f"loss={losses[0]:.2f}->{losses[-1]:.2f} "
                    f"trained={'OK' if trained else 'FAILED'}")})
    print_fn(f"  time_to_trained_model: {rows[-1]['derived']} "
             f"({ms:.0f}ms end-to-end)")

    rows.append({"name": "analytics_parity_all",
                 "us_per_call": "",
                 "parity": 1 if all_parity else 0,
                 "derived": "OK" if all_parity else "MISMATCH"})
    print_fn(f"  analytics parity: {'OK' if all_parity else 'MISMATCH'}")
    if not all_parity and raise_on_mismatch:
        raise AssertionError("analytics backend parity violated")
    return rows
