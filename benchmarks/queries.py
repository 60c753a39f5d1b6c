"""Shared query library for the paper-table benchmarks.

Builds the §6 world (roads + speed observations + trips) and two query
families:

  * Q1–Q5 — traffic speed variability: "accumulate all the speed
    observations per road segment during the morning rush hours (8−9 am on
    weekdays), and compute the standard deviation of the speeds, normalized
    with respect to its mean — the *coefficient of variation*",
  * Q6–Q7 — Tesseract trip queries (§2): "all trips passing through region
    A during time window T1 and region B during T2", served by the
    per-shard ``spacetime`` index (:mod:`repro.tess`),
  * Q8–Q9 — *ordered* Tesseract trip queries: the same legs sequenced with
    ``Tesseract.then()`` ("through A during T1 **and then** B during T2"),
    resolved by the refine kernel's per-constraint first-hit timestamps.
"""
from __future__ import annotations

import time

import jax

from repro.core import P, proto, IN, BETWEEN, group, fdb
from repro.data.synthetic import (CITIES, BAY_AREA, city_region,
                                  generate_world)
from repro.exec import AdHocEngine, Catalog
from repro.fdb import build_fdb
from repro.geo import AreaTree
from repro.tess import Tesseract

__all__ = ["sync", "time_best", "build_catalog", "region_for",
           "q_variability", "QUERIES", "tesseract_for", "q_tesseract",
           "TRIP_QUERIES", "TRIP_DAY", "ORDERED_TRIP_QUERIES"]


def sync(out):
    """Block on the device values reachable from ``out``: jax dispatch is
    async, so a clock must stop at completion, not at enqueue."""
    jax.block_until_ready(out)
    return out


def time_best(fn, repeats: int = 3):
    """``(result, best wall ms of repeats)`` after one warm-up call (jit
    compile and priming stay out of the timed calls)."""
    sync(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = sync(fn())
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e3


def build_catalog(scale: float = 1.0, num_shards: int = 20,
                  seed: int = 0) -> Catalog:
    world = generate_world(scale=scale, seed=seed)
    cat = Catalog(server_slots=64)
    cat.register(build_fdb("Roads", world["roads_schema"],
                           world["roads"], num_shards=max(4, num_shards // 4)))
    cat.register(build_fdb("SpeedObservations",
                           world["observations_schema"],
                           world["observations"], num_shards=num_shards))
    cat.register(build_fdb("RouteRequests",
                           world["route_requests_schema"],
                           world["route_requests"],
                           num_shards=max(4, num_shards // 4)))
    cat.register(build_fdb("Trips", world["trips_schema"], world["trips"],
                           num_shards=max(10, num_shards // 2)))
    return cat


def region_for(cities) -> AreaTree:
    """Union of city bounding boxes → selection region."""
    return city_region(*cities)


def q_variability(cities, months: int, *, mode: str = "multi_index",
                  sample: float | None = None):
    """Coefficient-of-variation per road (Q1–Q5) under a selection mode.

    mode = 'multi_index'  — geospatial + hour + dow + month indices
           'geo_index'    — geospatial index only; time filtered post-read
           'full_scan'    — no index use at all (filter everything)
    """
    region = region_for(cities)
    flow = fdb("SpeedObservations")
    time_pred = (BETWEEN(P.hour, 8, 9) & BETWEEN(P.dow, 0, 4)
                 & BETWEEN(P.month, 1, months))
    if mode == "multi_index":
        flow = flow.find(IN(P.loc, region) & time_pred)
    elif mode == "geo_index":
        flow = flow.find(IN(P.loc, region)).filter(time_pred)
    elif mode == "full_scan":
        # obscure the predicates so the planner cannot use any index:
        # (x + 0) is no longer a bare FieldRef
        flow = flow.filter(
            IN(P.loc, region) if False else (
                ((P.hour + 0) >= 8) & ((P.hour + 0) <= 9)
                & ((P.dow + 0) <= 4) & ((P.month + 0) <= months)))
        # geospatial containment without the index:
        flow = flow.filter(IN_region_residual(region))
    else:
        raise ValueError(mode)
    if sample:
        flow = flow.sample(sample)
    return (flow.aggregate(group(P.road_id)
                           .avg(mean_speed=P.speed)
                           .std_dev(std_speed=P.speed)
                           .count("n"))
            .map(lambda p: proto(road_id=p.road_id, n=p.n,
                                 cov=p.std_speed / p.mean_speed)))


def IN_region_residual(region):
    """Point-in-region as a plain expression (no index use)."""
    from repro.core.exprs import InRegion, FieldRef, ExprProxy, BinOp, Lit
    # InRegion on a synthetic FieldRef copy — identical math, but applied
    # via filter() so the planner never sees it in find()
    return ExprProxy(InRegion(FieldRef("loc"), region))


#: paper §6 query list
QUERIES = {
    "Q1": (("SF",), 1),
    "Q2": (("SF",), 6),
    "Q3": (BAY_AREA, 1),
    "Q4": (BAY_AREA, 6),
    "Q5": (tuple(CITIES), 1),       # "California" = every city
}


# --------------------------------------------------------------------------
# Tesseract trip queries (Q6–Q7)
# --------------------------------------------------------------------------

#: synthetic-week day the trip queries pin their windows to (0=Mon … 6=Sun)
TRIP_DAY = 2


def tesseract_for(legs, day: int = TRIP_DAY,
                  ordered: bool = False) -> Tesseract:
    """``legs``: sequence of ``(cities, hour0, hour1)`` constraints — the
    trip must pass through ``region_for(cities)`` during ``[hour0, hour1]``
    of ``day`` (track ``t`` is seconds since the week's epoch).
    ``ordered`` sequences the legs with ``then()``: each leg's first hit
    must come strictly before the next leg's (A-then-B trip queries)."""
    tess = None
    for cities, h0, h1 in legs:
        region = region_for(cities)
        t0 = day * 86400.0 + h0 * 3600.0
        t1 = day * 86400.0 + h1 * 3600.0
        tess = Tesseract(region, t0, t1) if tess is None \
            else (tess.then(region, t0, t1) if ordered
                  else tess.also(region, t0, t1))
    return tess


def q_tesseract(legs, day: int = TRIP_DAY, ordered: bool = False):
    """Trip ids + durations matching a multi-constraint Tesseract query."""
    return (fdb("Trips").tesseract(tesseract_for(legs, day,
                                                 ordered=ordered))
            .map(lambda p: proto(id=p.id, day=p.day,
                                 duration_s=p.duration_s)))


#: Q6: morning SF → Berkeley commute; Q7: Bay Area → LA long-haul
TRIP_QUERIES = {
    "Q6": ((("SF",), 6, 12), (("Berkeley",), 6, 14)),
    "Q7": ((BAY_AREA, 6, 12), (("LA",), 6, 18)),
}

#: ordered (A-then-B) variants: Q8 sequences Q6's commute (SF first, then
#: Berkeley), Q9 sequences Q7's long-haul (Bay Area first, then LA) — the
#: synthetic inter-city trips run origin-city-first, so ordering keeps the
#: true A→B trips and drops the B→A ones Q6/Q7 also admit
ORDERED_TRIP_QUERIES = {
    "Q8": TRIP_QUERIES["Q6"],
    "Q9": TRIP_QUERIES["Q7"],
}
