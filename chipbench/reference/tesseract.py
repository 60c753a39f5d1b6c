"""Tesseract trip selection (the paper's §2 queries, Q6-Q11).

A trip satisfies a leg when some track point lies in the leg's region
with its time inside the leg's window (both ends inclusive).  A leg may
ask for at least ``at_least`` such points, or for a dwell: at least one
hit and ``last hit - first hit >= dwell_s``.  An ordered query asks each
leg's first hit to come strictly before the next leg's.  The answer is
the trips that satisfy every leg, with their day and duration.
"""
from __future__ import annotations

import numpy as np

from .geo import in_cities

__all__ = ["expected", "control", "compare", "legs", "candidates"]

DAY_S = 86400.0
HOUR_S = 3600.0


def legs(spec: dict):
    """``(cities, t0, t1, at_least, dwell_s)`` per leg, in seconds of the
    week."""
    base = spec["day"] * DAY_S
    return [(leg["cities"], base + leg["hours"][0] * HOUR_S,
             base + leg["hours"][1] * HOUR_S, int(leg.get("at_least", 1)),
             leg.get("dwell_s")) for leg in spec["legs"]]


def _points(tables):
    splits = tables["track.lat/splits"]
    trip = np.repeat(np.arange(splits.size - 1), np.diff(splits))
    return trip, splits.size - 1


def _answer(tables, ok, dtype=np.float64):
    ids = tables["id"][ok]
    order = np.argsort(ids)
    dur = tables["duration_s"][ok][order].astype(dtype)
    return {"id": ids[order], "day": tables["day"][ok][order],
            "duration_s": dur.astype(np.float64)}


def expected(tables: dict, spec: dict, cfg: dict, dtype=np.float64) -> dict:
    """The answer, with every coordinate, time and point test in
    ``dtype`` (the configuration's precision by default)."""
    f = np.dtype(dtype).type
    trip, n = _points(tables)
    t = tables["track.t"].astype(dtype)
    lat = tables["track.lat"].astype(dtype)
    lng = tables["track.lng"].astype(dtype)
    ok = np.ones(n, dtype=bool)
    firsts = []
    for cities, t0, t1, k, dwell in legs(spec):
        hit = in_cities(lat, lng, cities, cfg["cities"], dtype) \
            & (t >= f(t0)) & (t <= f(t1))
        count = np.bincount(trip[hit], minlength=n)
        first = np.full(n, np.inf, dtype=dtype)
        np.minimum.at(first, trip[hit], t[hit])
        last = np.full(n, -np.inf, dtype=dtype)
        np.maximum.at(last, trip[hit], t[hit])
        if k > 0:                    # at_least(0) stops filtering
            ok &= count >= k
        if dwell is not None:
            ok &= (count > 0) & (last - first >= f(dwell))
        firsts.append(first)
    if spec.get("ordered"):
        for a, b in zip(firsts, firsts[1:]):
            ok &= a < b
    return _answer(tables, ok, dtype)


def candidates(tables: dict, spec: dict, cfg: dict, bucket_s: float = 900.0
               ) -> np.ndarray:
    """Trips the spacetime index would hand to the exact pass: for every
    leg some point in the region's cells whose 15-minute bucket meets the
    window's buckets."""
    trip, n = _points(tables)
    t = tables["track.t"]
    b = np.floor(t / bucket_s)
    ok = np.ones(n, dtype=bool)
    for cities, t0, t1, _, _ in legs(spec):
        hit = in_cities(tables["track.lat"], tables["track.lng"], cities,
                        cfg["cities"]) \
            & (b >= np.floor(t0 / bucket_s)) & (b <= np.floor(t1 / bucket_s))
        ok &= np.bincount(trip[hit], minlength=n) > 0
    return ok


def control(tables: dict, spec: dict, cfg: dict) -> dict:
    """The reference one precision step below the configuration's float64:
    the table, the point tests and the answer in float32."""
    return expected(tables, spec, cfg, np.float32)


def compare(got: dict, want: dict) -> dict:
    """``rows_mismatched``: trips in one answer and not the other, repeated
    trips, and trips whose day or duration differ."""
    gi = np.asarray(got["id"], dtype=np.int64)
    wi = np.asarray(want["id"], dtype=np.int64)
    dup = gi.size - np.unique(gi).size
    common, a, b = np.intersect1d(gi, wi, return_indices=True)
    missing = (gi.size - dup - common.size) + (wi.size - common.size)
    off = np.sum((np.asarray(got["day"])[a] != np.asarray(want["day"])[b])
                 | (np.asarray(got["duration_s"])[a]
                    != np.asarray(want["duration_s"])[b]))
    return {"rows_mismatched": int(dup + missing + off)}
