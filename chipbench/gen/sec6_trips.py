"""Trips of the §6 world: space-time tracks over the road network.

The distributions of ``repro.data.synthetic.generate_world``'s trips,
vectorised: a start city by the city weights, a neighbouring end city for
``inter_city_share`` of trips (the second half of the segments there), a
commute-shaped start hour over a 7-day week, and each segment's points
copied from a road's polyline, spaced ``point_gap_s`` apart.
"""
from __future__ import annotations

import numpy as np

from .world import city_names, roads, rng_for

__all__ = ["shards"]


def _start_hours(cfg: dict, rng, n: int) -> np.ndarray:
    u = rng.uniform(0, 1, n)
    out = np.empty(n)
    edge = 0.0
    for part in cfg["start_hour_mix"]:
        sel = (u >= edge) & (u < edge + part["share"])
        edge += part["share"]
        if "normal" in part:
            out[sel] = rng.normal(*part["normal"], sel.sum())
        else:
            out[sel] = rng.uniform(*part["uniform"], sel.sum())
    out[u >= edge] = 0.0
    return np.clip(out, 0.0, 23.5)


def shards(cfg: dict, seed: int):
    net = roads(cfg, rng_for(seed, 0))
    names = city_names(cfg)
    n_shards = int(cfg["shards"])
    per = int(cfg["trips"]) // n_shards
    if per * n_shards != cfg["trips"]:
        raise ValueError("trips must split evenly over shards")
    pools = [np.flatnonzero(net["city"] == c) for c in range(len(names))]
    if any(v.size == 0 for v in pools):
        raise ValueError("a city has no road")
    seg0, seg1 = cfg["segments_per_trip"]
    w = np.array([cfg["roads"]["city_weights"][c] for c in names])
    nbr = [np.array([names.index(b) for b in cfg["neighbors"][a]])
           for a in names]
    gap0, gap1 = cfg["point_gap_s"]
    out = []
    for s in range(n_shards):
        rng = rng_for(seed, 1 + s)
        k = rng.integers(seg0, seg1 + 1, size=per)
        a = rng.choice(len(names), size=per, p=w / w.sum())
        inter = rng.uniform(0, 1, per) < cfg["inter_city_share"]
        b = a.copy()
        pick = rng.integers(0, 1 << 30, per)
        for c in range(len(names)):
            m = inter & (a == c)
            b[m] = nbr[c][pick[m] % nbr[c].size]
        k1 = np.where(b == a, k, np.maximum(1, k // 2))
        n_seg = int(k.sum())
        trip_of_seg = np.repeat(np.arange(per), k)
        pos_in_trip = np.arange(n_seg) - np.repeat(np.cumsum(k) - k, k)
        seg_city = np.where(pos_in_trip < k1[trip_of_seg], a[trip_of_seg],
                            b[trip_of_seg])
        seg_road = np.empty(n_seg, dtype=np.int64)
        draw = rng.integers(0, 1 << 30, n_seg)
        for c, pool in enumerate(pools):
            m = seg_city == c
            seg_road[m] = pool[draw[m] % pool.size]
        seg_len = net["npts"][seg_road]
        # points: each segment's road polyline, in order
        starts = net["poly_splits"][seg_road]
        flat = np.repeat(starts - np.concatenate([[0], np.cumsum(seg_len)[:-1]]),
                         seg_len) + np.arange(int(seg_len.sum()))
        lat = net["poly_lat"][flat]
        lng = net["poly_lng"][flat]
        pts_per_trip = np.bincount(trip_of_seg, weights=seg_len,
                                   minlength=per).astype(np.int64)
        splits = np.zeros(per + 1, dtype=np.int64)
        np.cumsum(pts_per_trip, out=splits[1:])
        day = rng.integers(0, 7, per).astype(np.int64)
        hour = _start_hours(cfg, rng, per)
        gaps = rng.uniform(gap0, gap1, int(splits[-1]))
        # t of a trip's first point is its start; each later point adds
        # the gap drawn after the previous one
        first = splits[:-1]
        cum = np.cumsum(gaps) - gaps
        cum -= np.repeat(cum[first], pts_per_trip)
        t = np.repeat(day * 86400.0 + hour * 3600.0, pts_per_trip) + cum
        last = splits[1:] - 1
        out.append({
            "id": (s + n_shards * np.arange(per, dtype=np.int64), None),
            "vehicle": (rng.integers(0, int(cfg["vehicles"]),
                                     per).astype(np.int64), None),
            "day": (day, None),
            "start_hour": (hour.astype(np.int64), None),
            "track.lat": (lat, splits),
            "track.lng": (lng, splits.copy()),
            "track.t": (t, splits.copy()),
            "duration_s": (t[last] - t[first], None),
        })
    return out
