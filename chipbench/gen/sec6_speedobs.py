"""SpeedObservations of the §6 world: one row per speed reading.

The distributions of ``repro.data.synthetic.generate_world``'s
observations, vectorised: each row reads a uniformly drawn road, at an
hour of a day-peaked normal, on a uniform day of week and month.
"""
from __future__ import annotations

import numpy as np

from .world import roads, rng_for

__all__ = ["shards", "rush_factor"]


def rush_factor(hour: np.ndarray) -> np.ndarray:
    """The true speed model's rush-hour multiplier per hour of day."""
    hour = np.asarray(hour)
    out = np.ones(hour.shape, dtype=np.float64)
    am = (hour >= 7) & (hour <= 9)
    out[am] = 0.55 + 0.1 * np.cos(hour[am] - 8)
    out[(hour >= 16) & (hour <= 18)] = 0.6
    out[(hour >= 0) & (hour <= 5)] = 1.15
    return out


def shards(cfg: dict, seed: int):
    net = roads(cfg, rng_for(seed, 0))
    n_roads = net["lat"].size
    n_shards = int(cfg["shards"])
    per = int(cfg["rows"]) // n_shards
    if per * n_shards != cfg["rows"]:
        raise ValueError("rows must split evenly over shards")
    out = []
    for s in range(n_shards):
        rng = rng_for(seed, 1 + s)
        road = rng.integers(0, n_roads, per)
        hour = np.clip(rng.normal(12, 5.5, per), 0, 23).astype(np.int64)
        speed = np.maximum(3.0, net["base_speed"][road] * rush_factor(hour)
                           + rng.normal(0.0, 1.0, per)
                           * net["variability"][road])
        out.append({
            "road_id": (road.astype(np.int64), None),
            "loc.lat": (net["lat"][road] + rng.normal(0, 1e-4, per), None),
            "loc.lng": (net["lng"][road] + rng.normal(0, 1e-4, per), None),
            "hour": (hour, None),
            "dow": (rng.integers(0, 7, per).astype(np.int64), None),
            "month": (rng.integers(1, 7, per).astype(np.int64), None),
            "speed": (speed, None),
            "accuracy_m": (np.abs(rng.normal(8, 6, per)) + 3, None),
        })
    return out
