"""Road network of the paper's §6 world, vectorised.

A numpy copy of the road distributions of ``repro.data.synthetic``: each
road lies in a city drawn by the configuration's city weights, at a
uniform point of the city's box, with a polyline of 2-5 points that
random-walks from it, a base speed, a speed limit and a variability.
"""
from __future__ import annotations

import numpy as np

__all__ = ["city_names", "roads", "rng_for"]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def city_names(cfg: dict):
    return list(cfg["cities"])


def roads(cfg: dict, rng: np.random.Generator) -> dict:
    """Arrays of the road network: ``city`` (index into ``city_names``),
    ``lat``/``lng``, the flat polyline ``poly_lat``/``poly_lng`` with
    ``poly_splits``, ``npts``, ``base_speed`` and ``variability``."""
    rc = cfg["roads"]
    names = city_names(cfg)
    n = int(rc["count"])
    w = np.array([rc["city_weights"][c] for c in names], dtype=np.float64)
    city = rng.choice(len(names), size=n, p=w / w.sum())
    box = np.array([[cfg["cities"][c][k] for k in ("lat0", "lng0", "dlat",
                                                    "dlng")]
                    for c in names])
    lat = box[city, 0] + rng.uniform(0, 1, n) * box[city, 2]
    lng = box[city, 1] + rng.uniform(0, 1, n) * box[city, 3]
    lo, hi = rc["polyline_points"]
    npts = rng.integers(lo, hi + 1, size=n)
    s0, s1 = rc["polyline_step_deg"]
    steps = rng.uniform(s0, s1, size=(n, hi - 1, 2)) \
        * rng.choice([-1.0, 1.0], size=(n, hi - 1, 2))
    walk = np.concatenate([np.zeros((n, 1, 2)), np.cumsum(steps, axis=1)],
                          axis=1)
    walk += np.stack([lat, lng], axis=1)[:, None, :]
    keep = np.arange(hi)[None, :] < npts[:, None]
    splits = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(npts, out=splits[1:])
    b0, b1 = rc["base_speed_kmh"]
    v0, v1 = rc["variability_kmh"]
    return {"city": city, "lat": lat, "lng": lng, "npts": npts,
            "poly_lat": walk[..., 0][keep], "poly_lng": walk[..., 1][keep],
            "poly_splits": splits,
            "base_speed": rng.uniform(b0, b1, n),
            "variability": rng.uniform(v0, v1, n)}
