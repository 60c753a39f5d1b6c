"""Region membership as the configuration states it.

A query region is the union of city boxes covered by level-6 area-tree
cells, the cells of a 30-bit-per-axis integer spherical-Mercator grid
that are 4096 units on a side.  A point is in the region when its
level-6 cell meets a city box, box corners included: the box's corners
are projected and the cell index of point and corners compared.
"""
from __future__ import annotations

import numpy as np

__all__ = ["mercator_xy", "in_cities", "LEVEL6_SHIFT"]

GRID = float(1 << 30)
MAX_LAT = 85.05112877980659
#: a level-l cell is 2**(30 - 3 l) units on a side; level 6: 2**12
LEVEL6_SHIFT = 12


def mercator_xy(lat, lng, dtype=np.float64):
    """Degrees -> integer web-Mercator cell ``(ix, iy)`` on the 2**30 grid
    (``iy`` grows southwards), computed in ``dtype``."""
    f = np.dtype(dtype).type
    lat = np.clip(np.asarray(lat, dtype=dtype), f(-MAX_LAT), f(MAX_LAT))
    lng = np.asarray(lng, dtype=dtype)
    x = (lng / f(360.0) + f(0.5)) % f(1.0)
    r = np.radians(lat)
    y = f(0.5) - np.log(np.tan(r) + f(1.0) / np.cos(r)) / f(2.0 * np.pi)
    ix = np.minimum((x * f(GRID)).astype(np.uint64), np.uint64(GRID - 1))
    iy = np.minimum(np.maximum(y, f(0.0)) * f(GRID),
                    f(GRID - 1)).astype(np.uint64)
    return ix.astype(np.int64), iy.astype(np.int64)


def in_cities(lat, lng, cities, boxes, dtype=np.float64) -> np.ndarray:
    """Points whose level-6 cell meets any of ``cities``' boxes, with
    every coordinate in ``dtype``.  ``boxes`` maps a city to ``{"lat0",
    "lng0", "dlat", "dlng"}``."""
    cx, cy = mercator_xy(lat, lng, dtype)
    cx >>= LEVEL6_SHIFT
    cy >>= LEVEL6_SHIFT
    out = np.zeros(cx.shape, dtype=bool)
    for c in cities:
        b = boxes[c]
        x, y = mercator_xy([b["lat0"], b["lat0"] + b["dlat"]],
                           [b["lng0"], b["lng0"] + b["dlng"]], dtype)
        x0, x1 = sorted(int(v) >> LEVEL6_SHIFT for v in x)
        y0, y1 = sorted(int(v) >> LEVEL6_SHIFT for v in y)
        out |= (cx >= x0) & (cx <= x1) & (cy >= y0) & (cy <= y1)
    return out
