"""Coefficient of variation of speed per road (the paper's Q1-Q5).

Rows in the cities' region, hour, day of week and month ranges (all
inclusive) are grouped by road; per road the row count, the mean speed,
the population standard deviation and their ratio.  The reference sums
in float64 with a two-pass standard deviation.
"""
from __future__ import annotations

import numpy as np

from .geo import in_cities

__all__ = ["expected", "control", "compare", "selected"]


def selected(tables: dict, spec: dict, cfg: dict) -> np.ndarray:
    """Row mask of the query's selection."""
    m = in_cities(tables["loc.lat"], tables["loc.lng"], spec["cities"],
                  cfg["cities"])
    for col in ("hour", "dow", "month"):
        lo, hi = spec[col]
        v = tables[col]
        m &= (v >= lo) & (v <= hi)
    return m


def _groups(tables, spec, cfg):
    m = selected(tables, spec, cfg)
    road = tables["road_id"][m]
    keys, inv = np.unique(road, return_inverse=True)
    return keys, inv, tables["speed"][m]


def expected(tables: dict, spec: dict, cfg: dict) -> dict:
    keys, inv, v = _groups(tables, spec, cfg)
    n = np.bincount(inv, minlength=keys.size)
    mean = np.bincount(inv, weights=v, minlength=keys.size) / n
    dev = v - mean[inv]
    std = np.sqrt(np.bincount(inv, weights=dev * dev,
                              minlength=keys.size) / n)
    return {"road_id": keys, "n": n, "cov": std / mean}


def control(tables: dict, spec: dict, cfg: dict) -> dict:
    """The reference one precision step down: values, sums and the
    finishing arithmetic in bfloat16, as a float32 program's next cheaper
    step would run them."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    keys, inv, v = _groups(tables, spec, cfg)
    v = v.astype(bf16)
    s = np.zeros(keys.size, dtype=bf16)
    s2 = np.zeros(keys.size, dtype=bf16)
    np.add.at(s, inv, v)
    np.add.at(s2, inv, v * v)
    n = np.bincount(inv, minlength=keys.size)
    nb = n.astype(bf16)
    mean = s / nb
    var = np.maximum(s2 / nb - mean * mean, bf16(0))
    cov = (np.sqrt(var.astype(np.float32)).astype(bf16) / mean)
    return {"road_id": keys, "n": n, "cov": cov.astype(np.float64)}


def compare(got: dict, want: dict) -> dict:
    """``groups_mismatched``: roads in one answer and not the other, plus
    roads whose row counts differ; ``cov_gap_max``: the widest gap in the
    coefficient of variation over the roads both hold."""
    gk = np.asarray(got["road_id"], dtype=np.int64)
    wk = np.asarray(want["road_id"], dtype=np.int64)
    dup = gk.size - np.unique(gk).size
    common, gi, wi = np.intersect1d(gk, wk, assume_unique=False,
                                    return_indices=True)
    missing = (gk.size - dup - common.size) + (wk.size - common.size)
    n_off = int(np.sum(np.asarray(got["n"])[gi] != np.asarray(want["n"])[wi]))
    gap = np.abs(np.asarray(got["cov"], dtype=np.float64)[gi]
                 - np.asarray(want["cov"], dtype=np.float64)[wi])
    gap_max = float(np.max(gap)) if gap.size else 0.0
    if np.isnan(gap).any():
        gap_max = float("inf")
    return {"groups_mismatched": int(dup + missing + n_off),
            "cov_gap_max": gap_max}
