"""Q6-Q11: Tesseract trip queries, legs of (cities, hour window) on one
day of the synthetic week, optionally ordered, with ``at_least`` counts
and ``dwell_s`` reductions on a leg."""
from __future__ import annotations

import numpy as np

from repro.core import fdb, proto
from repro.tess import Tesseract

from . import region

__all__ = ["flow", "answer", "tesseract"]

DAY_S = 86400.0
HOUR_S = 3600.0


def tesseract(spec: dict, cfg: dict) -> Tesseract:
    tess = None
    base = spec["day"] * DAY_S
    for leg in spec["legs"]:
        area = region(leg["cities"], cfg["cities"])
        t0 = base + leg["hours"][0] * HOUR_S
        t1 = base + leg["hours"][1] * HOUR_S
        if tess is None:
            tess = Tesseract(area, t0, t1)
        elif spec.get("ordered"):
            tess = tess.then(area, t0, t1)
        else:
            tess = tess.also(area, t0, t1)
        if "at_least" in leg:
            tess = tess.at_least(int(leg["at_least"]))
        if "dwell_s" in leg:
            tess = tess.dwell(float(leg["dwell_s"]))
    return tess


def flow(spec: dict, cfg: dict):
    return (fdb(cfg["table"]).tesseract(tesseract(spec, cfg))
            .map(lambda p: proto(id=p.id, day=p.day,
                                 duration_s=p.duration_s)))


def answer(result) -> dict:
    b = result.batch
    ids = np.asarray(b["id"].values)
    order = np.argsort(ids, kind="stable")
    return {"id": ids[order], "day": np.asarray(b["day"].values)[order],
            "duration_s": np.asarray(b["duration_s"].values)[order]}
