"""Q1-Q5: coefficient of variation of speed per road, as the paper's §6
writes it: index-backed selection on region, hour, weekday and month,
then a grouped average, standard deviation and count."""
from __future__ import annotations

import numpy as np

from repro.core import BETWEEN, IN, P, fdb, group, proto

from . import region

__all__ = ["flow", "answer"]


def flow(spec: dict, cfg: dict):
    pred = IN(P.loc, region(spec["cities"], cfg["cities"]))
    for col in ("hour", "dow", "month"):
        lo, hi = spec[col]
        pred = pred & BETWEEN(getattr(P, col), lo, hi)
    return (fdb(cfg["table"]).find(pred)
            .aggregate(group(P.road_id).avg(mean_speed=P.speed)
                       .std_dev(std_speed=P.speed).count("n"))
            .map(lambda p: proto(road_id=p.road_id, n=p.n,
                                 cov=p.std_speed / p.mean_speed)))


def answer(result) -> dict:
    b = result.batch
    return {"road_id": np.asarray(b["road_id"].values),
            "n": np.asarray(b["n"].values),
            "cov": np.asarray(b["cov"].values, dtype=np.float64)}
