"""Grouped aggregation: each selected row's group key (int32) and value
(float32) read once, and per result group its count, sum and sum of
squares (float32 each) written once."""
from __future__ import annotations

import numpy as np

from ..reference.variability import selected

EVENTS = ("segment_agg",)


def need_bytes(tables: dict, spec: dict, cfg: dict) -> float:
    m = selected(tables, spec, cfg)
    groups = np.unique(tables["road_id"][m]).size
    return float(m.sum()) * (4 + 4) + groups * 3 * 4
