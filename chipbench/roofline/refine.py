"""Exact track refine: every track point of each candidate trip read once
(its 64-bit cell key and 64-bit time), and one verdict byte per candidate
trip written.  Candidates are the trips the spacetime index hands on."""
from __future__ import annotations

import numpy as np

from ..reference.tesseract import candidates

EVENTS = ("refine_tracks",)


def need_bytes(tables: dict, spec: dict, cfg: dict) -> float:
    cand = candidates(tables, spec, cfg)
    points = np.diff(tables["track.lat/splits"])[cand].sum()
    return float(points) * (8 + 8) + float(cand.sum())
