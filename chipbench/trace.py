"""From a profiler trace to device busy time, idle gaps and stage times.

The reduction works on plain event lists, so that it can be checked on
hand-built ones: device operations as ``(name, start_s, end_s, labels)``
and host spans as ``(name, start_s, end_s)``.  On a TPU a device event's
name is its HLO instruction text; an operation is known by the
instruction's name (``segment_agg.1``, ``fusion.3``), and a Pallas kernel
by the name of its ``pallas_call``.  :func:`read_xplane` turns
the ``.xplane.pb`` file that ``jax.profiler`` writes into those lists.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Iterable, List, Optional, Sequence, Tuple

__all__ = ["union", "busy_seconds", "idle_gaps", "span_at", "reduce_trace",
           "stage_seconds", "op_name", "read_xplane", "WINDOW_SPAN"]

#: the host span that marks the traced slice of the window
WINDOW_SPAN = "chipbench.window"

#: device lines that hold one event per operation executed
_OP_LINES = ("XLA Ops",)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of ``intervals`` (touching ones merge)."""
    out: List[List[float]] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(merged: Sequence[Tuple[float, float]], lo: float,
                 hi: float) -> float:
    """Length of ``merged`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def idle_gaps(merged: Sequence[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    gaps, at = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def span_at(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """Name of the innermost host span open at ``t`` (the one that began
    last), or ``"no span"``."""
    best: Optional[Tuple[str, float, float]] = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or s >= best[1]):
            best = (name, s, e)
    return best[0] if best else "no span"


def reduce_trace(ops, spans, lo: float, hi: float, n_devices: int = 1,
                 top: int = 10) -> dict:
    """Busy and idle time of the device inside ``[lo, hi]``, the
    operations that took most time, and the longest idle gaps, each named
    by the host span open in its middle.  ``ops`` may hold several
    devices' operations (``labels`` then carry ``device``); busy time is
    averaged over ``n_devices``."""
    per_dev = defaultdict(list)
    by_name = defaultdict(float)
    for name, s, e, labels in ops:
        if e <= lo or s >= hi:
            continue
        per_dev[labels.get("device", 0)].append((s, e))
        by_name[name] += min(e, hi) - max(s, lo)
    busy = sum(busy_seconds(union(iv), lo, hi) for iv in per_dev.values())
    busy /= max(1, n_devices)
    first = union(per_dev[min(per_dev)]) if per_dev else []
    gaps = sorted(idle_gaps(first, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy, "window_s": hi - lo,
        "device_ops": sorted(([n, t] for n, t in by_name.items()),
                             key=lambda p: -p[1])[:top],
        "idle_gaps": [[span_at(spans, (s + e) / 2), e - s]
                      for s, e in gaps[:top]],
    }


def stage_seconds(ops, prefixes: Sequence[str], lo: float, hi: float
                  ) -> float:
    """Device time inside ``[lo, hi]`` of the operations whose name starts
    with any of ``prefixes``."""
    total = 0.0
    for name, s, e, _ in ops:
        if e > lo and s < hi and name.startswith(tuple(prefixes)):
            total += min(e, hi) - max(s, lo)
    return total


def op_name(hlo_text: str) -> str:
    """The instruction's own name from a device event's HLO text:
    ``%segment_agg.1 = f32[...] custom-call(...)`` -> ``segment_agg.1``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def read_xplane(directory: str):
    """``(ops, spans, window, devices)`` of the newest trace under
    ``directory``: device operations of every TPU plane, host spans of
    every host thread, the ``[lo, hi]`` of the :data:`WINDOW_SPAN` span
    (``None`` without one), and the number of device planes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    ops, spans, window, devices = [], [], None, 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            dev = plane.name
            for line in plane.lines:
                if line.name not in _OP_LINES:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9, {"device": dev}))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
                    elif ev.name.startswith("chipbench."):
                        spans.append((ev.name[len("chipbench."):], s, e))
    return ops, spans, window, devices
