"""The program's own spans in a traced run, and the device idle time
they explain.

The program opens ``warpflow.<name>`` host spans (``src/repro/spans.py``)
on the profiler's clock, each query's carrying its number as ``query``
and a server batch's its size as ``n``.  The harness reduces a trace to
device operations and its own ``chipbench.*`` spans, and hands a metric
reader that reduction, not the file; so :func:`from_ctx` finds the file
again, in the ``chipbench-trace-*`` directory of the temporary directory
that ``run.py`` writes it to, by the slice's bounds, and reads the
program's spans from it.  A trace with no such span (a program that
opens none) reads as ``None``, and every reader then reports nothing.

Each function works on plain lists, so that it can be checked on
hand-built ones: spans as ``(name, start_s, end_s, meta)`` with the
``warpflow.`` prefix taken off, device operations as ``chipbench.trace``
has them.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import WINDOW_SPAN, idle_gaps, span_at, union

__all__ = ["PREFIX", "ROOTS", "TAIL", "read_spans", "from_ctx",
           "span_seconds", "device_gaps", "idle_attributed",
           "queue_waits", "per_query_ms", "describe"]

#: the prefix of every span the program opens
PREFIX = "warpflow."

#: spans that hold a whole query or batch: a gap under one of these alone
#: is not explained by any layer
ROOTS = ("query", "serve.batch")

#: the host tail of a query: from the wave's outputs to the answer
TAIL = ("finalize", "gather", "merge", "mix")

#: the host event of every executable JAX obtains, compiled or loaded
#: from the persistent cache (the profiler's Python tracer names it)
COMPILE_EVENT = " compile_or_get_cached"

Span = Tuple[str, float, float, Dict[str, object]]


def read_spans(path: str):
    """``(spans, window, compiles)`` of one ``.xplane.pb``: the program's
    spans on every host thread, the ``[lo, hi]`` of the harness's window
    span (``None`` without one), and the ``(start, end)`` of every
    executable obtained."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Span] = []
    compiles: List[Tuple[float, float]] = []
    window = None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name[len(PREFIX):], s, e,
                                  dict(ev.stats)))
                elif ev.name == WINDOW_SPAN:
                    window = (s, e)
                elif ev.name.endswith(COMPILE_EVENT):
                    compiles.append((s, e))
    spans.sort(key=lambda sp: sp[1])
    return spans, window, sorted(compiles)


@functools.lru_cache(maxsize=1)
def _find(lo: float, hi: float):
    pattern = os.path.join(tempfile.gettempdir(), "chipbench-trace-*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        spans, window, compiles = read_spans(path)
        if window == (lo, hi):
            return (spans, compiles) if spans else None
    return None


def from_ctx(ctx):
    """``(spans, compiles)`` of the run's trace, or ``None`` where the run
    was not traced or the program opened no span."""
    if ctx.trace is None:
        return None
    return _find(ctx.trace["lo"], ctx.trace["hi"])


def span_seconds(spans: Sequence[Span], names: Sequence[str], lo: float,
                 hi: float) -> float:
    """Host time inside ``[lo, hi]`` of the spans named in ``names``,
    summed over threads: two spans that overlap both count."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for name, s, e, _ in spans if name in names)


def per_query_ms(ctx, names: Sequence[str]) -> Optional[float]:
    """Milliseconds of the ``names`` spans in the traced slice per query
    answered in it, or ``None`` where there is nothing to read."""
    got = from_ctx(ctx)
    if got is None or not ctx.traced:
        return None
    secs = span_seconds(got[0], names, ctx.trace["lo"], ctx.trace["hi"])
    return 1e3 * secs / len(ctx.traced)


def device_gaps(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle stretches of the first device inside ``[lo, hi]``, as
    ``chipbench.trace.reduce_trace`` finds them."""
    per_dev: Dict[object, list] = {}
    for _name, s, e, labels in ops:
        if e > lo and s < hi:
            per_dev.setdefault(labels.get("device", 0), []).append((s, e))
    first = union(per_dev[min(per_dev)]) if per_dev else []
    return idle_gaps(first, lo, hi)


def idle_attributed(ops, spans: Sequence[Span], lo: float, hi: float
                    ) -> Tuple[float, float]:
    """``(idle, explained)``: the device's idle seconds inside
    ``[lo, hi]``, and how many of them lie under a layer span (any span
    but the :data:`ROOTS`)."""
    layer = union((s, e) for name, s, e, _ in spans if name not in ROOTS)
    idle = explained = 0.0
    for gs, ge in device_gaps(ops, lo, hi):
        idle += ge - gs
        explained += sum(max(0.0, min(e, ge) - max(s, gs))
                         for s, e in layer)
    return idle, explained


def queue_waits(spans: Sequence[Span], lo: float, hi: float) -> List[float]:
    """Seconds each server query waited: from its ``submit`` span to the
    start of the ``serve.batch`` span that planned it, for the batches
    that start inside ``[lo, hi]``.  A query is counted once, at the
    batch that planned it first."""
    submitted = {m["query"]: s for name, s, _e, m in spans
                 if name == "submit" and "query" in m}
    batches = [(s, e) for name, s, e, _ in spans
               if name == "serve.batch" and lo <= s <= hi]
    waits, seen = [], set()
    for name, s, _e, m in spans:
        q = m.get("query")
        if name != "plan" or q in seen or q not in submitted:
            continue
        for bs, be in batches:
            if bs <= s <= be:
                waits.append(bs - submitted[q])
                seen.add(q)
                break
    return waits


def describe(ops, spans: Sequence[Span], compiles, lo: float, hi: float,
             top: int = 10) -> dict:
    """The slice by span: host seconds per span name, the device's idle
    seconds by the innermost span open in the middle of each gap (on any
    thread: the one that began last), the longest gaps so named, and the
    span each executable obtained in the slice fell under."""
    def tally(pairs):
        out: Dict[str, float] = {}
        for name, secs in pairs:
            out[name] = out.get(name, 0.0) + secs
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    timed = [(name, s, e) for name, s, e, _ in spans]
    gaps = sorted(device_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])
    named = [[span_at(timed, (s + e) / 2), e - s] for s, e in gaps]
    return {
        "span_s": tally((name, max(0.0, min(e, hi) - max(s, lo)))
                        for name, s, e, _ in spans),
        "idle_s": tally(named),
        "idle_gaps": named[:top],
        "compiles": [[span_at(timed, s), e - s]
                     for s, e in compiles if lo <= s <= hi],
    }
