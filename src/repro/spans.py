"""Host spans of the query path, on the profiler's own clock.

``span(name, **meta)`` opens ``warpflow.<name>`` as a
``jax.profiler.TraceAnnotation``: while a profiler trace is active the
span lands in the trace's host plane, on the same timeline as the
device's operations, with ``meta`` as event stats; with no trace active
it costs about a microsecond and records nothing.  Whether a trace is
active is the only switch.

A query is numbered where it enters the program (``AdHocEngine.collect``
or ``QueryServer.submit``) and its spans carry that number as
``query=``; a server batch span carries ``n=``, the queries it holds.
Spans open per query or per wave, never per shard or per row.
"""
from __future__ import annotations

import itertools

from jax.profiler import TraceAnnotation

__all__ = ["span", "next_query_id", "PREFIX"]

#: every span the program opens starts with this
PREFIX = "warpflow."

# one numbering for the whole process: a trace covers every engine and
# server in it, so two of them must never hand out the same number
_query_ids = itertools.count(1)


def next_query_id() -> int:
    return next(_query_ids)


def span(name: str, **meta) -> TraceAnnotation:
    """The span ``warpflow.<name>``; ``meta`` entries that are ``None``
    are left out."""
    return TraceAnnotation(PREFIX + name,
                           **{k: v for k, v in meta.items()
                              if v is not None})
