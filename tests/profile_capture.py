"""The program's host spans from a CPU profiler trace, for tests.

``with captured_spans(directory) as spans:`` runs its body under
``jax.profiler`` and fills ``spans`` when the body ends: one
:class:`Span` per ``warpflow.*`` host event, in order of start.
"""
import contextlib
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterator, List

import jax

from repro.spans import PREFIX


@dataclass
class Span:
    name: str                 # without the ``warpflow.`` prefix
    start: float              # seconds on the profiler's clock
    end: float
    meta: Dict[str, object] = field(default_factory=dict)

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def read_spans(directory: str) -> List[Span]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name[len(PREFIX):],
                                    ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: s.start)


@contextlib.contextmanager
def captured_spans(directory) -> Iterator[List[Span]]:
    spans: List[Span] = []
    jax.profiler.start_trace(str(directory))
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    spans.extend(read_spans(str(directory)))
