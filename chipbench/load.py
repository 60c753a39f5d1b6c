"""The load generator: one closed loop for every traffic file.

A traffic file (``chipbench/traffic/<mix>.json``) gives ``queries``, a
number of ``clients`` and the ``entry`` they call:

* ``session`` -- each client calls ``Session.run`` and waits for it;
* ``server`` -- one live ``QueryServer`` (result cache off) serves every
  client; each submits and waits for its future.

Each client sends its next query when its last answer returns: client c's
k-th query is ``queries[(seed + c + clients * k) mod len(queries)]``, so
every seed sends the same queries in another order.  A query is timed
from its call to its answer in host memory.  No client starts a query
after the window closes, and every query started is waited for, a minute
past the close at most.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, List, Optional

__all__ = ["Record", "Closed", "LATE_S"]

#: how long past the window's close an answer is waited for
LATE_S = 60.0


@dataclass
class Record:
    query: int                       # index into the loop's ``distinct()``
    start: float                     # perf_counter seconds at the call
    end: Optional[float] = None
    result: Any = None               # the program's result, until read
    answer: Any = None
    error: Optional[str] = None
    traced: bool = False


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class Closed:
    def __init__(self, traffic: dict, seed: int):
        self.specs = list(traffic["queries"])
        self.clients = int(traffic["clients"])
        self.entry = traffic["entry"]
        if self.entry not in ("session", "server"):
            raise ValueError(f"unknown entry {self.entry!r}")
        self.offset = int(seed) % len(self.specs)
        self.server = None

    def distinct(self) -> List[dict]:
        return self.specs

    def trace_after(self) -> int:
        """Queries in the traced slice: one pass over the mix."""
        return len(self.specs)

    def _call(self, session):
        if self.entry == "session":
            return lambda flow, deadline: session.run(flow)
        if self.server is None:
            self.server = session.serve(cache=False)
        server = self.server
        return lambda flow, deadline: server.submit(flow).result(
            timeout=None if deadline is None
            else max(deadline - time.perf_counter(), 0.001))

    def _drive(self, call, flows, until: Optional[float], turns: int,
               tracer):
        """Every client's closed loop on a thread of its own, until the
        window closes (``until``) or for ``turns`` queries each."""
        n = len(flows)
        records: List[Record] = []
        lock = threading.Lock()
        deadline = None if until is None else until + LATE_S

        def client(c: int) -> None:
            k = 0
            while (time.perf_counter() < until) if until is not None \
                    else k < turns:
                i = (self.offset + c + self.clients * k) % n
                k += 1
                rec = Record(i, time.perf_counter(), traced=tracer.active)
                with _span("run"):
                    try:
                        rec.result = call(flows[i], deadline)
                    except Exception as e:      # counted as failed
                        rec.error = repr(e)
                    rec.end = time.perf_counter()
                # traced: begun and ended inside the traced slice
                rec.traced = rec.traced and tracer.active
                with lock:
                    records.append(rec)
                    tracer.completed(len(records))

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"chipbench-client-{c}")
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return records

    def warm(self, session, flows, tracer) -> None:
        """The loop itself, until every query of the mix has run once."""
        turns = -(-len(flows) // self.clients)
        for rec in self._drive(self._call(session), flows, None, turns,
                               tracer):
            if rec.error is not None:
                raise RuntimeError(f"warm-up query {rec.query} failed: "
                                   f"{rec.error}")

    def run(self, session, flows, answer, seconds: float, tracer):
        """The window: ``(records, start, end of the last answer)``, each
        record's answer read from its result once every client is done."""
        t0 = time.perf_counter()
        records = self._drive(self._call(session), flows, t0 + seconds, 0,
                              tracer)
        t1 = max((r.end for r in records), default=time.perf_counter())
        for rec in records:
            if rec.error is None:
                try:
                    rec.answer = answer[rec.query](rec.result)
                except Exception as e:          # counted as failed
                    rec.error = repr(e)
            rec.result = None
        return records, t0, t1

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
