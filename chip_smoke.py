#!/usr/bin/env python3
"""Chip smoke test: the query engine's main path, end to end, on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py                          # one chip
    python3 chip_smoke.py --four-chips --scale 10  # P=4 vs P=1 on 4 chips

With no option it builds the paper's §6 world (roads, speed observations,
trips) with ``benchmarks.queries.build_catalog`` at ``--scale`` (50: about
1 M speed observations, 60 k trips, 30 k roads), generated from
``--seed``, and drives the normal entry points on ``Session(backend=
"jax")`` with the Pallas kernels compiled for the chip:

* Q1–Q5 (speed-variability aggregates), Q6–Q9 (Tesseract trip queries,
  Q8/Q9 ordered) and Q10/Q11 (``at_least`` and ``dwell``), cold and warm;
* 8 concurrent compatible Tesseract queries through a ``QueryServer``,
  which must coalesce into shared multi-query dispatches;
* three appended windows of a ``StreamingFDb``, each answered fresh;
* a few ``to_dataset()`` → ``MLPRegressor`` training steps.

Every answer is compared with the numpy backend on the same data:
selections byte for byte, aggregates within a stated float32 tolerance.
A fused wave that declines to the per-primitive path, or a coalesced
group that falls back to solo queries, is an error.  ``--four-chips``
runs only the partition phase: Q1, Q6 and Q10 at P=4 and at P=1 in one
process, compared with each other.

The script exits non-zero without a TPU, when the kernels are not the
compiled Pallas ones, or when any phase fails.  Its last line on success
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: Aggregate tolerance.  On the chip ``segment_agg`` sums value and
#: value² in float32 (unit roundoff 2**-24) where numpy sums float64, and
#: std = sqrt(E[v²] − mean²) cancels most of those bits when a group's
#: spread is small against its mean (speeds ≈ 48 ± 9): with n rows the
#: error in std is about sqrt(n · 2**-24) · mean.  For the few hundred
#: rows per road group here that stays under 1e-3 of the mean, so the
#: coefficient of variation (std / mean) must agree within this bound.
COV_ATOL = 2e-3
COV_RTOL = 1e-3

#: per-primitive kernel launches: any of these in a query's launch counts
#: means a wave declined the fused path
PRIMITIVE_OPS = ("bitmap_intersect", "bitmap_intersect_batched", "compact",
                 "compact_batched", "segment_agg", "refine_tracks",
                 "refine_tracks_batched", "refine_tracks_multi")


class SmokeFailure(AssertionError):
    """A check of the smoke test did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    """The device list, or exit non-zero naming what JAX found instead."""
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" or len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s); JAX found "
                 f"{len(devs)} {platform!r} device(s) "
                 f"({devs[0].device_kind})")
    from repro.kernels import ops
    impl = ops.default_impl()
    if impl != "pallas":
        sys.exit(f"chip_smoke: kernel impl on the TPU is {impl!r}, not "
                 f"'pallas' (is REPRO_KERNEL_IMPL set?)")
    return devs


class CompileClock:
    """Seconds JAX spent obtaining executables (compiling, or loading
    them from the persistent cache) and the cache's hits and misses."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phases:
    """Runs named phases; a failing phase is reported and the run goes on
    to the next one, but the script then exits non-zero."""

    def __init__(self):
        self.results = []

    def run(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
            ok = True
        except Exception:
            traceback.print_exc()
            out, ok = None, False
        secs = time.perf_counter() - t0
        self.results.append((name, ok))
        log(f"phase {name}: {'PASS' if ok else 'FAIL'} ({secs:.1f} s)")
        return out

    @property
    def ok(self) -> bool:
        return all(ok for _, ok in self.results)


# --------------------------------------------------------------- answers
def selection_identical(ref, got) -> bool:
    from benchmarks.bench_backends import batches_identical
    return batches_identical(ref.batch, got.batch)


def aggregates_close(ref, got):
    """Group keys and counts exact, cov within the float32 tolerance;
    returns the largest |Δcov|."""
    a, b = ref.batch, got.batch
    check(a.n == b.n, f"group count {b.n} != oracle {a.n}")
    for p in ("road_id", "n"):
        check(np.array_equal(a[p].values, b[p].values),
              f"aggregate column {p} differs from the oracle")
    ca, cb = a["cov"].values, b["cov"].values
    diff = np.abs(ca - cb)
    check(bool(np.all(diff <= COV_ATOL + COV_RTOL * np.abs(ca))),
          f"cov outside tolerance: max |Δ| {float(diff.max())}")
    return float(diff.max()) if diff.size else 0.0


def fused_launches(session, flow, kind="sel"):
    """(expected fused dispatches, merge combines) for ``flow``: only an
    aggregate merges partitions, a selection concatenates them."""
    from repro.core.planner import plan_flow
    from repro.exec.batched import resolve_partition_plan
    engine = session.engine
    plan = plan_flow(flow, engine.catalog)
    pplan = resolve_partition_plan(engine.partitions, engine.backend, plan,
                                   None, None)
    merges = pplan.merge_combines() if kind == "agg" else 0
    return pplan.wave_dispatches(engine.wave), merges


def check_fused(counts, want_waves, want_merges, op="run_wave_fused"):
    """No per-primitive fallback, and exactly the fused dispatch count."""
    fallback = {k: v for k, v in counts.items() if k in PRIMITIVE_OPS}
    check(not fallback, f"a wave declined the fused path: {counts}")
    check(counts.get(op, 0) == want_waves,
          f"{op} dispatches {counts.get(op, 0)} != {want_waves}: {counts}")
    check(counts.get("merge_partials", 0) == want_merges,
          f"merge_partials {counts.get('merge_partials', 0)} != "
          f"{want_merges}")


def timed_collect(session, flow):
    """(result, wall ms, kernel launches) of one ``Session.run``; the
    result is a host batch, so the clock stops after the device work."""
    from repro.kernels import ops
    ops.reset_launch_counts()
    t0 = time.perf_counter()
    res = session.run(flow)
    ms = (time.perf_counter() - t0) * 1e3
    return res, ms, dict(ops.launch_counts())


def query_flows():
    """name → (flow, kind): kind 'agg' compares within tolerance, 'sel'
    byte for byte."""
    from benchmarks.bench_analytics import analytics_tesseracts
    from benchmarks.queries import (ORDERED_TRIP_QUERIES, QUERIES,
                                    TRIP_QUERIES, q_tesseract,
                                    q_variability)
    from repro.core import fdb, proto
    flows = {}
    for name, (cities, months) in QUERIES.items():
        flows[name] = (q_variability(cities, months), "agg")
    for name, legs in TRIP_QUERIES.items():
        flows[name] = (q_tesseract(legs), "sel")
    for name, legs in ORDERED_TRIP_QUERIES.items():
        flows[name] = (q_tesseract(legs, ordered=True), "sel")
    for name, tess in analytics_tesseracts().items():
        flows[name] = (fdb("Trips").tesseract(tess)
                       .map(lambda p: proto(id=p.id)), "sel")
    return flows


# ---------------------------------------------------------------- phases
def phase_build(scale: float, seed: int):
    from benchmarks.queries import build_catalog
    t0 = time.perf_counter()
    cat = build_catalog(scale=scale, seed=seed)
    log(f"world: scale={scale} seed={seed} built in "
        f"{time.perf_counter() - t0:.1f} s")
    for name in cat.names():
        db = cat.get(name)
        log(f"  {name}: {sum(s.n for s in db.shards)} docs in "
            f"{db.num_shards} shards")
    return cat


def phase_prime(cat, jx, dev):
    backend = jx.engine.backend
    t0 = time.perf_counter()
    for name in cat.names():
        backend.prime_fdb(cat.get(name))
    stats = dev.memory_stats() or {}
    log(f"device-resident: {backend.device_cache.nbytes()} bytes in "
        f"{len(backend.device_cache)} buffers (DeviceCache.nbytes); "
        f"device bytes_in_use={stats.get('bytes_in_use')} "
        f"(primed in {time.perf_counter() - t0:.1f} s)")


def phase_query(name, flow, kind, jx, npx):
    want_waves, want_merges = fused_launches(jx, flow, kind)
    res, cold_ms, counts = timed_collect(jx, flow)
    check_fused(counts, want_waves, want_merges)
    res, warm_ms, counts = timed_collect(jx, flow)
    check_fused(counts, want_waves, want_merges)
    ref = npx.run(flow)
    if kind == "agg":
        err = aggregates_close(ref, res)
        verdict = f"groups={res.batch.n} max|Δcov|={err:.3g}"
    else:
        check(selection_identical(ref, res),
              "selection differs from the numpy oracle")
        verdict = f"rows={res.batch.n} byte-identical"
    log(f"  {name}: cold_ms={cold_ms:.1f} warm_ms={warm_ms:.1f} "
        f"fused_dispatches={want_waves} {verdict}")


def phase_serve(jx, npx):
    from benchmarks.bench_serve import _pool
    from repro.kernels import ops
    flows = _pool(8)
    srv = jx.serve(start=False, cache=False)
    try:
        want_waves, _ = fused_launches(jx, flows[0])
        for rnd in ("cold", "warm"):
            ops.reset_launch_counts()
            t0 = time.perf_counter()
            futs = [srv.submit(f) for f in flows]
            srv.run_pending()
            results = [f.result(600) for f in futs]
            ms = (time.perf_counter() - t0) * 1e3
            counts = dict(ops.launch_counts())
            check_fused(counts, want_waves, 0, op="run_wave_fused_multi")
            check(counts.get("run_wave_fused", 0) == 0,
                  f"a query ran outside the coalesced dispatch: {counts}")
            log(f"  serve {rnd}: 8 queries in {ms:.1f} ms, "
                f"run_wave_fused_multi={want_waves}")
        st = srv.stats()
        check(st["coalesced_queries"] == 16 and st["fallback_queries"] == 0,
              f"queries did not coalesce: {st}")
        for f, res in zip(flows, results):
            check(selection_identical(npx.run(f), res),
                  "a served query differs from the numpy oracle")
        log(f"  served {st['served']} queries, all coalesced, "
            f"byte-identical to the oracle")
    finally:
        srv.close()


def phase_streaming(seed: int):
    from repro.core import Session, fdb
    from repro.data.synthetic import CITIES, city_region, generate_world
    from repro.exec import Catalog
    from repro.fdb.streaming import StreamingFDb
    from repro.tess import Tesseract

    day = 86400.0
    tess = (Tesseract(city_region("SF"), 2 * day + 6 * 3600,
                      2 * day + 12 * 3600)
            .also(city_region("Berkeley"), 2 * day + 6 * 3600,
                  2 * day + 14 * 3600))
    flow = fdb("Trips").tesseract(tess)

    def probe_trip(trip_id):
        """A trip the commute query must select: SF → Berkeley, day 2."""
        def center(city):
            lat0, lng0, dlat, dlng = CITIES[city]
            return lat0 + dlat / 2, lng0 + dlng / 2
        pts = [center("SF")] * 3 + [center("Berkeley")] * 3
        t0 = 2 * day + 7 * 3600
        return {"id": trip_id, "vehicle": 0, "day": 2, "start_hour": 7,
                "track": {"lat": [p[0] for p in pts],
                          "lng": [p[1] for p in pts],
                          "t": [t0 + 300.0 * k for k in range(6)]},
                "duration_s": 1500.0}

    world = generate_world(scale=5.0, seed=seed)
    trips = sorted(world["trips"],
                   key=lambda r: r["track"]["t"][0] if r["track"]["t"]
                   else 0.0)
    live = StreamingFDb("Trips", world["trips_schema"],
                        flush_threshold=1024, compact_threshold=0)
    cat = Catalog()
    cat.register(live)
    npx = Session(catalog=cat, backend="numpy")
    windows = np.array_split(np.arange(len(trips)), 3)
    next_id = max(r["id"] for r in trips) + 1
    with Session(catalog=cat, backend="jax").serve(start=False) as srv:
        for k, idx in enumerate(windows):
            live.extend([trips[i] for i in idx] + [probe_trip(next_id + k)])
            live.flush()
            t0 = time.perf_counter()
            fut = srv.submit(flow)
            srv.run_pending()
            res = fut.result(600)
            ms = (time.perf_counter() - t0) * 1e3
            ids = set(res.batch["id"].values.tolist())
            check(next_id + k in ids,
                  f"window {k}: the appended probe trip is not answered")
            check(selection_identical(npx.run(flow), res),
                  f"window {k}: answer differs from the numpy oracle")
            log(f"  window {k}: +{len(idx) + 1} trips, generation "
                f"{live.stats()['generation']}, {res.batch.n} trips "
                f"selected in {ms:.1f} ms, fresh and byte-identical")


def phase_training(jx, npx):
    from repro.core import P, BETWEEN, fdb
    roads = jx.run(fdb("Roads")).to_dict("id")

    def dataset(session):
        return (fdb("SpeedObservations").find(BETWEEN(P.month, 1, 4))
                .to_dataset(features={"hour": P.hour * 1.0,
                                      "dow": P.dow * 1.0,
                                      "sl": roads[P.road_id].speed_limit},
                            target=P.speed, engine=session.engine))
    ds, ref = dataset(jx), dataset(npx)
    check(np.array_equal(ds.features, ref.features)
          and np.array_equal(ds.targets, ref.targets),
          "training rows differ from the numpy oracle")
    t0 = time.perf_counter()
    _, losses = ds.fit(steps=30, lr=2e-3, batch=256)
    losses = np.asarray(losses, np.float64)
    check(bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0],
          f"training did not reduce the loss: {losses[0]} → {losses[-1]}")
    log(f"  trained on {len(ds)} rows: loss {losses[0]:.2f} → "
        f"{losses[-1]:.2f} in 30 steps ({time.perf_counter() - t0:.1f} s)")


def phase_partitions(cat, flows):
    """Q1, Q6 and Q10 at P=4 against P=1 in one process."""
    from repro.core import Session
    from repro.exec import ExecConfig
    sessions = {p: Session(catalog=cat,
                           config=ExecConfig(backend="jax", partitions=p))
                for p in (1, 4)}
    for name in ("Q1", "Q6", "Q10"):
        flow, kind = flows[name]
        out = {}
        for p, ses in sessions.items():
            want_waves, want_merges = fused_launches(ses, flow, kind)
            res, ms, counts = timed_collect(ses, flow)
            check_fused(counts, want_waves, want_merges)
            out[p] = res
            log(f"  {name} P={p}: {ms:.1f} ms, fused={want_waves} "
                f"merge={want_merges}")
        if kind == "agg":
            err = aggregates_close(out[1], out[4])
            log(f"  {name}: P=4 ≡ P=1 within tolerance "
                f"(max|Δcov|={err:.3g})")
        else:
            check(selection_identical(out[1], out[4]),
                  f"{name}: P=4 selection differs from P=1")
            log(f"  {name}: P=4 ≡ P=1 byte-identical "
                f"({out[4].batch.n} rows)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the P=4 vs P=1 partition phase")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devs = require_tpu(4 if args.four_chips else 1)
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}")
    clock = CompileClock()
    phases = Phases()
    cat = phases.run("build", phase_build, args.scale, args.seed)
    if cat is None:
        return 1
    flows = query_flows()
    if args.four_chips:
        check(len(devs) == 4, f"--four-chips needs 4 devices: {devs}")
        phases.run("partitions", phase_partitions, cat, flows)
    else:
        from repro.core import Session
        jx = Session(catalog=cat, backend="jax")
        npx = Session(catalog=cat, backend="numpy")
        phases.run("prime", phase_prime, cat, jx, dev)
        for name, (flow, kind) in flows.items():
            phases.run(f"query {name}", phase_query, name, flow, kind, jx,
                       npx)
        phases.run("serve", phase_serve, jx, npx)
        phases.run("streaming", phase_streaming, args.seed)
        phases.run("training", phase_training, jx, npx)
    log(f"compile: {clock.seconds:.1f} s obtaining executables, "
        f"cache hits={clock.hits} misses={clock.misses} ({cache_dir})")
    failed = [n for n, ok in phases.results if not ok]
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
