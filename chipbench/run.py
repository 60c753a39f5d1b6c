#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  The run generates the configuration's
table from ``--seed`` (``chipbench/gen``), loads it with the program's
``FDb.load``, primes it on the device, warms the traffic's query shapes,
then drives the traffic for ``--seconds`` (``chipbench/load.py``).  After
the window it compares every answer with the plain reference
(``chipbench/reference``) and prints, as its last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, last, ``checks``: each compared number
with its limit.  The same numbers end standard error.

It exits non-zero, with no result, without a TPU or with fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: generated tables live here, keyed by (configuration, seed)
DATA_DIR = ROOT / "chipbench" / "data"

#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so only a cell's first run in a checkout compiles, and two checkouts
#: never share one
CACHE_DIR = ROOT / ".jax_cache"

#: JAX keeps a compiled program in the persistent cache only when it took
#: at least this long to compile: 0 keeps every program, so a second run
#: of a seed finds all of them (threshold measured in PERF.md)
MIN_COMPILE_S = 0.0


class NoChip(SystemExit):
    """The run needs chips this machine does not have."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


def require_chip(chips: int):
    """The devices, or exit non-zero naming what JAX found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform!r} device(s)")
    from repro.kernels import ops
    if ops.default_impl() != "pallas":
        raise NoChip(f"chipbench: kernel impl is {ops.default_impl()!r}, "
                     f"not 'pallas' (is REPRO_KERNEL_IMPL set?)")
    return devs[:chips]


class CompileClock:
    """Executables JAX obtained (compiled or loaded from the persistent
    cache), their seconds, and the cache's hits and misses, per phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _bump(self, key, by=1.0):
        c = self.counts.setdefault(self.phase, {"compiles": 0, "seconds": 0.0,
                                                "hits": 0, "misses": 0})
        c[key] += by

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._bump("compiles")
            self._bump("seconds", secs)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._bump("hits")
        elif event == "/jax/compilation_cache/cache_misses":
            self._bump("misses")

    def of(self, phase):
        return self.counts.get(phase, {"compiles": 0, "seconds": 0.0,
                                       "hits": 0, "misses": 0})


class Tracer:
    """Profiles the first ``after`` queries of the window when on."""

    def __init__(self, on: bool, after: int, directory: str):
        self.on, self.after, self.directory = on, after, directory
        self.active = False
        self._span = None

    def start(self):
        if not self.on:
            return
        import jax
        jax.profiler.start_trace(self.directory)
        self._span = jax.profiler.TraceAnnotation("chipbench.window")
        self._span.__enter__()
        self.active = True

    def completed(self, n: int):
        if self.active and n >= self.after:
            self.stop()

    def stop(self):
        if not self.active:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def resolve(name: str):
    """(benchmark, cell, configuration, traffic) for the cell ``name``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT / entry["file"])
    traffic = load_json(ROOT / "chipbench" / "traffic"
                        / f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of every value."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end(name: str, lat_ms, setup_s: float, window_s: float):
    if name == "setup_s":
        return setup_s
    if not lat_ms:
        return None
    if name == "qps":
        return len(lat_ms) / window_s
    if name == "p50_ms":
        return percentile(lat_ms, 50)
    if name == "p95_ms":
        return percentile(lat_ms, 95)
    raise KeyError(f"no end-to-end metric {name!r}")


def check(cfg: dict, distinct, records, tables):
    """Every answer against the reference of its query: per number the
    worst reading over the answers, beside its limit."""
    worst: dict = {}
    wants: dict = {}
    for rec in records:
        if rec.answer is None:
            continue
        spec = distinct[rec.query]
        ref = importlib.import_module(f"chipbench.reference.{spec['kind']}")
        if rec.query not in wants:
            wants[rec.query] = ref.expected(tables, spec, cfg)
        for k, v in ref.compare(rec.answer, wants[rec.query]).items():
            worst[k] = max(worst.get(k, 0), v)
    failed = sum(1 for r in records if r.answer is None)
    checks = {k: {"value": v, "limit": cfg["limits"][k]}
              for k, v in sorted(worst.items())}
    checks["queries_failed"] = {"value": failed, "limit": 0}
    correct = bool(worst) and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    return correct, checks


def run(args, require=require_chip) -> dict:
    import jax
    bench, cell, cfg, traffic = resolve(args.workload)
    devs = require(int(cell["chips"]))
    # the program keeps its cache where this variable points
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_S)
    clock = CompileClock()
    log(f"cell {cell['name']}: {cfg['name']} x {cell['traffic']}, seed "
        f"{args.seed}, {len(devs)} {devs[0].device_kind}, compile cache "
        f"{cache_dir}")

    from chipbench.gen import ensure
    directory, gen_s, reused = ensure(cfg, args.seed, str(DATA_DIR))
    log(f"setup gen_s={gen_s:.3f} reused={reused} ({directory})")

    from repro.core import Session
    from repro.exec import Catalog
    from repro.fdb import FDb
    t = time.perf_counter()
    db = FDb.load(directory)
    cat = Catalog(server_slots=64)
    cat.register(db)
    log(f"setup load_s={time.perf_counter() - t:.3f} ({db.num_docs} docs "
        f"in {db.num_shards} shards, {db.nbytes()} bytes, indexes built)")

    session = Session(catalog=cat, backend="jax")
    backend = session.engine.backend
    t = time.perf_counter()
    backend.prime_fdb(db)
    stats = devs[0].memory_stats() or {}
    log(f"setup prime_s={time.perf_counter() - t:.3f} resident "
        f"{backend.device_cache.nbytes()} bytes in "
        f"{len(backend.device_cache)} buffers; device bytes_in_use "
        f"{stats.get('bytes_in_use')}")

    from chipbench.load import Closed
    loop = Closed(traffic, args.seed)
    distinct = loop.distinct()
    kinds = [importlib.import_module(f"chipbench.kinds.{s['kind']}")
             for s in distinct]
    flows = [k.flow(s, cfg) for k, s in zip(kinds, distinct)]
    answer = [k.answer for k in kinds]
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    tracer = Tracer(bool(args.trace), loop.trace_after(), trace_dir)
    t = time.perf_counter()
    loop.warm(session, flows, tracer)
    warm = clock.of("setup")
    log(f"setup warm_s={time.perf_counter() - t:.3f} executables "
        f"{warm['compiles']} in {warm['seconds']:.3f} s, cache hits "
        f"{warm['hits']} misses {warm['misses']}")

    from repro.kernels import ops
    server0 = loop.server.stats() if loop.server is not None else None
    ops.reset_launch_counts()
    clock.phase = "window"
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f}")
    tracer.start()
    try:
        records, t0, t1 = loop.run(session, flows, answer,
                                   float(args.seconds), tracer)
    finally:
        tracer.stop()
        clock.phase = "after"
    launches = dict(ops.launch_counts())
    server = None
    if server0 is not None:
        st = loop.server.stats()
        server = {k: st[k] - server0[k]
                  for k in ("served", "coalesced_queries",
                            "coalesced_batches", "fallback_queries")}
    loop.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    done = [r for r in records if r.answer is not None]
    lat_ms = [(r.end - r.start) * 1e3 for r in done]
    window_s = t1 - t0
    win = clock.of("window")
    log(f"window {window_s:.3f} s: {len(records)} queries, {len(done)} "
        f"answered; executables obtained {win['compiles']}; launches "
        f"{launches}; server {server}; device peak {peak} bytes")
    del session, backend, db, cat

    from chipbench.reference.tables import load as load_tables
    t = time.perf_counter()
    tables = load_tables(directory)
    correct, checks = check(cfg, distinct, records, tables)
    log(f"reference compared {len(records)} answers in "
        f"{time.perf_counter() - t:.3f} s")

    result = {"correct": correct, "attempted": len(records),
              "failed": checks["queries_failed"]["value"], "metrics": {},
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(peak)}}
    if args.trace:
        from chipbench.trace import read_xplane, reduce_trace
        ops_, spans, window, n_dev = read_xplane(trace_dir)
        lo, hi = window
        red = reduce_trace(ops_, spans, lo, hi, n_devices=len(devs))
        trace = dict(red, ops=ops_, lo=lo, hi=hi)
        ctx = SimpleNamespace(
            cfg=cfg, cell=cell, tables=tables, trace=trace,
            device_kind=devs[0].device_kind,
            traced=[distinct[r.query] for r in records
                    if r.traced and r.answer is not None],
            window={"compiles": win["compiles"], "launches": launches,
                    "completed": len(done), "server": server})
        for m in bench["per_layer"]:
            if not applies(m, cell):
                continue
            mod = importlib.import_module(
                "chipbench.metrics." + m["name"].replace(".", "__"))
            v = mod.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace: {len(ops_)} device ops over {red['window_s']:.3f} s, "
            f"busy {red['busy_s']:.3f} s")
    else:
        for m in bench["end_to_end"]:
            if not applies(m, cell):
                continue
            v = end_to_end(m["name"], lat_ms, setup_s, window_s)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = checks
    return result


def main(argv=None, require=require_chip) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args, require=require)
    except NoChip as e:
        print(str(e.code), file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
