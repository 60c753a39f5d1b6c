"""End-to-end execution-backend comparison: numpy oracle vs jax kernels.

Extends the per-kernel microbenchmarks (bench_kernels) to the full query
path: every Q1–Q5 benchmark query runs under both registered backends —
the jax side through the **fused** wave path (one ``run_wave_fused``
dispatch per ⌈shards/wave⌉ wave chaining probe → compact → segment-agg,
device-resident columns; ``REPRO_EXEC_FUSED=0`` restores the legacy
per-primitive wave launches) — and the report shows per-query wall time,
speedup, kernel-launch counts, and a byte-level parity verdict against
the numpy per-shard oracle — the contract every future lowering (GPU,
sharded meshes) must keep.  Timing blocks on the last device output
before the clock stops (jax dispatch is async).

On CPU the jax backend resolves to the ``reference`` kernel impl, so the
timing column measures dispatch overhead, not TPU speedup; run with
``REPRO_KERNEL_IMPL=pallas`` on a TPU host for the hardware numbers.

Every row carries a ``parity`` bit; ``benchmarks.run`` exits non-zero when
any suite reports a false one (the CI bench smoke gate).
"""
from __future__ import annotations

import numpy as np

from repro.exec import AdHocEngine, get_backend
from repro.fdb.index import bitmap_from_ids, bitmap_full
from repro.kernels import ops as kernel_ops

from .queries import QUERIES, build_catalog, q_variability, time_best

__all__ = ["run", "batches_identical"]


def batches_identical(a, b) -> bool:
    if a.n != b.n or a.paths() != b.paths():
        return False
    for p in a.paths():
        ca, cb = a[p], b[p]
        if ca.values.dtype != cb.values.dtype:
            return False
        if not np.array_equal(ca.values, cb.values):
            return False
        if (ca.row_splits is None) != (cb.row_splits is None):
            return False
        if ca.row_splits is not None and \
                not np.array_equal(ca.row_splits, cb.row_splits):
            return False
        if ca.vocab != cb.vocab:
            return False
    return True


def _bench_primitives(rows, print_fn):
    """Backend primitive microbenches: the three hot-path ops, both ways."""
    rng = np.random.default_rng(0)
    n = 1 << 20
    full = bitmap_full(n)
    probes = [bitmap_from_ids(rng.choice(n, n // 3, replace=False), n)
              for _ in range(4)]
    mask = rng.random(n) < 0.3
    codes = rng.integers(0, 1024, n)
    vals = rng.normal(48.0, 9.0, n)
    for bname in ("numpy", "jax"):
        be = get_backend(bname)
        for op_name, fn in [
                ("intersect_4x1M", lambda: be.intersect_bitmaps(full, probes)),
                ("select_ids_1M", lambda: be.select_ids(full, n)),
                ("compact_1M", lambda: be.compact_mask(mask)),
                ("segment_agg_1M_1024g",
                 lambda: be.segment_aggregate(codes, vals, 1024))]:
            _, ms = time_best(fn)
            rows.append({"name": f"backend_{bname}_{op_name}",
                         "us_per_call": round(ms * 1e3, 1),
                         "derived": f"{n / (ms * 1e3):.1f} Melem/s"})
            print_fn(f"  {rows[-1]['name']:44s} "
                     f"{rows[-1]['us_per_call']:10.1f} µs  "
                     f"{rows[-1]['derived']}")


def run(scale: float = 0.5, print_fn=print, raise_on_mismatch: bool = True):
    rows: list = []
    _bench_primitives(rows, print_fn)

    cat = build_catalog(scale=scale)
    engines = {b: AdHocEngine(cat, backend=b) for b in ("numpy", "jax")}
    n_shards = cat.get("SpeedObservations").num_shards
    wave = engines["jax"].wave
    all_parity = True
    for qname, (cities, months) in QUERIES.items():
        flow = q_variability(cities, months)
        results, times = {}, {}
        launches = 0
        for bname, eng in engines.items():
            if bname == "jax":
                kernel_ops.reset_launch_counts()
            res, ms = time_best(lambda e=eng: e.collect(flow), repeats=2)
            results[bname], times[bname] = res, ms
            if bname != "jax":
                continue
            # kernel dispatches per collect on the batched jax path:
            # launch counts are deterministic, so the 3 timed calls
            # (warm + 2 repeats) divide evenly.  On the fused path the
            # whole query is ⌈shards/wave⌉ ``run_wave_fused`` dispatches
            # total; with REPRO_EXEC_FUSED=0 it is ⌈shards/wave⌉ per
            # primitive
            launches = sum(kernel_ops.launch_counts().values()) // 3
        parity = batches_identical(results["numpy"].batch,
                                   results["jax"].batch) \
            and results["numpy"].profile.rows_selected \
            == results["jax"].profile.rows_selected
        all_parity &= parity
        speedup = times["numpy"] / max(times["jax"], 1e-9)
        rows.append({
            "name": f"backend_e2e_{qname}",
            "us_per_call": round(times["jax"] * 1e3, 1),
            "parity": 1 if parity else 0,
            "derived": (f"numpy={times['numpy']:.1f}ms "
                        f"jax={times['jax']:.1f}ms "
                        f"speedup={speedup:.2f}x "
                        f"rows={results['numpy'].batch.n} "
                        f"launches={launches} "
                        f"shards={n_shards} wave={wave} "
                        f"parity={'OK' if parity else 'MISMATCH'}")})
        print_fn(f"  {qname}: {rows[-1]['derived']}")
    rows.append({"name": "backend_parity_all",
                 "us_per_call": "",
                 "parity": 1 if all_parity else 0,
                 "derived": "OK" if all_parity else "MISMATCH"})
    print_fn(f"  parity across all queries: "
             f"{'OK' if all_parity else 'MISMATCH'}")
    if not all_parity and raise_on_mismatch:
        raise AssertionError("backend parity violated — see report rows")
    return rows
