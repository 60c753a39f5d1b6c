"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus per-bench extra
columns) and a human-readable transcript.  ``--scale`` grows the synthetic
world; default sizes finish on a laptop CPU in a few minutes.

``--json`` additionally writes one machine-readable ``BENCH_<suite>.json``
per suite (per-query wall time + parity bit where the suite checks
parity), so the perf trajectory can be tracked across PRs
(``benchmarks/check_regression.py`` compares against a committed
baseline).

Exit status is the CI contract: **non-zero whenever any suite reports a
false parity bit** (numpy oracle ≠ jax batched path) or errored outright,
so the bench smoke job cannot go green on broken output.  The persistent
compile cache is on (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def _write_json(suite: str, rows: list, scale: float, out_dir: str) -> str:
    """One BENCH_<suite>.json: rows with wall time + parity bit."""
    payload = {
        "suite": suite,
        "scale": scale,
        "rows": [
            {"name": r.get("name"),
             "us_per_call": r.get("us_per_call",
                                  r.get("exec_ms", r.get("compute_ms"))),
             **({"parity": r["parity"]} if "parity" in r else {}),
             **({"stages": r["stages"]} if "stages" in r else {}),
             **({"error": r["error"]} if "error" in r else {}),
             "derived": r.get("derived") or ",".join(
                 f"{k}={v}" for k, v in r.items()
                 if k not in ("name", "us_per_call", "derived"))}
            for r in rows
        ],
    }
    path = os.path.join(out_dir, f"BENCH_{suite}.json")
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1)
    return path


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    from .suites import SUITES

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of "
                         + "|".join(SUITES))
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<suite>.json per suite "
                         "(wall time + parity bit)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for --json output files")
    args = ap.parse_args()
    enable_compile_cache()

    # one bench per registry entry (benchmarks/suites.py): --only here,
    # check_regression.py --suite, and the Makefile all read the same table
    import importlib

    def _bench(spec):
        mod = importlib.import_module(f".{spec['module']}", __package__)
        kw = {}
        if spec["scale"]:
            kw["scale"] = args.scale
        if spec["parity"]:
            # parity verdicts flow into rows; this harness owns the exit
            # code
            kw["raise_on_mismatch"] = False
        return lambda: mod.run(**kw)

    benches = {name: _bench(spec) for name, spec in SUITES.items()}
    only = {s for s in (args.only or "").split(",") if s}
    unknown = only - set(benches)
    if unknown:
        raise SystemExit(f"unknown --only suite(s): {sorted(unknown)}; "
                         f"known: {sorted(benches)}")
    all_rows = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"== {name} ==", flush=True)
        try:
            suite_rows = fn() or []
        except Exception as e:  # keep the harness going; report at end
            traceback.print_exc()
            print(f"  BENCH FAILED: {name}: {e!r}", file=sys.stderr)
            suite_rows = [{"name": f"{name}_FAILED", "error": repr(e)}]
        all_rows.extend(suite_rows)
        if args.json:
            path = _write_json(name, suite_rows, args.scale, args.json_dir)
            print(f"  wrote {path}")

    print("\nname,us_per_call,derived")
    for r in all_rows:
        us = r.get("us_per_call", r.get("exec_ms", r.get("compute_ms", "")))
        derived = r.get("derived") or ",".join(
            f"{k}={v}" for k, v in r.items()
            if k not in ("name", "us_per_call", "derived"))
        print(f"{r['name']},{us},\"{derived}\"")

    parity_bad = [r["name"] for r in all_rows
                  if "parity" in r and not r["parity"]]
    errors = [r["name"] for r in all_rows if "error" in r]
    if parity_bad:
        print(f"\nPARITY FAILURE: {parity_bad}", file=sys.stderr)
        sys.exit(1)
    if errors:
        print(f"\nSUITE ERRORS: {errors}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
