#!/usr/bin/env python3
"""The controls: each configuration's reference one precision step below
the precision it states, compared as a run's answers are, to show the
limits catch it.

    python3 chipbench/control.py --workload <name> --seeds <n> [<n> ...]

For every seed it generates the cell's table at the cell's own size and
puts each query kind's ``control`` (``chipbench/reference``) in the
program's place for every distinct query of the cell's traffic: the
aggregate reference computed in bfloat16, one step below the program's
float32, and the trip reference (table, point tests and answer) in
float32, one step below the stated float64.  It prints per seed the
worst reading of each compared number beside its limit.  The benchmark's
own runs do not run it.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

__all__ = ["readings"]


def readings(cfg: dict, traffic: dict, seed: int, tables) -> dict:
    """Worst reading of each compared number over the mix's queries, with
    each kind's control in place of the program."""
    worst: dict = {}
    for spec in traffic["queries"]:
        ref = importlib.import_module(f"chipbench.reference.{spec['kind']}")
        got = ref.control(tables, spec, cfg)
        want = ref.expected(tables, spec, cfg)
        for k, v in ref.compare(got, want).items():
            worst[k] = max(worst.get(k, 0), v)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench.gen import ensure
    from chipbench.reference.tables import load
    from chipbench.run import DATA_DIR, resolve
    _, cell, cfg, traffic = resolve(args.workload)
    for seed in args.seeds:
        directory, _, _ = ensure(cfg, seed, str(DATA_DIR))
        worst = readings(cfg, traffic, seed, load(directory))
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": {k: {"value": v,
                                          "limit": cfg["limits"][k]}
                                      for k, v in worst.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
