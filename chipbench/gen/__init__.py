"""Table generators: one module per configuration, found by its name.

``chipbench/gen/<config>.py`` defines ``shards(cfg, seed)``, which returns
the configuration's table as a list of shards, each a dict of dotted leaf
path -> ``(values, row_splits or None)``.  :func:`ensure` draws that world
once from the configuration's ``world_seed`` and deals each shard's rows
in an order drawn from the run's seed (:func:`place`): every seed holds the
same rows in the same shards, so it gives the same postings, selections
and compiled shapes, in another order.  It writes the result in the
on-disk layout that ``repro.fdb.FDb.save`` writes and ``FDb.load`` reads
(a ``MANIFEST.json`` plus one ``shard-NNNNN.npz`` per shard), under a
directory keyed by (configuration, seed), and reuses that directory when a
later run asks for the same pair.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import time

import numpy as np

from .world import rng_for

__all__ = ["ensure", "place", "write_fdb"]


def place(shards, seed: int):
    """``shards`` with each shard's rows in an order drawn from ``seed``; a
    repeated column's values move with their rows."""
    out = []
    for s, cols in enumerate(shards):
        perm = None
        placed = {}
        for path, (values, splits) in cols.items():
            if perm is None:
                n = values.shape[0] if splits is None else splits.size - 1
                perm = rng_for(seed, 1 + s).permutation(n)
            if splits is None:
                placed[path] = (values[perm], None)
                continue
            lens = np.diff(splits)[perm]
            new = np.zeros(perm.size + 1, dtype=np.int64)
            np.cumsum(lens, out=new[1:])
            idx = np.repeat(splits[:-1][perm] - new[:-1], lens) \
                + np.arange(int(new[-1]))
            placed[path] = (values[idx], new)
        out.append(placed)
    return out


def write_fdb(directory: str, cfg: dict, shards) -> None:
    """Write ``shards`` as an FDb directory (``FDb.save``'s layout)."""
    os.makedirs(directory)
    rows_per_shard = []
    for i, cols in enumerate(shards):
        arrays = {}
        n = None
        for path, (values, splits) in cols.items():
            arrays[f"col/{path}/values"] = values
            if splits is not None:
                arrays[f"col/{path}/splits"] = splits
                rows = splits.size - 1
            else:
                rows = values.shape[0]
            if n is not None and rows != n:
                raise ValueError(f"shard {i}: column {path} has {rows} rows, "
                                 f"expected {n}")
            n = rows
        rows_per_shard.append(int(n))
        arrays["__n__"] = np.array([n], dtype=np.int64)
        np.savez(os.path.join(directory, f"shard-{i:05d}.npz"), **arrays)
    manifest = {"name": cfg["table"], "schema": cfg["schema"],
                "num_shards": len(shards), "rows": rows_per_shard}
    with open(os.path.join(directory, "MANIFEST.json"), "w") as fh:
        json.dump(manifest, fh)


def ensure(cfg: dict, seed: int, root: str):
    """The FDb directory of (``cfg``, ``seed``) under ``root``, generated
    if absent.  Returns ``(directory, seconds spent generating, reused)``.
    The directory appears whole or not at all: it is written under a
    staging name and renamed."""
    directory = os.path.join(root, cfg["name"], str(int(seed)))
    if os.path.exists(os.path.join(directory, "MANIFEST.json")):
        return directory, 0.0, True
    t0 = time.perf_counter()
    module = importlib.import_module(f"chipbench.gen.{cfg['name']}")
    shards = place(module.shards(cfg, int(cfg["world_seed"])), int(seed))
    staging = directory + ".staging"
    shutil.rmtree(staging, ignore_errors=True)
    shutil.rmtree(directory, ignore_errors=True)
    write_fdb(staging, cfg, shards)
    os.rename(staging, directory)
    return directory, time.perf_counter() - t0, False
