"""The generated table, read back from its files as whole columns."""
from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["load"]


def load(directory: str) -> dict:
    """Dotted path -> column over all shards in shard order; a repeated
    column comes with ``<path>/splits`` (row offsets into its values)."""
    with open(os.path.join(directory, "MANIFEST.json")) as fh:
        manifest = json.load(fh)
    parts: dict = {}
    for i in range(manifest["num_shards"]):
        with np.load(os.path.join(directory, f"shard-{i:05d}.npz")) as z:
            for key in z.files:
                if key.startswith("col/"):
                    parts.setdefault(key, []).append(z[key])
    out = {}
    for key, arrs in parts.items():
        _, path, what = key.split("/")
        if what == "values":
            out[path] = np.concatenate(arrs)
        elif what == "splits":
            lens = np.concatenate([np.diff(a) for a in arrs])
            splits = np.zeros(lens.size + 1, dtype=np.int64)
            np.cumsum(lens, out=splits[1:])
            out[path + "/splits"] = splits
    return out
