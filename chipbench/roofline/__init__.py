"""What a stage of the query path must move, whatever implements it.

One module per stage, ``chipbench/roofline/<stage>.py``, with
``EVENTS`` (prefixes of the device-trace operation names that belong to
the stage: the names of its Pallas kernels) and ``need_bytes(tables, spec, cfg)``: the bytes the
stage has to read and write for one query, computed from the query's
inputs, never from the kernel's padded blocks.  :func:`share` divides the
least time those bytes take at the chip's peak bandwidth by the device
time the trace gives the stage.
"""
from __future__ import annotations

import importlib
import json
import os

from ..trace import stage_seconds

__all__ = ["peaks", "share"]

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(_PEAKS) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def share(stage: str, ctx):
    """Percent of the stage's roofline over the traced queries, or
    ``None`` where the trace holds no operation of the stage."""
    if ctx.trace is None or not ctx.traced:
        return None
    mod = importlib.import_module(f"chipbench.roofline.{stage}")
    lo, hi = ctx.trace["lo"], ctx.trace["hi"]
    secs = stage_seconds(ctx.trace["ops"], mod.EVENTS, lo, hi)
    if secs <= 0:
        return None
    need = sum(mod.need_bytes(ctx.tables, spec, ctx.cfg)
               for spec in ctx.traced)
    least = need / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / secs
