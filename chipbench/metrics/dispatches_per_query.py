"""Fused-wave dispatches and partition merges per query over the window,
from ``repro.kernels.ops.launch_counts``."""


def read(ctx):
    done = ctx.window["completed"]
    if not done:
        return None
    lc = ctx.window["launches"]
    return (lc.get("run_wave_fused", 0) + lc.get("merge_partials", 0)) / done
