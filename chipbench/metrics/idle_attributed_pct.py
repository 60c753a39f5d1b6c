"""Share of the device's idle time in the traced slice that lies under
a layer span of the program (any ``warpflow.*`` span but ``query`` and
``serve.batch``).  Reading it also logs the slice by span: host seconds
per span, idle seconds and the longest idle gaps named by the innermost
span open in them, and the span each executable obtained in the slice
fell under."""
import json

from ..spans import describe, from_ctx, idle_attributed


def read(ctx):
    got = from_ctx(ctx)
    if got is None:
        return None
    spans, compiles = got
    lo, hi, ops = ctx.trace["lo"], ctx.trace["hi"], ctx.trace["ops"]
    print("chipbench: spans "
          + json.dumps(describe(ops, spans, compiles, lo, hi)), flush=True)
    idle, explained = idle_attributed(ops, spans, lo, hi)
    return 100.0 * explained / idle if idle > 0 else None
