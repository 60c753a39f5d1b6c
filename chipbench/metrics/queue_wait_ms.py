"""Mean milliseconds a served query waited, from its ``warpflow.submit``
span to the start of the ``warpflow.serve.batch`` span that planned it,
over the batches that start in the traced slice."""
from ..spans import from_ctx, queue_waits


def read(ctx):
    got = from_ctx(ctx)
    if got is None:
        return None
    waits = queue_waits(got[0], ctx.trace["lo"], ctx.trace["hi"])
    return 1e3 * sum(waits) / len(waits) if waits else None
