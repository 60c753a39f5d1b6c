"""Host milliseconds per query stacking and uploading a wave's inputs
before its fused dispatch, priming included (``warpflow.stack`` and
``warpflow.prime`` spans), over the traced slice."""
from ..spans import per_query_ms


def read(ctx):
    return per_query_ms(ctx, ("stack", "prime"))
