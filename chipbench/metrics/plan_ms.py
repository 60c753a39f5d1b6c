"""Host milliseconds per query in the planner (``warpflow.plan`` spans)
over the traced slice."""
from ..spans import per_query_ms


def read(ctx):
    return per_query_ms(ctx, ("plan",))
