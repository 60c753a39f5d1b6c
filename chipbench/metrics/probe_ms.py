"""Host milliseconds per query building the index probe bitmaps of each
wave (``warpflow.probe`` spans) over the traced slice."""
from ..spans import per_query_ms


def read(ctx):
    return per_query_ms(ctx, ("probe",))
