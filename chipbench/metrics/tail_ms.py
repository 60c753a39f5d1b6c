"""Host milliseconds per query from a wave's outputs to the answer:
aggregate finalize, column gathers, the partition merge and the mixer
(``warpflow.finalize``, ``gather``, ``merge`` and ``mix`` spans), over
the traced slice.  Host thread time: gathers on parallel threads add
up."""
from ..spans import TAIL, per_query_ms


def read(ctx):
    return per_query_ms(ctx, TAIL)
