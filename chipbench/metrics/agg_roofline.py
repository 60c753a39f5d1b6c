"""Aggregation stage's share of its roofline (see ``roofline/agg.py``)."""
from ..roofline import share


def read(ctx):
    return share("agg", ctx)
