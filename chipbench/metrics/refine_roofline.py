"""Refine stage's share of its roofline (see ``roofline/refine.py``)."""
from ..roofline import share


def read(ctx):
    return share("refine", ctx)
