"""Roofline share of the walk's scan reduce: least time for its counted
work over its device time in the trace."""
from bench.metrics._layers import roofline_pct


def read(r):
    return roofline_pct(r, "walk_reduce")
