"""Hit share of the walk's splice cache (single-device checks keyed on the
canonical occupancy pattern): hits over lookups; None where the window
made no lookup."""
from bench.metrics._program import hit_pct


def read(r):
    return hit_pct(r, "splice")
