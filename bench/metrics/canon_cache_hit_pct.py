"""Hit share of the slowdown model's canonical factor cache (per snapshot,
keyed on the device's contention pattern): hits over lookups; None where
the window made no lookup."""
from bench.metrics._program import hit_pct


def read(r):
    return hit_pct(r, "canon")
