"""Hit share of the walk's effective-vector cache (per task signature and
plan, patched per commit): hits over lookups; None where the window made
no lookup."""
from bench.metrics._program import hit_pct


def read(r):
    return hit_pct(r, "eff")
