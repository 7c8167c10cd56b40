"""Hit share of the walk reduce's device-resident plan constants (keyed by
the plan's content): hits over lookups; None where the window made no
lookup, or the program keeps no such cache."""
from bench.metrics._program import hit_pct


def read(r):
    return hit_pct(r, "plan_dev")
