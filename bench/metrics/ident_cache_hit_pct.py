"""Hit share of the walk's identity-keyed factor cache (a device view
unchanged since its last check): hits over lookups; None where the
window made no lookup."""
from bench.metrics._program import hit_pct


def read(r):
    return hit_pct(r, "ident")
