"""Calls of the two device entries (slowdown aggregation, scan reduce
single and batched) per decision."""


def read(r):
    d = r.window.decisions
    return sum(r.calls.values()) / d if d else None
