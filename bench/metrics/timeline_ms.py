"""Timeline (DES advance) host wall per decision."""
from bench.metrics._layers import per_decision_ms


def read(r):
    return per_decision_ms(r, "advance")
