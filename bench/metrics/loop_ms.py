"""Serving-loop host wall (completion sync and admission, the walk
excluded) per decision."""
from bench.metrics._layers import per_decision_ms


def read(r):
    return per_decision_ms(r, "sync", "admit")
