"""Walk host wall (map_pending: Alg. 1 walk, slowdown model, device
entries) per decision."""
from bench.metrics._layers import per_decision_ms


def read(r):
    return per_decision_ms(r, "map")
