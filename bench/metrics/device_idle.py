"""Share of the traced window in which no operation ran on the device."""
from bench.metrics._layers import idle_pct


def read(r):
    return idle_pct(r)
