"""Self wall of the slowdown model's batch entries (``slowdown.score``:
the host numpy around the aggregation, its device entry excluded) per
decision."""
from bench.metrics._program import SELF, span_ms


def read(r):
    return span_ms(r, lambda k: k == "slowdown.score", SELF)
