"""Self wall of the timeline's reprice flush (``timeline.flush``, the
slowdown model's scoring excluded) per decision."""
from bench.metrics._program import SELF, span_ms


def read(r):
    return span_ms(r, lambda k: k == "timeline.flush", SELF)
