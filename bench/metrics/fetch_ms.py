"""Host wall of the device entries' blocking reads of their answers
(``device.*.fetch``) per decision."""
from bench.metrics._program import span_ms


def read(r):
    return span_ms(r, lambda k: k.startswith("device.")
                   and k.endswith(".fetch"))
