"""Host wall inside the device entries (scan reduce single and batched,
slowdown aggregation: argument preparation, launch and the blocking reads
of the answers) per decision."""
from bench.metrics._program import is_device_entry, span_ms


def read(r):
    return span_ms(r, is_device_entry)
