"""Host values handed to a device call (``device.h2d``) per decision."""
from bench.metrics._program import per_decision


def read(r):
    return per_decision(r, "device.h2d")
