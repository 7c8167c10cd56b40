"""Blocking device-to-host reads (``device.fetch``) per decision."""
from bench.metrics._program import per_decision


def read(r):
    return per_decision(r, "device.fetch")
