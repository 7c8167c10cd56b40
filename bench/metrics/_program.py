"""Arithmetic the readers of the program's own spans and counters share.

They read ``repro.core.trace.captured()``: the program's totals over the
newest profiler capture, which a traced run opens at the window's open and
closes at its close (spans as ``(count, wall_s, self_s)``).  Each returns
None for a program that keeps no such totals."""
from __future__ import annotations

WALL, SELF = 1, 2


def totals():
    try:
        from repro.core.trace import captured
    except ImportError:
        return None
    return captured()


def span_ms(r, pick, col=WALL):
    """Summed wall (``col=SELF``: self wall) of the spans whose name
    ``pick`` accepts, in ms per decision."""
    p, d = totals(), r.window.decisions
    if p is None or not d:
        return None
    return 1e3 * sum(v[col] for k, v in p["spans"].items() if pick(k)) / d


def is_device_entry(name: str) -> bool:
    """``device.walk_reduce``, ``device.walk_reduce_batch``,
    ``device.slowdown``; not their ``.call`` / ``.fetch`` children."""
    return name.startswith("device.") and name.count(".") == 1


def per_decision(r, counter: str):
    p, d = totals(), r.window.decisions
    if p is None or not d:
        return None
    return p["counters"].get(counter, 0) / d


def hit_pct(r, cache: str):
    """Hits over lookups of ``cache.<cache>.{hit,miss}``; None where the
    window made no lookup."""
    p = totals()
    if p is None:
        return None
    c = p["counters"]
    hits = c.get(f"cache.{cache}.hit", 0)
    lookups = hits + c.get(f"cache.{cache}.miss", 0)
    return 100.0 * hits / lookups if lookups > 0 else None
