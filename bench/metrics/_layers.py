"""Arithmetic the metric readers share (read by path from each reader)."""
from __future__ import annotations


def per_decision_ms(r, *phases):
    """Host wall of the loop's ``phases`` over the window, per decision."""
    if not r.window.decisions:
        return None
    return 1e3 * sum(r.phase[p] for p in phases) / r.window.decisions


def idle_pct(r):
    t = r.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def roofline_pct(r, kernel):
    """The kernel's roofline share over the traced window; None where the
    trace saw none of its device time or the window made no such call."""
    from bench.roofline import share_pct
    t = r.trace
    if t is None or t.kernel_s.get(kernel, 0.0) <= 0:
        return None
    ops, nbytes = r.work[kernel]
    if ops <= 0:
        return None
    return share_pct(ops, nbytes, t.kernel_s[kernel], r.device_kind)[0]
