"""Reduction of a profiler trace to device metrics.

Reads the ``.xplane.pb`` the JAX profiler writes, with nothing but
``jax.profiler.ProfileData``.  On each device plane the modules line holds
one event per execution of a jitted program (named ``<program>(<id>)``)
and the ops line one event per operation inside it
(``bench/device_events.json`` names the planes, the lines and the programs
of each kernel); host spans are the ``bench.*`` annotations the harness
writes around the loop's phases.

* busy: the union of the operation intervals inside the traced window,
  averaged over the devices used; idle share is 1 - busy / window.
* kernel time: the summed device durations of the kernel's program
  executions.
* breakdown: the operations that took most time (named
  ``<program>:<op>``), and the idle gaps summed by what the host was doing
  (the innermost ``bench.*`` span covering the gap's middle).
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from pathlib import Path

from .cell import load_json

EVENTS = Path(__file__).resolve().parent / "device_events.json"
WINDOW_SPAN = "bench.window"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the devices used
    kernel_s: dict = field(default_factory=dict)
    device_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _innermost(spans: list, starts: list, t: float) -> str:
    """Name of the innermost host span covering ``t``.  Spans only nest or
    follow one another, so the covering span that started last is the
    innermost, and it lies a few entries back at most."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 8, -1), -1):
        if spans[j][1] > t:
            return spans[j][2]
    return "bench.outside_phases"


def _program(name: str) -> str:
    """``jit_reduce(2711875206488356751)`` -> ``jit_reduce``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.1 = (s32[1]...) fusion(...)`` -> ``fusion.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def summarize(space, n_devices: int = 1, table: dict = None) -> TraceSummary:
    """Reduce a ``ProfileData`` (or anything with its ``planes``) to a
    :class:`TraceSummary` over the window the ``bench.window`` span marks.
    """
    table = table or load_json(EVENTS)
    prefix = table["device_plane_prefix"]
    kernel_of = {prog: k for k, progs in table["kernels"].items()
                 for prog in progs}
    spans: list = []                       # (start, end, name) host spans
    win = None
    devices: list = []
    for plane in space.planes:
        if plane.name.startswith(prefix):
            devices.append(plane)
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("bench."):
                    continue
                s = ev.start_ns
                e = s + ev.duration_ns
                if ev.name == WINDOW_SPAN:
                    win = (s, e)
                else:
                    spans.append((s, e, ev.name))
    if win is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    devices.sort(key=lambda p: p.name)
    devices = devices[:n_devices]
    if not devices:
        raise ValueError(f"the trace holds no plane named {prefix}*")
    w0, w1 = win
    spans.sort()
    starts = [sp[0] for sp in spans]
    busy_total = 0.0
    kernel_ns: dict = {}
    op_ns: dict = {}
    gaps: dict = {}

    def clipped(line):
        for ev in line.events:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                yield s, e, ev.name

    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = []
        if table["modules_line"] in lines:
            mods = sorted(clipped(lines[table["modules_line"]]))
        for s, e, name in mods:
            k = kernel_of.get(_program(name))
            if k is not None:
                kernel_ns[k] = kernel_ns.get(k, 0.0) + (e - s)
        ops = []
        if table["ops_line"] in lines:
            ops = sorted(clipped(lines[table["ops_line"]]))
        # name each operation by the program execution that holds it
        m = 0
        for s, e, name in ops:
            while m < len(mods) and mods[m][1] <= s:
                m += 1
            prog = (_program(mods[m][2])
                    if m < len(mods) and mods[m][0] <= s else "")
            key = f"{prog}:{_op(name)}"
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        merged = _union([(s, e) for s, e, _ in ops])
        busy_total += sum(e - s for s, e in merged)
        # idle gaps inside the window, named by the host's innermost span
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                name = _innermost(spans, starts, (g0 + g1) / 2)
                gaps[name] = gaps.get(name, 0.0) + (g1 - g0)
    top = lambda d: sorted(([k, v * 1e-9] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=busy_total * 1e-9 / len(devices),
                        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
                        device_ops=top(op_ns), idle_gaps=top(gaps))


def summarize_file(path: str, n_devices: int = 1) -> TraceSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path), n_devices)
