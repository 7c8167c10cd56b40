"""The trace reduction on a trace recorded on the chip (trimmed to two
waves, ``tpu_trace.pbtxt``): busy union, idle share, kernel time by
program name, and the idle gaps named by the host's spans."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).with_name("tpu_trace.pbtxt")


@pytest.fixture(scope="module")
def space():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(FIXTURE.read_text())


def _events(space, plane, line):
    for p in space.planes:
        if p.name == plane:
            for ln in p.lines:
                if ln.name == line:
                    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events]
    return []


def test_busy_is_the_union_of_operations(space):
    s = trace.summarize(space)
    (w0, w1, _), = [e for e in _events(space, "/host:CPU", "python3")
                    if e[2] == "bench.window"]
    assert s.window_s == pytest.approx((w1 - w0) * 1e-9)
    # independent count: paint every operation onto a 1 ns grid
    ops = _events(space, "/device:TPU:0", "XLA Ops")
    grid = np.zeros(int(w1 - w0) + 2, dtype=bool)
    for a, b, _ in ops:
        grid[int(round(a - w0)):int(round(b - w0))] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-9, abs=len(ops) * 1e-9)
    idle = 1 - s.busy_s / s.window_s
    assert 0.99 < idle < 1.0


def test_kernel_time_by_program_name(space):
    s = trace.summarize(space)
    mods = _events(space, "/device:TPU:0", "XLA Modules")
    want = {"walk_reduce": "jit_reduce(", "slowdown_kernel": "jit_factors_call("}
    for k, prefix in want.items():
        total = sum(b - a for a, b, n in mods if n.startswith(prefix)) * 1e-9
        assert total > 0
        assert s.kernel_s[k] == pytest.approx(total)
    assert set(s.kernel_s) == set(want)


def test_breakdown_names_ops_and_gaps(space):
    s = trace.summarize(space)
    assert 0 < len(s.device_ops) <= 10
    assert all(":" in name for name, _ in s.device_ops)
    assert any(name.startswith("jit_reduce:") for name, _ in s.device_ops)
    gaps = dict(s.idle_gaps)
    # the host spent these waves in the walk: that is where the device idled
    assert max(gaps, key=gaps.get) == "bench.map"
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_trace_without_a_window_span_is_refused(space):
    class NoWindow:
        planes = [p for p in space.planes if p.name != "/host:CPU"]
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(NoWindow())
