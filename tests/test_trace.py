"""The program's spans and counters (``repro.core.trace``): totals and self
time, one span stack per thread, counters, the profiler's copy of each
span, and the device entries' spans and counters on the jax path."""
import glob
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import trace


def _delta(s0, s1, name):
    a = s0["spans"].get(name, (0, 0.0, 0.0))
    return tuple(x - y for x, y in zip(s1["spans"][name], a))


def _count(s0, s1, name):
    return s1["counters"].get(name, 0) - s0["counters"].get(name, 0)


def test_totals_and_self_time_under_nesting():
    s0 = trace.snapshot()
    with trace.span("test.nest.outer") as outer:
        for _ in range(2):
            with trace.span("test.nest.inner") as inner:
                with trace.span("test.nest.leaf"):
                    time.sleep(0.002)
        time.sleep(0.001)
    s1 = trace.snapshot()
    n, wall, own = _delta(s0, s1, "test.nest.outer")
    ni, wi, si = _delta(s0, s1, "test.nest.inner")
    nl, wl, sl = _delta(s0, s1, "test.nest.leaf")
    assert (n, ni, nl) == (1, 2, 2)
    assert wall == pytest.approx(outer.wall, rel=1e-12)
    assert inner.wall > 0.002
    assert own == pytest.approx(wall - wi, rel=1e-9)
    assert si == pytest.approx(wi - wl, rel=1e-9)
    assert sl == pytest.approx(wl, rel=1e-12)
    assert own >= 0.001 and wl >= 0.004
    # self walls telescope to the outermost span's wall
    assert own + si + sl == pytest.approx(wall, rel=1e-9)


def test_each_thread_keeps_its_own_stack():
    started, release = threading.Event(), threading.Event()

    def worker():
        with trace.span("test.thread.worker"):
            started.set()
            release.wait(5.0)

    s0 = trace.snapshot()
    with trace.span("test.thread.main"):
        th = threading.Thread(target=worker)
        th.start()
        assert started.wait(5.0)
        # the worker's span is open while the main thread's runs; neither
        # is the other's child
        with trace.span("test.thread.child"):
            time.sleep(0.002)
        release.set()
        th.join(5.0)
    assert not th.is_alive()
    s1 = trace.snapshot()
    _, wall, own = _delta(s0, s1, "test.thread.main")
    _, wc, _ = _delta(s0, s1, "test.thread.child")
    _, ww, sw = _delta(s0, s1, "test.thread.worker")
    assert own == pytest.approx(wall - wc, rel=1e-9)
    assert sw == pytest.approx(ww, rel=1e-12)


def test_totals_exact_under_thread_contention():
    """Many short-lived threads write while snapshots are taken: no update
    is lost, and finished threads' totals stay counted."""
    per, workers = 500, 24
    s0 = trace.snapshot()
    stop = threading.Event()

    def writer():
        for _ in range(per):
            with trace.span("test.stress.outer"):
                with trace.span("test.stress.inner"):
                    trace.count("test.stress.n")

    def reader():
        while not stop.is_set():
            trace.snapshot()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        rd = threading.Thread(target=reader)
        rd.start()
        for _ in range(2):              # a second generation of threads
            ths = [threading.Thread(target=writer) for _ in range(workers)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(60.0)
            assert not any(th.is_alive() for th in ths)
        stop.set()
        rd.join(60.0)
        assert not rd.is_alive()
    finally:
        sys.setswitchinterval(old)
    s1 = trace.snapshot()
    total = 2 * workers * per
    assert _count(s0, s1, "test.stress.n") == total
    assert _delta(s0, s1, "test.stress.outer")[0] == total
    assert _delta(s0, s1, "test.stress.inner")[0] == total


def test_counters_add():
    s0 = trace.snapshot()
    trace.count("test.counter")
    trace.count("test.counter", 9)
    s1 = trace.snapshot()
    assert _count(s0, s1, "test.counter") == 10
    assert "test.never" not in s1["counters"]


def test_importing_the_module_loads_no_jax():
    code = ("import sys; import repro.core.trace as t; "
            "t.span('x').__enter__().__exit__(None, None, None); "
            "sys.exit('jax' in sys.modules)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_no_capture_reads_none():
    code = ("import sys; import repro.core.trace as t; "
            "t.span('x').__enter__().__exit__(None, None, None); "
            "sys.exit(t.captured() is not None)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_captured_totals_cover_the_newest_capture(tmp_path):
    import jax
    for k in range(2):             # the second capture replaces the first
        with trace.span("test.cap.before"):
            trace.count("test.cap.n")
        across = trace.span("test.cap.across").__enter__()
        jax.profiler.start_trace(str(tmp_path / str(k)))
        try:
            for _ in range(2):
                with trace.span("test.cap.inside"):
                    trace.count("test.cap.n", 5)
            across.__exit__(None, None, None)
            with trace.span("test.cap.tail"):
                time.sleep(0.001)
                # a capture still running reads up to now
                assert trace.captured()["counters"]["test.cap.n"] == 10
                jax.profiler.stop_trace()
        except BaseException:
            jax.profiler.stop_trace()
            raise
        with trace.span("test.cap.after"):
            trace.count("test.cap.n", 7)
        got = trace.captured()
        zero = (0, 0.0, 0.0)
        assert got["counters"]["test.cap.n"] == 10
        assert got["spans"]["test.cap.inside"][0] == 2
        # a span counts whole where it closes
        assert got["spans"]["test.cap.across"][0] == 1
        assert got["spans"]["test.cap.across"][1] == \
            pytest.approx(across.wall, rel=1e-12)
        for name in ("test.cap.before", "test.cap.tail", "test.cap.after"):
            assert got["spans"].get(name, zero)[0] == 0
        # what ran after the capture does not move it
        with trace.span("test.cap.after"):
            trace.count("test.cap.n")
        assert trace.captured() == got


def _profile(tmp_path, fn):
    """Run ``fn`` under the jax profiler; the host events by name."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return events


def test_spans_appear_in_a_profiler_trace(tmp_path):
    def work():
        with trace.span("test.prof.wave", wave=3, rid=41) as sp:
            with trace.span("test.prof.inner"):
                time.sleep(0.001)
            sp.note(readings=2)

    ev = _profile(tmp_path, work)
    (w0, w1, meta), = ev["test.prof.wave"]
    (i0, i1, _), = ev["test.prof.inner"]
    assert meta == {"wave": 3, "rid": 41, "readings": 2}
    assert w0 <= i0 < i1 <= w1


def test_device_entries_count_and_nest_on_the_jax_path(tmp_path,
                                                       monkeypatch):
    from repro.kernels import walk_kernel
    from repro.kernels.slowdown_kernel import slowdown_factors_pallas
    monkeypatch.delenv("REPRO_WALK_KERNEL", raising=False)
    monkeypatch.setattr(walk_kernel, "_AUTO_JAX", True)
    monkeypatch.setattr(walk_kernel, "_PLANS", walk_kernel._ResidentPlans(8))
    n = 16
    ok = np.ones(n, dtype=bool)
    key = np.arange(n, dtype=np.float64)
    cols = (np.array([0, 0]), np.array([n, n // 2]), np.array([n, 8]),
            np.array([1, 0]), np.array([1.0, 0.0]), np.array([0, 1]))
    x = np.full((5, 3), 0.1)
    args = (np.array([0.5, 0.2, 0.1]), np.ones(5), np.zeros(5), 0.1)
    walk_kernel.scan_reduce(ok, key, *cols, 0.5)       # compile off-trace
    walk_kernel.scan_reduce_batch(ok[None], key[None],
                                  *(c[None] for c in cols), 0.5)
    slowdown_factors_pallas(x, *args)

    def work():
        walk_kernel.scan_reduce(ok, key, *cols, 0.5)

    s0 = trace.snapshot()
    ev = _profile(tmp_path, work)
    s1 = trace.snapshot()
    # one packed read; one packed upload, the plan's constants resident
    assert _count(s0, s1, "device.fetch") == 1
    assert _count(s0, s1, "device.h2d") == 1
    assert _count(s0, s1, "cache.plan_dev.hit") == 1
    for name in ("device.walk_reduce", "device.walk_reduce.call",
                 "device.walk_reduce.fetch"):
        assert _delta(s0, s1, name)[0] == 1
    (e0, e1, _), = ev["device.walk_reduce"]
    for child in ("device.walk_reduce.call", "device.walk_reduce.fetch"):
        (c0, c1, _), = ev[child]
        assert e0 <= c0 < c1 <= e1
    assert ev["device.walk_reduce.call"][0][1] \
        <= ev["device.walk_reduce.fetch"][0][0]

    s0 = trace.snapshot()
    walk_kernel.scan_reduce_batch(np.stack([ok, ok]), np.stack([key, key]),
                                  *(np.stack([c, c]) for c in cols), 0.5)
    slowdown_factors_pallas(x, *args)
    s1 = trace.snapshot()
    # the two-row stack's constants are new: uploaded once, then resident
    assert _count(s0, s1, "device.fetch") == 1 + 1
    assert _count(s0, s1, "device.h2d") == 2 + 4
    assert _count(s0, s1, "cache.plan_dev.miss") == 1
    for entry in ("device.walk_reduce_batch", "device.slowdown"):
        n_e, wall, own = _delta(s0, s1, entry)
        _, wc, _ = _delta(s0, s1, entry + ".call")
        _, wf, _ = _delta(s0, s1, entry + ".fetch")
        assert n_e == 1
        assert own == pytest.approx(wall - wc - wf, rel=1e-9)
