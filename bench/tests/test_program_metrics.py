"""The readers of the program's own spans and counters, on synthetic
totals and on a profiler capture of the program's spans."""
from types import SimpleNamespace

import pytest

from bench.metrics import _program
from bench.run import RunRecord, load_reader

NAMES = ("dispatch_ms", "fetch_ms", "host_syncs", "h2d_values",
         "slowdown_ms", "flush_ms", "canon_cache_hit_pct",
         "ident_cache_hit_pct", "splice_cache_hit_pct", "eff_cache_hit_pct")

PROGRAM = {
    "spans": {
        # (count, wall_s, self_s)
        "serve.map": (10, 0.150, 0.020),
        "device.walk_reduce": (30, 0.060, 0.004),
        "device.walk_reduce.call": (30, 0.030, 0.030),
        "device.walk_reduce.fetch": (30, 0.026, 0.026),
        "device.walk_reduce_batch": (5, 0.020, 0.002),
        "device.walk_reduce_batch.fetch": (5, 0.008, 0.008),
        "device.slowdown": (2, 0.010, 0.004),
        "device.slowdown.fetch": (2, 0.002, 0.002),
        "slowdown.score": (8, 0.016, 0.006),
        "timeline.flush": (40, 0.004, 0.003),
    },
    "counters": {"device.fetch": 142, "device.h2d": 319,
                 "cache.canon.hit": 3, "cache.canon.miss": 1,
                 "cache.eff.hit": 9, "cache.eff.miss": 1,
                 "cache.ident.miss": 5},
}


def _record(decisions=10):
    return RunRecord(cell=None, window=SimpleNamespace(decisions=decisions),
                     phase={}, calls={}, work={}, device_kind="cpu",
                     setup_s=0.0)


def _read(name, rec):
    return load_reader(name)(rec)


def test_readers_on_a_synthetic_window(monkeypatch):
    monkeypatch.setattr(_program, "totals", lambda: PROGRAM)
    rec = _record()
    assert _read("dispatch_ms", rec) == pytest.approx(9.0)
    assert _read("fetch_ms", rec) == pytest.approx(3.6)
    assert _read("host_syncs", rec) == pytest.approx(14.2)
    assert _read("h2d_values", rec) == pytest.approx(31.9)
    assert _read("slowdown_ms", rec) == pytest.approx(0.6)
    assert _read("flush_ms", rec) == pytest.approx(0.3)
    assert _read("canon_cache_hit_pct", rec) == pytest.approx(75.0)
    assert _read("eff_cache_hit_pct", rec) == pytest.approx(90.0)
    assert _read("ident_cache_hit_pct", rec) == 0.0
    # the window made no splice lookup
    assert _read("splice_cache_hit_pct", rec) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_without_the_program_totals_is_none(name, monkeypatch):
    # a program that keeps no totals (older commits), or no decision
    monkeypatch.setattr(_program, "totals", lambda: None)
    assert _read(name, _record()) is None
    monkeypatch.setattr(_program, "totals", lambda: PROGRAM)
    if not name.endswith("_pct"):
        assert _read(name, _record(decisions=0)) is None


def test_program_without_the_trace_module_reads_none(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert _program.totals() is None


def test_readers_read_the_profiler_capture(tmp_path):
    """The window is the profiler's capture: what the program counts
    before and after it stays out."""
    import jax

    from repro.core import trace
    trace.count("cache.splice.miss", 3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("timeline.flush"):
            trace.count("cache.splice.hit")
            trace.count("cache.splice.miss")
    finally:
        jax.profiler.stop_trace()
    with trace.span("timeline.flush"):
        trace.count("cache.splice.hit", 5)
    assert _read("splice_cache_hit_pct", _record()) == pytest.approx(50.0)
    wall = trace.captured()["spans"]["timeline.flush"][1]
    assert _read("flush_ms", _record(decisions=4)) == \
        pytest.approx(1e3 * wall / 4)
