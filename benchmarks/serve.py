"""Online serving continuum throughput: ServeLoop co-simulation gates.

Drives seeded open-loop traffic (a Poisson tenant + a diurnal tenant,
rates scaled with ``mult``) through the session-resident timeline on the
Fig. 13 mining topology at mult=8 and mult=64 (smoke: mult=2).  Each run
asserts the zero-rebuild guarantee (``engine_opens == 1``) and records

* sustained co-simulation throughput (``wall_rps`` — requests processed
  per wall-clock second, the gated metric),
* tail latency (p50/p99/p999, simulated time — deterministic per seed),
* per-tenant SLA attainment (a reject counts as a miss) and
  rejected/deferred counts.

Emits ``BENCH_serve.json``; ``--check`` fails (exit 1) when ``wall_rps``
at either scale regresses >20% vs the checked-in baseline, when p99
drifts >20% (it is seed-deterministic, so drift means the engine's event
order changed), or when SLA attainment drops >2 points; ``--smoke`` runs
a seconds-scale variant for CI.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import os

from repro.core import (DiurnalArrivals, PoissonArrivals, ServeLoop,
                        TenantSpec, Testbed, build_orchestrators,
                        build_testbed, ground_truth_traverser, heye_traverser,
                        single_task_request)
from repro.serve.admission import AdaptiveWindow, AdmissionController

from .common import Table, check_gate, fail_gates, write_payload
from .scaling import mining_counts

_JSON = Path(__file__).resolve().parent.parent / "BENCH_serve.json"

# ~115 * mult offered rps over a horizon that shrinks with mult, so every
# scale serves a comparable ~1.1k-request stream and wall_rps isolates
# per-request co-simulation cost (bigger fleet, same request count)
_MINING_RATE = 75.0
_VISION_BASE, _VISION_PEAK = 20.0, 60.0
_HORIZON = 10.0
# absolute co-simulation throughput floor at the largest scale: the
# session-resident walk state keeps steady-state serving O(changed
# devices), worth >=3x the cold-walk baseline on the reference machine
_X64_WALL_RPS_FLOOR = 200.0


def _serve_loop(mult: int, batch_window=0.0) -> tuple[ServeLoop, Testbed]:
    """The mult-scaled mining fleet's ServeLoop (not yet run) and its
    testbed."""
    ec, sc = mining_counts(mult)
    tb = build_testbed(edge_counts=ec, server_counts=sc)
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    horizon = _HORIZON / mult
    tenants = [
        TenantSpec("mining",
                   PoissonArrivals(rate=_MINING_RATE * mult, seed=11),
                   single_task_request("svm", origin=tb.edges[0], sla=0.10),
                   sla=0.10),
        TenantSpec("vision",
                   DiurnalArrivals(base_rate=_VISION_BASE * mult,
                                   peak_rate=_VISION_PEAK * mult,
                                   period=horizon, seed=12),
                   single_task_request("mlp", origin=tb.edges[1], sla=0.15),
                   sla=0.15),
    ]
    loop = ServeLoop(tb.graph, root, tenants,
                     truth=ground_truth_traverser(tb.graph, 0),
                     admission=AdmissionController(slack=4.0,
                                                   defer_delay=0.005,
                                                   max_defers=1),
                     batch_window=batch_window,
                     horizon=horizon)
    return loop, tb


def _serve_once(mult: int, batch_window=0.0):
    loop, tb = _serve_loop(mult, batch_window)
    stats = loop.run()
    if stats.engine_opens != 1:
        raise AssertionError(
            f"x{mult}: {stats.engine_opens} TimelineEngine builds "
            "(the resident-timeline guarantee is exactly 1)")
    counters = {
        "route_holder_copies": tb.graph.route_holder_copies,
        "route_overlay_copies": tb.graph.route_overlay_copies,
        "route_overlay_compactions": tb.graph.route_overlay_compactions,
        "route_row_builds": tb.graph.route_row_builds,
    }
    return stats, counters


def _assert_fastpath_parity(mult: int) -> None:
    """Whole-run equivalence of the serving fast path against the cold
    per-wave walk (``REPRO_SERVE_FASTPATH=0``): verdicts, reject reasons
    and completion times must agree to 1e-9."""
    fast, _ = _serve_once(mult)
    old = os.environ.get("REPRO_SERVE_FASTPATH")
    os.environ["REPRO_SERVE_FASTPATH"] = "0"
    try:
        cold, _ = _serve_once(mult)
    finally:
        if old is None:
            del os.environ["REPRO_SERVE_FASTPATH"]
        else:
            os.environ["REPRO_SERVE_FASTPATH"] = old
    if len(fast.requests) != len(cold.requests):
        raise AssertionError(
            f"fastpath parity x{mult}: {len(fast.requests)} requests vs "
            f"{len(cold.requests)} on the oracle path")
    import math
    for a, b in zip(fast.requests, cold.requests):
        if a.verdict != b.verdict or a.reject_reason != b.reject_reason:
            raise AssertionError(
                f"fastpath parity x{mult}: request {a.rid} "
                f"{a.verdict}/{a.reject_reason!r} vs "
                f"{b.verdict}/{b.reject_reason!r}")
        if math.isnan(a.finish) and math.isnan(b.finish):
            continue
        if abs(a.finish - b.finish) > 1e-9:
            raise AssertionError(
                f"fastpath parity x{mult}: request {a.rid} finish "
                f"{a.finish!r} vs {b.finish!r}")


def run(smoke: bool = False, check: bool = False) -> Table:
    t = Table("serve", "online serving continuum: resident-timeline loop")
    baseline = json.loads(_JSON.read_text()) if _JSON.exists() else None

    mults = [2] if smoke else [8, 64]
    counters: dict = {}
    last_stats = None
    for mult in mults:
        t0 = time.perf_counter()
        stats, counters = _serve_once(mult)
        last_stats = stats
        s = stats.summary()
        t.add(f"x{mult}_requests", s["requests"], "req",
              accepted=s["accepted"], rejected=s["rejected"],
              deferrals=s["deferrals"])
        t.add(f"x{mult}_wall_rps", s["wall_rps"], "req/s",
              wall_s=round(stats.wall_s, 3))
        t.add(f"x{mult}_served_rps", s["served_rps"], "req/s",
              offered_rps=round(s["offered_rps"], 1))
        t.add(f"x{mult}_p50_ms", s["p50_ms"], "ms")
        t.add(f"x{mult}_p99_ms", s["p99_ms"], "ms")
        t.add(f"x{mult}_p999_ms", s["p999_ms"], "ms")
        t.add(f"x{mult}_sla_attainment", s["sla_attainment"], "frac",
              **{f"sla_{k}": round(v, 4)
                 for k, v in s["sla_by_tenant"].items()})
        t.add(f"x{mult}_engine_opens", s["engine_opens"], "builds",
              n_events=s["n_events"], mapped_tasks=s["mapped_tasks"],
              total_s=round(time.perf_counter() - t0, 2))

    if smoke:
        # CI parity drill: the small-wave fast path must be whole-run
        # bit-equivalent to the cold per-wave walk
        _assert_fastpath_parity(2)
    else:
        # overload-adaptive coalescing at the largest scale (reported,
        # not gated: wave shapes are the point, wall varies with load)
        stats, _ = _serve_once(64, batch_window=AdaptiveWindow(
            max_window=0.002))
        s = stats.summary()
        hist = stats.wave_size_hist()
        t.add("x64_adaptive_wall_rps", s["wall_rps"], "req/s",
              wall_s=round(stats.wall_s, 3))
        t.add("x64_adaptive_p99_ms", s["p99_ms"], "ms",
              sla=round(s["sla_attainment"], 4))
        t.add("x64_adaptive_max_wave", max(hist), "req",
              waves=sum(hist.values()))

    gates = {f"x{mult}_{metric}": thr for mult in mults for metric, thr in (
        ("wall_rps", {"floor_ratio": 0.8}),
        ("p99_ms", {"ceil_ratio": 1.2}),
        ("sla_attainment", {"floor_delta": 0.02}),
    )}
    gates["x64_wall_rps_abs"] = {"floor_abs": _X64_WALL_RPS_FLOOR}
    # route-table copy/build counters plus the per-phase wall breakdown
    # and wave-size histogram of the largest gated run, surfaced in the
    # payload meta so baseline diffs show COW/fast-path behaviour changes
    extra_meta = {k: int(v) for k, v in counters.items()}
    if last_stats is not None:
        extra_meta["phase_wall"] = {
            k: round(v, 3) for k, v in last_stats.phase_wall.items()}
        extra_meta["wave_size_hist"] = {
            str(k): v for k, v in sorted(last_stats.wave_size_hist().items())}
    write_payload(t, _JSON, smoke, gates, extra_meta=extra_meta)
    if check and not smoke:
        msgs = [msg for mult in mults for msg in (
            check_gate(t, baseline, f"x{mult}_wall_rps", floor_ratio=0.8),
            check_gate(t, baseline, f"x{mult}_p99_ms", ceil_ratio=1.2,
                       note="seed-deterministic: the event order changed"),
            check_gate(t, baseline, f"x{mult}_sla_attainment",
                       floor_delta=0.02),
        )]
        # absolute floor on the flagship metric: the serving fast path
        # holds >=3x the PR 9 steady-state throughput regardless of
        # which baseline file is checked in
        rps = t.get("x64_wall_rps")
        if rps < _X64_WALL_RPS_FLOOR:
            msgs.append(
                f"x64_wall_rps={rps:.1f} below the absolute floor "
                f"{_X64_WALL_RPS_FLOOR} (serving fast path regressed)")
        fail_gates(t, msgs)
    return t


if __name__ == "__main__":
    args = sys.argv[1:]
    run(smoke="--smoke" in args, check="--check" in args).print_csv()
