#!/usr/bin/env python3
"""Smoke run of the H-EYE scheduler's main path on one TPU chip.

    python chip_smoke.py            # from the root of a checkout

Everything runs in this one process; it starts no children.  Phases:

* serve: the mult=64 ``ServeLoop`` run of ``benchmarks/serve.py``: the
  mining fleet (832 devices, 4224 PUs) with a Poisson ``svm`` tenant and a
  diurnal ``mlp`` tenant, about 1142 requests.
* walk: the mult=128 whole-fleet batch walk of ``benchmarks/des.py``
  (1664 devices, 8448 PUs, 4608 mining tasks) through
  ``SchedulerSession.submit`` + ``map_pending`` + ``execute``.
* reference: both phases again in this process on the float64 numpy
  paths, by resetting the module-level kernel selections.  Verdicts and
  placements must be identical; where a placement differs, both
  competing keys are printed, and they must be within 1e-6 relative (a
  true fp32 near-tie).  Finish times, the p99 and the Fig. 14 overhead
  must agree within 1e-5 relative.

The earlier lines report set-up facts: the device, the implementation
each dispatch point selected, kernel call counts, compile requests (and
how many the persistent cache answered) and wall seconds per phase.
They are not benchmark numbers.  The last line is ``{"ok": true,
"device": {...}}``.  The script exits non-zero, and prints no such line,
unless JAX's first device is a TPU, or when any ``REPRO_*`` variable is
set.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from collections import Counter
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVE_MULT = 64
WALK_MULT = 128
NEAR_TIE = 1e-6          # competing placement keys: a true fp32 near-tie
AGREE = 1e-5             # finish times, p99, Fig. 14 overhead


class SmokeFailure(Exception):
    pass


@dataclass
class Row:
    """One request (serve) or task (walk) as the comparison sees it."""
    label: str
    verdict: str
    pus: tuple
    keys: tuple            # prediction totals of the chosen placements
    finish: float


@dataclass
class PhaseResult:
    rows: list
    p99: float
    overhead: float        # Fig. 14: mean scheduling overhead / exec time
    note: str


def _rel(a: float, b: float) -> float:
    if a == b or (math.isnan(a) and math.isnan(b)):
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def _keys(results: dict, tasks) -> tuple:
    out = []
    for t in tasks:
        r = results.get(t.uid)
        out.append(math.nan if r is None else r.prediction.total)
    return tuple(out)


def serve_phase() -> PhaseResult:
    from benchmarks.serve import _serve_loop
    loop, _ = _serve_loop(SERVE_MULT)
    stats = loop.run()
    if stats.engine_opens != 1:
        raise SmokeFailure(f"serve: {stats.engine_opens} TimelineEngine "
                           "builds (the resident timeline opens once)")
    results = loop.session.results
    rows = [Row(f"request {r.rid}", f"{r.verdict}/{r.reject_reason}",
                tuple(t.assigned_pu for t in r.tasks),
                _keys(results, r.tasks), r.finish)
            for r in stats.requests]
    run = loop.session.finalize_online()
    s = stats.summary()
    return PhaseResult(
        rows, stats.latency_percentiles()[99.0],
        run.mean_overhead_ratio(loop.session.cfg),
        f"{s['requests']} requests ({s['accepted']} accepted, "
        f"{s['rejected']} rejected), engine_opens={stats.engine_opens}, "
        f"{s['n_events']} events")


def walk_phase() -> PhaseResult:
    from benchmarks.scaling import mining_counts
    from repro.core import (SchedulerSession, build_orchestrators,
                            build_testbed, ground_truth_traverser,
                            heye_traverser, mining_workload)
    ec, sc = mining_counts(WALK_MULT)
    tb = build_testbed(edge_counts=ec, server_counts=sc)
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    session = SchedulerSession(tb.graph, root,
                               truth=ground_truth_traverser(tb.graph, 0))
    cfg = mining_workload(tb, n_sensors=12 * WALK_MULT, n_readings=1)
    session.submit(cfg)
    session.map_pending()
    run = session.execute()
    declined = set(session.unmapped)
    tasks = list(cfg)
    rows = [Row(f"task {i} ({t.kind} from {t.origin})",
                "declined" if t.uid in declined else "mapped",
                (session.mapping.get(t.uid),),
                _keys(session.results, [t]), run.timeline.finish[t.uid])
            for i, t in enumerate(tasks)]
    return PhaseResult(
        rows, run.latency_percentiles(cfg)[99.0],
        run.mean_overhead_ratio(cfg),
        f"{len(tasks)} tasks on {sum(ec.values()) + sum(sc.values())} "
        f"devices / {len(tb.graph.compiled().pu_names)} PUs, "
        f"{len(declined)} declined")


def compare(phase: str, dev: PhaseResult, ref: PhaseResult) -> str:
    """Hold the chip run to the float64 host reference; returns a summary
    line, raises on disagreement."""
    if len(dev.rows) != len(ref.rows):
        raise SmokeFailure(f"{phase}: {len(dev.rows)} rows on the chip vs "
                           f"{len(ref.rows)} on the host reference")
    ties = 0
    worst = 0.0
    for a, b in zip(dev.rows, ref.rows):
        if a.verdict != b.verdict:
            raise SmokeFailure(
                f"{phase}: {a.label} verdict {a.verdict} on the chip vs "
                f"{b.verdict} on the host (keys {a.keys!r} vs {b.keys!r})")
        if a.pus != b.pus:
            print(f"{phase}: {a.label} placed on {a.pus} with key "
                  f"{a.keys!r} on the chip, on {b.pus} with key {b.keys!r} "
                  "on the host")
            if not all(_rel(x, y) <= NEAR_TIE
                       for x, y in zip(a.keys, b.keys)):
                raise SmokeFailure(f"{phase}: {a.label} placement differs "
                                   "and the keys are not an fp32 near-tie")
            ties += 1
            continue
        d = _rel(a.finish, b.finish)
        worst = max(worst, d)
        if d > AGREE:
            raise SmokeFailure(f"{phase}: {a.label} finishes at {a.finish!r}"
                               f" on the chip vs {b.finish!r} on the host")
    for name, x, y in (("p99", dev.p99, ref.p99),
                       ("Fig. 14 overhead", dev.overhead, ref.overhead)):
        if _rel(x, y) > AGREE:
            raise SmokeFailure(f"{phase}: {name} {x!r} on the chip vs {y!r}"
                               " on the host")
    return (f"agreement {phase}: {len(dev.rows)} rows, verdicts identical, "
            f"{ties} placement near-ties, max finish rel diff {worst!r}, "
            f"p99 {dev.p99!r} vs {ref.p99!r}, Fig. 14 overhead "
            f"{dev.overhead!r} vs {ref.overhead!r}")


def smoke() -> None:
    """Run the device phases, then the host reference, and compare."""
    import jax
    from repro.core import slowdown
    from repro.kernels import slowdown_kernel, walk_kernel

    compiles: Counter = Counter()

    # the backend-compile event fires for every compile request, also
    # for those the persistent cache answers
    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["requests"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    calls: Counter = Counter()
    buckets: set = set()
    aggregate = slowdown._select_aggregate()
    use_jax = walk_kernel._use_jax()
    print(f"dispatch: slowdown aggregate -> {aggregate.__module__}."
          f"{aggregate.__qualname__}")
    print("dispatch: walk scan reduce -> "
          + ("jitted jax reduce" if use_jax else "numpy reference"))
    if aggregate is not slowdown_kernel.slowdown_factors_pallas:
        raise SmokeFailure("the slowdown aggregate did not select the "
                           "Pallas kernel")
    if not use_jax:
        raise SmokeFailure("the walk reduce did not select the jax path")

    def counted_aggregate(x, *args):
        calls["slowdown_kernel"] += 1
        buckets.add(slowdown_kernel.bucket(len(x)))
        return aggregate(x, *args)

    reduce_one = walk_kernel._jax_reduce()
    reduce_batch = walk_kernel._jax_reduce_batch()

    def counted(fn, name):
        def call(*args):
            calls[name] += 1
            return fn(*args)
        return call

    slowdown._AGGREGATE = counted_aggregate
    walk_kernel._JAX_REDUCE = counted(reduce_one, "walk_reduce")
    walk_kernel._JAX_REDUCE_BATCH = counted(reduce_batch,
                                            "walk_reduce_batch")

    def timed(name, fn):
        c0, k0 = Counter(compiles), Counter(calls)
        t0 = time.perf_counter()
        res = fn()
        wall = time.perf_counter() - t0
        dc, dk = compiles - c0, calls - k0
        print(f"phase {name}: {res.note}; wall {wall!r} s; compile requests "
              f"{dc['requests']} ({dc['cache_hits']} from the persistent "
              f"cache); device kernel calls {dict(sorted(dk.items()))}")
        return res, dk

    dev_serve, k_serve = timed("serve (device)", serve_phase)
    dev_walk, k_walk = timed("walk (device)", walk_phase)
    for name, k in (("serve", k_serve), ("walk", k_walk)):
        if not k["slowdown_kernel"] or not (k["walk_reduce"]
                                            + k["walk_reduce_batch"]):
            raise SmokeFailure(f"{name}: a device kernel was never called "
                               f"({dict(k)})")
    n_shapes = slowdown_kernel.factors_call._cache_size()
    print(f"slowdown kernel: {n_shapes} shapes compiled for "
          f"{len(buckets)} buckets reached {sorted(buckets)}")
    if n_shapes > len(buckets):
        raise SmokeFailure("the slowdown kernel compiled more shapes than "
                           "there are buckets")
    print(f"walk reduce: {reduce_one._cache_size()} shapes compiled, "
          f"batched form {reduce_batch._cache_size()}")

    # the float64 host reference: reset the module-level selections
    slowdown._AGGREGATE = slowdown._aggregate_np
    walk_kernel._AUTO_JAX = False
    ref_serve, k = timed("serve (host reference)", serve_phase)
    ref_walk, k2 = timed("walk (host reference)", walk_phase)
    if k or k2:
        raise SmokeFailure("the host reference called a device kernel")
    print(compare("serve", dev_serve, ref_serve))
    print(compare("walk", dev_walk, ref_walk))


def main() -> int:
    forced = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if forced:
        print(f"chip_smoke: refusing to run with {forced} set: the run "
              "checks the selection the program makes itself",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; there is no CPU fallback",
              file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        import benchmarks.serve  # noqa: F401
        import repro.kernels     # noqa: F401  (places the compile cache)
    except ImportError as e:
        print(f"chip_smoke: the repository is not next to this script: {e}",
              file=sys.stderr)
        return 1
    print(f"platform: {dev.platform}; device_kind: {dev.device_kind}; "
          f"devices: {len(devices)}; jax {jax.__version__}")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    try:
        smoke()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
