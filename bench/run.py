"""Run one benchmark cell on the accelerator and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  One process: it builds the cell's fleet,
warms up every program the window will run and the fleet's occupancy,
measures for ``--seconds`` on the host clock (``--trace 1``: under the
profiler, for the per-layer metrics), then checks what the window decided
(``bench/check.py``).  The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and
the compared numbers under ``checks``; the compared numbers are also the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
METRICS_DIR = ROOT / "bench" / "metrics"
PHASES = ("advance", "sync", "map", "admit")


class NoDevice(Exception):
    pass


def configure_environment() -> None:
    """Before JAX loads: the compile cache inside this checkout (a fixed
    path, so every run of a cell here after the first finds its programs,
    and two checkouts never share one, even where the environment names
    another directory), and no TPU logs outside it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def accelerator(chips: int):
    """The devices the cell runs on; raises :class:`NoDevice` unless JAX's
    devices are TPUs and there are at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX's first device is {devices[0].platform!r} "
                       f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


# ---------------------------------------------------------------------------
# compile counting (the persistent cache's answers count too)
# ---------------------------------------------------------------------------
class CompileCounter:
    def __init__(self) -> None:
        self.n = 0

    def install(self) -> None:
        import jax

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# warm-up of every program shape the window can reach
# ---------------------------------------------------------------------------
def warm_programs(loop, probe) -> int:
    """Call each device entry once per shape the cell's fleet can reach:
    the slowdown aggregation at every pool bucket from 8 to 8192 (both
    slowdown models' kappa), the scan reduce at every orchestrator plan's
    widths, and its batched form at those widths for two and three rows
    (the distinct tasks of one reading).  Returns the calls made."""
    import numpy as np
    session = loop.session
    root = session.policy
    comp = session.graph.compiled()
    n_r = len(comp.rclass_names)
    kappas = {float(root.traverser.slowdown.params.superlinear),
              float(session.truth.slowdown.params.superlinear)}
    calls = 0
    agg = probe.impl["slowdown_kernel"]
    for kappa in sorted(kappas):
        b = 8
        while b <= 8192:
            agg(np.zeros((b, n_r)), np.zeros(n_r), np.zeros(b), np.zeros(b),
                kappa)
            calls += 1
            b *= 2
    seen = set()
    one, batch = probe.impl["walk_reduce"], probe.impl["walk_reduce_batch"]
    for orc in root.iter_tree():
        plan = orc._scan_plan(comp)
        n, m = len(plan.pus), len(plan.pu_lo)
        lqc = orc.config.local_query_cost
        if not n or (n, m, lqc) in seen:
            continue
        seen.add((n, m, lqc))
        cols = (plan.pu_lo, plan.pu_hi, plan.leafcnt, plan.nchild,
                plan.hopsum, plan.depth)
        one(np.ones(n, dtype=bool), np.zeros(n), *cols, lqc)
        calls += 1
        for rows in (2, 3):
            batch(np.ones((rows, n), dtype=bool), np.zeros((rows, n)),
                  *(np.stack([c] * rows) for c in cols), lqc)
            calls += 1
    return calls


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------
@dataclass
class RunRecord:
    """What a metric reader may read: the window's counts, the loop's
    phase walls over the window, the device-entry counters, and the
    trace's summary (traced runs only)."""

    cell: object
    window: object
    phase: dict
    calls: Counter
    work: dict
    device_kind: str
    setup_s: float
    trace: Optional[object] = None


def load_reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell_name: str, traced: bool) -> list:
    """The metrics this cell reports: the end-to-end ones without
    ``--trace``, the per-layer ones with it (a metric's ``workloads`` lists
    its cells; without the key, every cell that reports what it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or m["moves"] in names)]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(cell, seed: int, seconds: float, traced: bool, devices,
             stand_ins: Optional[dict] = None, on_event=print) -> dict:
    """Build, warm, measure and check one cell on ``devices``; returns the
    result object.  ``stand_ins`` puts something else under the device
    entries (see :class:`bench.probe.Probe`) or, under ``"loop"``, breaks
    the built loop: the control and the tests give it, the benchmark's
    runs never do."""
    import numpy as np

    from . import check
    from .cell import drive, make_plan, make_stream, window_result, build_loop
    from .probe import Probe

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    compiles = CompileCounter()
    compiles.install()
    probe = Probe(stand_ins, offset=seed)
    stream = make_stream(cell, seed, seconds)
    plan = make_plan(cell, stream, seconds)
    loop = build_loop(cell, stream, plan)
    if stand_ins and "loop" in stand_ins:
        stand_ins["loop"](loop)
    state: dict = {}
    probe.install()
    try:
        warm_calls = warm_programs(loop, probe)
        if traced:
            _install_spans(loop)

        def on_open(lp):
            if traced:
                import jax
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # host spans are ours alone
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=opts)
                state["span"] = jax.profiler.TraceAnnotation("bench.window")
                state["span"].__enter__()
            state["compiles0"] = compiles.n
            state["pw0"] = dict(lp.phase_wall, pace=lp.pace_wall)
            state["t_open"] = time.perf_counter()
            probe.start()

        def on_close(lp):
            probe.stop()
            state["pw1"] = dict(lp.phase_wall, pace=lp.pace_wall)
            state["compiles1"] = compiles.n
            if traced:
                import jax
                state["span"].__exit__(None, None, None)
                jax.profiler.stop_trace()

        loop.on_open, loop.on_close = on_open, on_close
        drive(loop)
    finally:
        probe.uninstall()
    setup_s = state["t_open"] - _T_START
    win = window_result(loop)
    in_window = state["compiles1"] - state["compiles0"]
    on_event(f"warm-up: {warm_calls} program calls, "
             f"{loop.waves - loop.window_waves} waves of traffic")
    on_event(f"compiles inside the window: {in_window}")
    quarters = np.histogram(
        [t for t in loop.decided_at.values() if loop.t0 <= t <= loop.t_end],
        bins=4, range=(loop.t0, loop.t_end))[0]
    on_event(f"decisions per quarter of the window: {quarters.tolist()}")
    on_event(f"window: {win.seconds!r} s, {loop.window_waves} waves, "
             f"{win.decisions} decisions, {win.attempted} attempted, "
             f"{win.failed} failed; device-entry calls "
             f"{dict(sorted(probe.calls.items()))}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    summary = None
    if traced:
        from .trace import find_xplane, summarize_file
        summary = summarize_file(find_xplane(str(TRACE_DIR)), len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the comparison: the sampled device answers, the whole run against
    # the plain reference, then the host replay of the stream
    t_check = time.perf_counter()
    numbers = check.device_answers(probe.records)
    probe.records = None
    numbers.update(check.against_reference(cell, stream, loop))
    t_ref = time.perf_counter()
    replay = _host_replay(cell, stream, seconds,
                          loop.waves - loop.window_waves // 2)
    numbers.update(check.trajectory(loop, replay))
    on_event(f"check: {time.perf_counter() - t_check!r} s, of which the "
             f"reference {t_ref - t_check!r} s; "
             f"{sum(len(w.readings) for w in loop.trail)} readings in "
             f"{len(loop.trail)} waves followed")
    lim = check.limits()
    correct = check.judge(numbers, lim) and in_window == 0

    phase = {k: state["pw1"][k] - state["pw0"][k] for k in PHASES}
    # the wave entry's wall holds a paced window's waits for due times:
    # they are idle time, not admission work
    phase["admit"] -= state["pw1"]["pace"] - state["pw0"]["pace"]
    rec = RunRecord(cell=cell, window=win, phase=phase,
                    calls=Counter(probe.calls), work=probe.work,
                    device_kind=devices[0].device_kind, setup_s=setup_s,
                    trace=summary)
    metrics = {}
    for m in cell_metrics(spec, cell.name, traced):
        v = load_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {k: {"value": numbers.get(k), "limit": lim[k]}
                     for k in lim}
    if in_window:
        out["checks"]["compiles_in_window"] = {"value": in_window,
                                               "limit": 0}
    return out


def _install_spans(loop) -> None:
    """Profiler spans around the loop's phases, from the harness's side of
    each call: ``bench.advance`` (timeline), ``bench.sync`` (completion
    reconciliation), ``bench.admit`` (the wave entry), inside it
    ``bench.map`` (the walk) and ``bench.pace`` (a paced window waiting
    for the next due time)."""
    import jax
    TA = jax.profiler.TraceAnnotation

    def spanned(name, fn):
        def call(*a, **kw):
            with TA(name):
                return fn(*a, **kw)
        return call

    loop.engine.advance = spanned("bench.advance", loop.engine.advance)
    loop._sync_completions = spanned("bench.sync", loop._sync_completions)
    loop.session.map_pending = spanned("bench.map", loop.session.map_pending)
    loop._admit_wave = spanned("bench.admit", loop._admit_wave)
    loop.pace_span = lambda: TA("bench.pace")


def _host_replay(cell, stream, seconds, waves):
    """The same stream through the program with its float64 host paths
    selected, stopped after ``waves`` wave entries."""
    from repro.core import slowdown
    from repro.kernels import walk_kernel

    from .cell import WindowPlan, build_loop, drive

    saved = (slowdown._AGGREGATE, walk_kernel._AUTO_JAX)
    slowdown._AGGREGATE = slowdown._aggregate_np
    walk_kernel._AUTO_JAX = False
    try:
        plan = WindowPlan(warm_until=float("inf"), seconds=seconds,
                          paced_rate=None, sim_rate=stream.sim_rate,
                          stop_after=waves)
        loop = build_loop(cell, stream, plan)
        drive(loop)
    finally:
        slowdown._AGGREGATE, walk_kernel._AUTO_JAX = saved
    return loop


def print_checks(out: dict) -> None:
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    forced = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if forced:
        print(f"bench: refusing to run with {forced} set: the benchmark "
              "times the implementations the program selects itself",
              file=sys.stderr)
        return 2
    configure_environment()
    from .cell import HarnessError, find_cell
    try:
        cell = find_cell(args.workload)
        devices = accelerator(cell.chips)
        import repro.kernels  # noqa: F401  (the program's device set-up)
    except (NoDevice, HarnessError, ImportError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(f"device: {d.platform} {d.device_kind} x{len(devices)}")
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices)
    except HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    print_checks(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
