"""One cell of the benchmark: the fleet, the traffic, the loop, the window.

A cell is a configuration (``bench/configs/<name>.json``: fleet, sources,
the request, admission, churn) under a traffic mix
(``bench/traffic/<name>.json``: mode, origins, rate).  ``BENCHMARK.json``
names both; nothing here knows any cell by name.

A request is a DAG of stages (:func:`request_stages`): a configuration
gives them under ``request.stages`` (kind, predecessors, bytes in and out,
pinned to the origin edge or not, a deadline per origin edge kind), or as
a mining ``reading``, whose tasks are independent stages that share one
deadline.  Its sources are sensors at ``reading.hz`` (one Poisson stream)
or, under ``sources``, one periodic source on every edge at a rate per
edge kind.  One request gets one final verdict: that is a decision.

The loop under test is the program's ``ServeLoop`` driven through
:class:`BenchLoop`, which overrides only the wave entry ``_admit_wave`` to
pace due times, stamp each reading's final verdict on the host clock and
close the window.  Every decision is the program's own work:
``SchedulerSession.map_pending`` -> ``Orchestrator.map_batch`` (the walk,
its scan reduce, the slowdown model and its kernel) -> ``TimelineEngine``.
"""
from __future__ import annotations

import contextlib
import json
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

from . import yardstick

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"

# a replay's stream holds enough readings to decide this many per second
# through the window: 36x the rate measured on one TPU v5e, so a faster
# program still never runs it dry
REPLAY_CEILING_PER_S = 2000


@dataclass
class ReadingRecord:
    """One request in one wave, as the program decided it: its tasks, in
    stage order, as ``(uid, kind, chosen PU, predicted total, charged
    overhead)`` and its verdict after the wave (``accepted``,
    ``deferred``, ``rejected``)."""

    rid: int
    origin: str
    defers: int                    # deferrals before this wave
    tasks: list
    verdict: str


@dataclass
class WaveRecord:
    now: float
    readings: list


class WindowClosed(Exception):
    """Raised from the wave entry to stop ``ServeLoop.run`` at the window's
    end (or, in a replay, after the same number of waves)."""


class HarnessError(Exception):
    """A run that cannot give a reading (the stream ran dry, a file is
    missing): an error, never a number."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in spec["configs"] if c["name"] == w["config"]), None)
    if conf is None:
        raise HarnessError(f"workload {name!r} names configuration "
                           f"{w['config']!r}, which BENCHMARK.json lacks")
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=int(w["chips"]), config=load_json(root / conf["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"))


# ---------------------------------------------------------------------------
# the request and its sources, as the configuration states them
# ---------------------------------------------------------------------------
def request_stages(conf: dict) -> list[dict]:
    """The request's stages in order, each with ``kind``, ``preds``
    (indices of earlier stages), ``input_bytes``, ``output_bytes``,
    ``deadline_s`` (seconds, or seconds per origin edge kind) and, where
    the configuration states it, ``pinned``."""
    if "request" in conf:
        return conf["request"]["stages"]
    rd = conf["reading"]
    return [{"kind": k, "preds": [], "input_bytes": rd["input_bytes"],
             "output_bytes": rd["output_bytes"],
             "deadline_s": rd["deadline_s"]} for k in rd["tasks"]]


def stage_deadline(stage: dict, edge_kind: str) -> float:
    dl = stage["deadline_s"]
    return float(dl[edge_kind] if isinstance(dl, dict) else dl)


def returns_to_pinned(stages: list[dict]) -> list[bool]:
    """Per stage: whether a pinned stage consumes its output, which must
    then travel back to the origin edge."""
    out = [False] * len(stages)
    for st in stages:
        if st.get("pinned"):
            for p in st["preds"]:
                out[p] = True
    return out


def request_rate(conf: dict) -> float:
    """Requests per simulated second that the configuration's sources
    offer."""
    src = conf.get("sources")
    if src is None:
        return conf["sensors"] * conf["reading"]["hz"]
    return float(sum(n * src["fps"][kind]
                     for kind, n in conf["fleet"]["edges"].items()))


# ---------------------------------------------------------------------------
# the stream: arrival instants and origins, all drawn from the seed
# ---------------------------------------------------------------------------
def seed_streams(seed: int, n: int = 4) -> list[int]:
    """Independent integer seeds for the stream's parts (arrivals, origins,
    Zipf ranks, churn), derived from one run seed of any size or sign."""
    ss = np.random.SeedSequence(abs(int(seed)))
    return [int(c.generate_state(1, np.uint64)[0] >> 1)
            for c in ss.spawn(n)]


@dataclass
class Stream:
    """The requests a run can draw on, in arrival order: simulated arrival
    instants (a Poisson superposition of the sensors, or the merged
    periodic sources) and each request's origin edge.  ``times`` is the
    arrival-process surface ``ServeLoop`` reads."""

    arrivals: np.ndarray
    origins: list
    sim_rate: float
    churn: list = field(default_factory=list)   # (t, entries) per wave

    def times(self, horizon: float) -> np.ndarray:
        return self.arrivals[self.arrivals < horizon]

    @property
    def horizon(self) -> float:
        return float(self.arrivals[-1]) + 1.0 / self.sim_rate


def readings_needed(cell: Cell, seconds: float) -> int:
    """Requests the stream must hold so that no run of ``seconds`` can
    run dry: the warm-up, then the window at the traffic's ceiling rate."""
    tr = cell.traffic
    rate = (tr["decisions_per_s"] if tr["mode"] == "paced"
            else REPLAY_CEILING_PER_S)
    sim_rate = request_rate(cell.config)
    warm = tr["warmup_sim_s"] * sim_rate
    return int(math.ceil(warm + 1.25 * rate * seconds + 64))


def _sensor_origins(conf: dict, tr: dict, edges: list, n: int,
                    s_org: int, s_rank: int) -> list:
    """Each of ``n`` readings' origin edge, by the traffic's origin law."""
    rng = np.random.default_rng(s_org)
    if tr["origins"] == "sensors":
        attach = yardstick.sensor_edges(edges, conf["sensors"],
                                        conf["sensor_ring_weights"])
        sensor = rng.integers(0, conf["sensors"], size=n)
        return [attach[s] for s in sensor.tolist()]
    if tr["origins"] == "zipf":
        ranked = yardstick.zipf_ranked_edges(
            edges, np.random.default_rng(s_rank))
        ranks = yardstick.zipf_draw(len(ranked), tr["zipf_s"], n, rng)
        return [ranked[r] for r in ranks.tolist()]
    raise HarnessError(f"unknown origin law {tr['origins']!r}")


def make_stream(cell: Cell, seed: int, seconds: float) -> Stream:
    conf, tr = cell.config, cell.traffic
    s_arr, s_org, s_rank, s_churn = seed_streams(seed)
    sim_rate = request_rate(conf)
    n = readings_needed(cell, seconds)
    horizon = n / sim_rate
    edges = yardstick.edge_names(conf["fleet"]["edges"])
    if (tr["origins"] == "headsets") != ("sources" in conf):
        raise HarnessError(f"origin law {tr['origins']!r} does not fit the "
                           "configuration: 'headsets' takes its periodic "
                           "sources, the other laws its sensors")
    if tr["origins"] == "headsets":
        src = conf["sources"]
        arrivals, origins = yardstick.periodic_sources(
            edges, src["fps"], horizon, np.random.default_rng(s_arr))
    else:
        arrivals = yardstick.PoissonArrivals(sim_rate,
                                             seed=s_arr).times(horizon)
        origins = _sensor_origins(conf, tr, edges, len(arrivals), s_org,
                                  s_rank)
    if len(arrivals) < 2:
        raise HarnessError("the stream holds no requests")
    churn = []
    ch = conf.get("churn")
    if ch:
        up = {f"link_{name}": conf["fleet"]["uplink_bytes_per_s"]
              for name, _ in edges}
        period = ch["period_s"]
        n_waves = int(horizon / period) + 1
        waves = yardstick.wireless_churn_schedule(
            up, n_waves, seed=s_churn, churn_frac=ch["churn_frac"],
            min_scale=ch["min_scale"], max_scale=ch["max_scale"])
        churn = [((k + 1) * period, w) for k, w in enumerate(waves)]
    return Stream(arrivals=arrivals, origins=origins, sim_rate=sim_rate,
                  churn=churn)


# ---------------------------------------------------------------------------
# the loop under test
# ---------------------------------------------------------------------------
@dataclass
class WindowPlan:
    """When the window opens and how due times are paced."""

    warm_until: float              # simulated instant the window opens at
    seconds: float
    paced_rate: Optional[float]    # readings per wall second, or None
    sim_rate: float
    stop_after: Optional[int] = None   # replay: stop after this many waves

    @property
    def stretch(self) -> float:
        """Wall seconds per simulated second in a paced window."""
        return self.sim_rate / self.paced_rate


def make_loop_class():
    """``BenchLoop`` is defined against the program's ``ServeLoop``, which
    is imported only once JAX has been configured."""
    from repro.core import ServeLoop

    class BenchLoop(ServeLoop):
        """``ServeLoop`` with the one wave entry overridden: pacing, verdict
        timestamps, and the window's close."""

        def __init__(self, *args, plan: WindowPlan, **kw) -> None:
            super().__init__(*args, **kw)
            self.plan = plan
            self.in_window = False
            self.t0 = self.t_end = self.sim0 = math.nan
            self.waves = 0             # completed wave entries
            self.window_waves = 0      # of which in the window
            self.decided_at: dict[int, float] = {}
            self.pace_wall = 0.0       # seconds slept waiting for due times
            self.on_open = None        # callback(loop) at the window's open
            self.on_close = None       # callback(loop) at the window's close
            self.pace_span = contextlib.nullcontext   # around pacing waits
            self.trail: list[WaveRecord] = []   # every wave, for the check
            self.stop_at = math.nan    # the wave instant the run stopped at
            self._mapped: dict = {}
            inner = self.session.map_pending

            def map_pending(*a, **kw):
                self._mapped = inner(*a, **kw)
                return self._mapped

            self.session.map_pending = map_pending

        def _open(self, now: float) -> None:
            if self.on_open is not None:
                self.on_open(self)
            self.in_window = True
            self.sim0 = now
            self.t0 = time.perf_counter()
            self.t_end = self.t0 + self.plan.seconds

        def _close(self, now: float) -> None:
            if self.on_close is not None:
                self.on_close(self)
            self.stop_at = now
            raise WindowClosed

        def _admit_wave(self, now, wave, events):
            plan = self.plan
            if plan.stop_after is not None and self.waves >= plan.stop_after:
                self.stop_at = now
                raise WindowClosed
            if not self.in_window and plan.stop_after is None \
                    and now >= plan.warm_until:
                self._open(now)
            if self.in_window:
                if plan.paced_rate is not None:
                    due = self.t0 + (now - self.sim0) * plan.stretch
                    if due >= self.t_end:
                        self._close(now)
                    before = time.perf_counter()
                    if due > before:
                        with self.pace_span():
                            time.sleep(due - before)
                    if due > before:
                        self.pace_wall += time.perf_counter() - before
                elif time.perf_counter() >= self.t_end:
                    self._close(now)
            pending = [r for r in wave if r.verdict == "pending"]
            defers = [r.defers for r in pending]
            self._mapped = {}
            super()._admit_wave(now, wave, events)
            t = time.perf_counter()
            for r in pending:
                if r.verdict != "pending":
                    self.decided_at[r.rid] = t
            self._record(now, pending, defers)
            self.waves += 1
            if self.in_window:
                self.window_waves += 1

        def _record(self, now, pending, defers) -> None:
            got = self._mapped
            rs = []
            for r, d0 in zip(pending, defers):
                tasks = []
                for t in r.tasks:
                    res = got.get(t.uid)
                    tasks.append((t.uid, t.kind,
                                  None if res is None else res.pu,
                                  math.nan if res is None
                                  else res.prediction.total,
                                  0.0 if res is None else res.overhead))
                verdict = ("deferred" if r.verdict == "pending"
                           else r.verdict)
                rs.append(ReadingRecord(rid=r.rid, origin=r.tasks[0].origin,
                                        defers=d0, tasks=tasks,
                                        verdict=verdict))
            self.trail.append(WaveRecord(now=now, readings=rs))

    return BenchLoop


def request_builder(conf: dict, origins: list):
    """``make_request(k, t)`` for the program's ``TenantSpec``: request
    ``k`` as a ``TaskGraph`` of the configuration's stages, each released
    at ``t`` from ``origins[k]`` with its deadline for that edge's kind, a
    dependency on each predecessor, and the attributes the program reads
    for a pinned stage (``pinned``) and for an output that returns to one
    (``succ_pinned_bytes``), as ``core.workloads.vr_frame`` sets them."""
    from repro.core import TaskGraph, make_task

    stages = request_stages(conf)
    back = returns_to_pinned(stages)
    kind_of = dict(yardstick.edge_names(conf["fleet"]["edges"]))

    def make_request(k: int, t: float):
        g = TaskGraph(f"reading#{k}")
        origin = origins[k]
        tasks = []
        for st, ret in zip(stages, back):
            task = make_task(st["kind"], origin=origin,
                             deadline=stage_deadline(st, kind_of[origin]),
                             input_bytes=st["input_bytes"],
                             output_bytes=st["output_bytes"],
                             release_time=t)
            if "pinned" in st:
                task.attrs["pinned"] = bool(st["pinned"])
            if ret:
                task.attrs["succ_pinned_bytes"] = task.output_bytes
            g.add(task, deps=[tasks[p] for p in st["preds"]])
            tasks.append(task)
        return g

    return make_request


def build_loop(cell: Cell, stream: Stream, plan: WindowPlan):
    """The configured fleet and its loop (not yet run)."""
    from repro.core import (Churn, TenantSpec, build_orchestrators,
                            build_testbed, ground_truth_traverser,
                            heye_traverser)
    from repro.serve.admission import AdmissionController

    conf = cell.config
    fl = conf["fleet"]
    tb = build_testbed(edge_counts=dict(fl["edges"]),
                       server_counts=dict(fl["servers"]))
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    make_request = request_builder(conf, stream.origins)
    adm = conf["admission"]
    tenant = TenantSpec("readings", stream, make_request,
                        sla=conf.get("reading", {}).get("deadline_s"))
    loop = make_loop_class()(
        tb.graph, root, [tenant],
        truth=ground_truth_traverser(tb.graph, conf["truth_seed"]),
        admission=AdmissionController(slack=adm["slack"],
                                      defer_delay=adm["defer_delay_s"],
                                      max_defers=adm["max_defers"]),
        batch_window=adm["batch_window_s"],
        horizon=stream.horizon,
        interventions=[(t, Churn(bandwidth=w)) for t, w in stream.churn],
        plan=plan)
    return loop


def make_plan(cell: Cell, stream: Stream, seconds: float) -> WindowPlan:
    tr = cell.traffic
    if tr["mode"] not in ("replay", "paced"):
        raise HarnessError(f"unknown traffic mode {tr['mode']!r}")
    return WindowPlan(warm_until=float(tr["warmup_sim_s"]),
                      seconds=float(seconds),
                      paced_rate=(float(tr["decisions_per_s"])
                                  if tr["mode"] == "paced" else None),
                      sim_rate=stream.sim_rate)


def drive(loop) -> None:
    """Run the loop until its window closes; a stream that runs out first
    is an error."""
    try:
        loop.run()
    except WindowClosed:
        return
    raise HarnessError("the stream ran dry before the window closed")


# ---------------------------------------------------------------------------
# what the window did
# ---------------------------------------------------------------------------
@dataclass
class WindowResult:
    seconds: float
    decisions: int                 # final verdicts inside the window
    attempted: int
    failed: int


def window_result(loop) -> WindowResult:
    t0, t_end = loop.t0, loop.t_end
    decided = {rid for rid, t in loop.decided_at.items() if t0 <= t <= t_end}
    by_rid = {r.rid: r for r in loop.requests}
    plan = loop.plan
    if plan.paced_rate is not None:
        # every reading due in the window, by its first arrival
        arr = loop.tenants[0].arrivals.arrivals
        due = t0 + (arr - loop.sim0) * plan.stretch
        sel = np.flatnonzero((arr >= loop.sim0) & (due < t_end)).tolist()
        failed = sum(1 for k in sel if k not in decided
                     or by_rid[k].verdict != "accepted")
        attempted = len(sel)
    else:
        taken = [r.rid for r in loop.requests if r.arrival >= loop.sim0]
        attempted = len(taken)
        failed = sum(1 for k in taken
                     if k not in decided or by_rid[k].verdict != "accepted")
    return WindowResult(seconds=t_end - t0, decisions=len(decided),
                        attempted=attempted, failed=failed)
