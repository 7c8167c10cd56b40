"""Plain float64 reference of what the serving loop decides.

Written from the paper's definitions, with the testbed as data
(``bench/testbed.json``) and no import of the program.  It follows the
program's run wave by wave, as a served model's reference follows the
served tokens: each wave's instant, its requests, the PU the program chose
for each task and the program's verdict are taken as given, and everything
else is computed here.  A request is a DAG of stages
(``bench.cell.request_stages``); a wave walks its requests' stages level
by level in dependency order, each level in task order, as the session's
dependency frontier maps them:

* the Alg. 1 walk of each task over the reference's own belief ledger:
  the origin device's PUs first, then the other devices of its cluster,
  then the other clusters, then the least-bad PU anywhere; at each level
  the feasible PU with the least predicted total (first in visit order on
  ties).  A stage pinned to its origin may use that device's PUs alone.
  A PU is feasible when it supports the task, its predicted total meets
  the stage's deadline, and no task already on its device would then miss
  its own (Alg. 1 line 15).  The predicted total is the inbound transfer
  (the stage's input from its predecessors' devices, or from the origin
  where it has none; the slowest leg), plus, off the origin, the return of
  its output to the origin where a pinned stage consumes it, plus queueing
  behind a full PU, plus standalone x slowdown;
* the decoupled slowdown factor (section 3.4) of the task and of every
  task it joins;
* the walk's scheduling overhead (Fig. 14), charged to the release time;
* the admission verdict, per request: accept unless a stage's predicted
  total passes its deadline x slack, which defers the request once and
  then rejects it;
* the ground-truth timeline: a discrete-event simulation of the accepted
  tasks' transfers (a root stage's input from the origin, each other
  stage's inputs as its predecessors' outputs from their devices; links
  shared equally, then the route latency) and compute (a stage starts once
  it is released and every input has landed; slowdown repriced whenever a
  device's set of running tasks changes, per-task irregularity noise drawn
  at start, PUs queueing past their tenancy), and bandwidth churn where
  the configuration has it.

:func:`compare` returns the compared numbers (``bench/check.py``).
"""
from __future__ import annotations

import heapq
import itertools
import math
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

from .cell import request_stages, returns_to_pinned, stage_deadline

TESTBED = Path(__file__).resolve().parent / "testbed.json"

CTOL = 1e-15        # compute work left below which a job has finished
XTOL = 1e-6         # transfer bytes left below which a transfer has landed


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------
@dataclass
class PU:
    gid: int
    dev: int
    pos: int                       # position on its device
    name: str
    cls: str
    profile: str
    tenancy: int
    mem_cap: float


@dataclass
class Device:
    idx: int
    name: str
    kind: str
    cluster: int
    pus: list                      # global PU ids, in visit order
    link: str
    ncr: list                      # ncr[i][j]: rclass where PU i meets j


class Fleet:
    """Devices in the orchestrator tree's order (the edge cluster, then the
    server cluster), their PUs, and the network."""

    def __init__(self, conf: dict, tb: dict) -> None:
        self.tb = tb
        net = tb["network"]
        self.devices: list[Device] = []
        self.pus: list[PU] = []
        self.by_name: dict[str, int] = {}
        self.bw: dict[str, float] = {"router--wan": net["wan_bytes_per_s"]}
        self.lat: dict[str, float] = {"router--wan": net["wan_latency_s"]}
        fl = conf["fleet"]
        n_edge = 0
        for kind, n in fl["edges"].items():
            for _ in range(n):
                self._add(f"{kind}_e{n_edge}", kind, 0, net["lan_bytes_per_s"],
                          net["lan_latency_s"])
                n_edge += 1
        n_srv = 0
        for kind, n in fl["servers"].items():
            for _ in range(n):
                self._add(f"{kind}_s{n_srv}", kind, 1, net["wan_bytes_per_s"],
                          net["wan_latency_s"])
                n_srv += 1
        self.clusters = [[d.idx for d in self.devices if d.cluster == c]
                         for c in (0, 1)]
        self._sa: dict = {}            # (kind, pu gid) -> standalone s
        self._tt: dict = {}            # (src, dst, bytes) -> transfer s
        sd = tb["slowdown"]
        self.beta = sd["beta"]
        self.mt_beta = sd["mt_beta"]
        self.kappa = float(sd["kappa"])
        self.lqc = float(tb["local_query_cost_s"])

    def _add(self, name: str, kind: str, cluster: int, bw: float,
             lat: float) -> None:
        st = self.tb["structures"][self.tb["kinds"][kind]]
        rc = self.tb["rclass"]
        d = Device(idx=len(self.devices), name=name, kind=kind,
                   cluster=cluster, pus=[], link=f"link_{name}", ncr=[])
        paths = []
        for pos, p in enumerate(st["pus"]):
            pu = PU(gid=len(self.pus), dev=d.idx, pos=pos,
                    name=f"{name}.{p['name']}", cls=p["class"],
                    profile=p["profile"], tenancy=int(p["tenancy"]),
                    mem_cap=float(p.get("mem_cap", math.inf)))
            self.pus.append(pu)
            self.by_name[pu.name] = pu.gid
            d.pus.append(pu.gid)
            paths.append(p["path"])
        for pa in paths:
            row = []
            for pb in paths:
                hit = next((r for r in pa if r in pb), None)
                row.append(rc[hit] if hit is not None else None)
            d.ncr.append(row)
        self.devices.append(d)
        self.bw[d.link] = bw
        self.lat[d.link] = lat

    def device_of(self, name: str) -> int:
        idx = self.__dict__.get("_di")
        if idx is None:
            idx = self._di = {d.name: d.idx for d in self.devices}
        return idx[name]

    def route(self, src: int, dst: int) -> list[str]:
        a, b = self.devices[src], self.devices[dst]
        if a.cluster == b.cluster:
            return [a.link, b.link]
        return [a.link, "router--wan", b.link]

    def set_bandwidth(self, link: str, bw: float) -> None:
        self.bw[link] = float(bw)
        self._tt.clear()

    def transfer_time(self, src: int, dst: int, nbytes: float) -> float:
        if src == dst:
            return 0.0
        hit = self._tt.get((src, dst, nbytes))
        if hit is None:
            hit = self._tt[(src, dst, nbytes)] = self._transfer(src, dst,
                                                                nbytes)
        return hit

    def _transfer(self, src: int, dst: int, nbytes: float) -> float:
        links = self.route(src, dst)
        lat = 0.0
        for ln in links:
            lat += self.lat[ln]
        bw = min(self.bw[ln] for ln in links)
        return lat + (nbytes * (1.0 / bw) if nbytes > 0 else 0.0)

    def standalone(self, kind: str, pu: PU) -> Optional[float]:
        key = (kind, pu.gid)
        if key not in self._sa:
            ms = self.tb["standalone_ms"].get(kind, {}).get(
                self.devices[pu.dev].kind, {}).get(pu.profile)
            self._sa[key] = None if ms is None else ms * 1e-3
        return self._sa[key]

    def least_standalone(self, kind: str, d: int) -> float:
        key = (kind, -1 - d)
        if key not in self._sa:
            sas = [self.standalone(kind, self.pus[g])
                   for g in self.devices[d].pus]
            sas = [x for x in sas if x is not None]
            self._sa[key] = min(sas) if sas else math.inf
        return self._sa[key]

    def factor(self, pu: PU, u: float, m: float, mt_x: float,
               res: dict) -> float:
        """Slowdown of a task with pu-usage ``u`` and capped memory usage
        ``m`` on ``pu``, with ``mt_x`` pu-usage of co-tenants on the same
        PU and ``res[rclass]`` memory usage of co-runners meeting it
        there."""
        k = self.kappa
        f = 1.0
        b = self.mt_beta.get(pu.cls, self.mt_beta["default"])
        if mt_x > 0 and b > 0:
            f *= 1.0 + b * mt_x * (1.0 + k * mt_x) * u
        for r, x in res.items():
            b = self.beta.get(r, 0.3)
            if x > 0 and b > 0:
                f *= 1.0 + b * x * (1.0 + k * x) * m
        return f if f > 1.0 else 1.0

    def pressures(self, pu: PU, others) -> tuple[float, dict]:
        """What ``others`` ((pu, u, m) each, on ``pu``'s device) press on
        ``pu`` with: the pu-usage of co-tenants on ``pu`` itself, and the
        memory usage of co-runners per rclass where they meet it."""
        ncr = self.devices[pu.dev].ncr[pu.pos]
        mt_x = 0.0
        res: dict = {}
        for po, uo, mo in others:
            if po.gid == pu.gid:
                mt_x += uo
            else:
                r = ncr[po.pos]
                if r is not None:
                    res[r] = res.get(r, 0.0) + mo
        return mt_x, res

    def pool_factors(self, members: list) -> list[float]:
        """Joint slowdown of each (pu, u, m) in one device's pool."""
        out = []
        for i, (pu, u, m) in enumerate(members):
            mt_x, res = self.pressures(
                pu, (o for j, o in enumerate(members) if j != i))
            out.append(self.factor(pu, u, m, mt_x, res))
        return out


# ---------------------------------------------------------------------------
# the walk over the belief ledger
# ---------------------------------------------------------------------------
@dataclass
class Belief:
    uid: int
    pu: PU
    u: float
    m: float
    est: float
    fac: float
    dl: float
    rel: float


@dataclass
class TaskIn:
    uid: int
    kind: str
    origin: int
    u: float
    mem: float
    dl: float
    in_bytes: float
    srcs: tuple = ()               # devices its input comes from
    ret_bytes: float = 0.0         # output owed back to the origin
    pinned: bool = False

    def __post_init__(self) -> None:
        # a stage with no placed predecessor takes its input at the origin
        if not self.srcs:
            self.srcs = (self.origin,)


@dataclass
class Score:
    pu: PU
    ok: bool
    total: float
    factor: float


class Walker:
    def __init__(self, fleet: Fleet) -> None:
        self.fleet = fleet
        self.belief: list[list[Belief]] = [[] for _ in fleet.devices]

    # -- ledger ------------------------------------------------------------
    def drop(self, uids: set) -> None:
        for d, rows in enumerate(self.belief):
            if rows and any(b.uid in uids for b in rows):
                self.belief[d] = [b for b in rows if b.uid not in uids]

    def prune(self, now: float) -> None:
        for d, rows in enumerate(self.belief):
            if rows and any(b.est <= now for b in rows):
                self.belief[d] = [b for b in rows if b.est > now]

    # -- scoring -----------------------------------------------------------
    def comm(self, t: TaskIn, d: int) -> float:
        """Inbound transfer onto device ``d`` (the slowest of the legs
        from ``t``'s sources) and, off the origin, the return leg."""
        fl = self.fleet
        c = 0.0
        if t.in_bytes > 0:
            for src in t.srcs:
                if src != d:
                    c = max(c, fl.transfer_time(src, d, t.in_bytes))
        if t.ret_bytes > 0 and d != t.origin:
            c += fl.transfer_time(d, t.origin, t.ret_bytes)
        return c

    def score_device(self, t: TaskIn, d: int, now: float,
                     constrained: bool) -> list[Score]:
        if t.pinned and d != t.origin:
            return []
        fl = self.fleet
        acts = self.belief[d]
        cols = [(a.pu, a.u, a.m) for a in acts]
        comm = self.comm(t, d)
        out = []
        for gid in fl.devices[d].pus:
            pu = fl.pus[gid]
            sa = fl.standalone(t.kind, pu)
            if sa is None:
                continue
            m_new = min(t.mem, pu.mem_cap)
            f = fl.factor(pu, t.u, m_new, *fl.pressures(pu, cols))
            if not constrained:
                out.append(Score(pu, True, comm + sa * f, f))
                continue
            on_pu = [a.est for a in acts if a.pu.gid == gid]
            wait = 0.0
            if on_pu and len(on_pu) >= pu.tenancy:
                wait = max(0.0, min(on_pu) - now)
            total = (comm + wait) + sa * f
            ok = not (total > t.dl) and self._keeps_deadlines(
                acts, cols, (pu, t.u, m_new), now)
            out.append(Score(pu, ok, total, f))
        return out

    def _keeps_deadlines(self, acts: list, cols: list, new: tuple,
                         now: float) -> bool:
        """Alg. 1 line 15: every task on the device still meets its
        deadline once the newcomer ``new`` (pu, u, m) joins."""
        fl = self.fleet
        for i, a in enumerate(acts):
            if not math.isfinite(a.dl):
                continue
            others = [c for j, c in enumerate(cols) if j != i]
            others.append(new)
            f = fl.factor(a.pu, a.u, a.m, *fl.pressures(a.pu, others))
            rem = max(0.0, a.est - now) / max(a.fac, 1e-12)
            if (now + rem * f) - a.rel > a.dl * (1 + 1e-9):
                return False
        return True

    def _lower_bound(self, t: TaskIn, d: int) -> float:
        if t.pinned and d != t.origin:
            return math.inf
        return self.comm(t, d) + self.fleet.least_standalone(t.kind, d)

    def _scan(self, t: TaskIn, devs: list, now: float, constrained: bool,
              want: int, every: bool = False):
        """Least feasible total over ``devs`` (first wins), the score of PU
        ``want`` if it lies there, and, with ``every``, the devices holding
        a feasible PU.  Devices whose least possible total cannot beat the
        best so far are skipped unless ``every`` or they hold ``want``."""
        best: Optional[Score] = None
        mine: Optional[Score] = None
        feasible_devs = []
        want_dev = self.fleet.pus[want].dev if want >= 0 else -1
        for d in devs:
            if (not every and d != want_dev and best is not None
                    and self._lower_bound(t, d) >= best.total):
                continue
            any_ok = False
            for s in self.score_device(t, d, now, constrained):
                if s.pu.gid == want:
                    mine = s
                if not s.ok:
                    continue
                any_ok = True
                if best is None or s.total < best.total:
                    best = s
            if any_ok:
                feasible_devs.append(d)
        return best, mine, feasible_devs

    def walk(self, t: TaskIn, now: float, want: int):
        """The reference's choice for ``t`` and its view of PU ``want``:
        ``(best, mine, overhead_of_want, in_scope)``.  ``mine`` is scored
        the way the level the reference chose scores (with or without the
        constraints), even where ``want`` lies outside that level."""
        fl = self.fleet
        origin_cl = fl.devices[t.origin].cluster
        levels = [
            ([t.origin], None),
            ([d for d in fl.clusters[origin_cl] if d != t.origin], None),
        ]
        for c, members in enumerate(fl.clusters):
            if c != origin_cl:
                levels.append((members, c))
        for devs, cl in levels:
            best, mine, feas = self._scan(t, devs, now, True, want,
                                          every=cl is not None)
            if best is None:
                continue
            in_scope = mine is not None and mine.ok
            if mine is None:
                mine = self._score_pu(t, want, now, True)
            if cl is None:
                wd = fl.pus[want].dev if want >= 0 else -1
                ov = len(fl.devices[wd].pus) * fl.lqc if wd >= 0 else 0.0
            else:
                # a cluster asked as a sibling: each device with a feasible
                # PU charges its queries at depth 1 and again in the sum
                ov = 0.0
                for d in feas:
                    ov += fl.lqc * len(fl.devices[d].pus) * 2.0
            return best, mine, ov, in_scope
        alldevs = [d.idx for d in fl.devices]
        best, mine, _ = self._scan(t, alldevs, now, False, want)
        return best, mine, 0.0, mine is not None

    def _score_pu(self, t: TaskIn, want: int, now: float,
                  constrained: bool) -> Optional[Score]:
        if want < 0:
            return None
        d = self.fleet.pus[want].dev
        return next((s for s in self.score_device(t, d, now, constrained)
                     if s.pu.gid == want), None)

    def commit(self, t: TaskIn, s: Score, now: float) -> Belief:
        b = Belief(uid=t.uid, pu=s.pu, u=t.u, m=min(t.mem, s.pu.mem_cap),
                   est=now + s.total, fac=s.factor, dl=t.dl, rel=now)
        self.belief[s.pu.dev].append(b)
        return b


# ---------------------------------------------------------------------------
# the ground-truth timeline
# ---------------------------------------------------------------------------
_INTERVENE, _RELEASE, _ARRIVE = 0, 1, 2


@dataclass
class Job:
    uid: int
    pu: PU
    sa: float
    u: float
    m: float
    irr: float
    release: float
    origin: int
    in_bytes: float
    out_bytes: float = 0.0
    n_preds: int = 0
    succs: list = field(default_factory=list)    # consumer jobs
    waiting: int = 1               # release + inputs still to land
    W: float = 0.0
    rate: float = 1.0
    t_last: float = 0.0
    eta: float = math.inf
    stamp: int = 0
    finish: float = math.nan


@dataclass
class Xfer:
    k: int
    uid: int
    links: list
    lat: float
    W: float
    rate: float = 1.0
    t_last: float = 0.0
    eta: float = math.inf
    stamp: int = 0


@dataclass
class Timeline:
    fleet: Fleet
    noise: float
    seed: int
    churn: list = field(default_factory=list)

    def __post_init__(self) -> None:
        self.rng = np.random.default_rng(self.seed)
        self.heap: list = []
        self.seq = itertools.count()
        self.time = 0.0
        self.jobs: dict[int, Job] = {}
        self.running: dict[int, Job] = {}
        self.xlive: dict[int, Xfer] = {}
        self.n_x = 0
        self.members: dict[str, int] = {}
        self.link_x: dict[str, set] = {}
        self.pool: dict[int, set] = {}
        self.pu_running: dict[int, int] = {}
        self.queue: dict[int, deque] = {}
        self.dirty_devs: set = set()
        self.dirty_links: set = set()
        self.stamp = 0
        self.done_log: list[int] = []
        for t, entries in self.churn:
            self._push(float(t), _INTERVENE, entries)

    def _push(self, t: float, kind: int, payload) -> None:
        heapq.heappush(self.heap, (t, next(self.seq), kind, payload))

    def inject(self, jobs: list) -> None:
        for j in jobs:
            self.jobs[j.uid] = j
            j.waiting = j.n_preds + 1
        for j in jobs:
            self._push(j.release, _RELEASE, j.uid)

    def drain(self) -> list[int]:
        out, self.done_log = self.done_log, []
        return out

    # -- lifecycle ---------------------------------------------------------
    def _start(self, j: Job) -> None:
        p = j.pu.gid
        if self.pu_running.get(p, 0) >= j.pu.tenancy:
            self.queue.setdefault(p, deque()).append(j)
            return
        self.pu_running[p] = self.pu_running.get(p, 0) + 1
        work = j.sa
        if self.noise > 0.0:
            work = j.sa * float(np.exp(self.rng.normal(0.0,
                                                       self.noise * j.irr)))
        j.W, j.rate, j.t_last, j.eta = work, 1.0, self.time, math.inf
        self.running[j.uid] = j
        self.pool.setdefault(j.pu.dev, set()).add(j.uid)
        self.dirty_devs.add(j.pu.dev)

    def _arrived(self, j: Job) -> None:
        j.waiting -= 1
        if j.waiting == 0:
            self._start(j)

    def _finish(self, j: Job) -> None:
        j.eta = math.inf
        p = j.pu.gid
        self.pu_running[p] -= 1
        j.finish = self.time
        del self.running[j.uid]
        self.pool[j.pu.dev].discard(j.uid)
        self.done_log.append(j.uid)
        for c in j.succs:
            if not self._launch(c, j.pu.dev, j.out_bytes):
                self._arrived(c)
        q = self.queue.get(p)
        if q:
            self._start(q.popleft())
        self.dirty_devs.add(j.pu.dev)

    def _launch(self, j: Job, src: int, nbytes: float) -> bool:
        """Start the transfer of ``nbytes`` from device ``src`` onto
        ``j``'s; False where nothing travels."""
        if src == j.pu.dev or nbytes <= 0:
            return False
        links = self.fleet.route(src, j.pu.dev)
        lat = 0.0
        for ln in links:
            lat += self.fleet.lat[ln]
        x = Xfer(k=self.n_x, uid=j.uid, links=links, lat=lat, W=nbytes,
                 t_last=self.time)
        self.n_x += 1
        self.xlive[x.k] = x
        for ln in links:
            self.members[ln] = self.members.get(ln, 0) + 1
            self.link_x.setdefault(ln, set()).add(x.k)
            self.dirty_links.add(ln)
        return True

    def _intervene(self, entries) -> None:
        for name, bw in entries:
            self.fleet.set_bandwidth(name, bw)
        for d, mem in self.pool.items():
            if mem:
                self.dirty_devs.add(d)
        for ln, xs in self.link_x.items():
            if xs:
                self.dirty_links.add(ln)

    def _flush(self) -> bool:
        t = self.time
        flushed = False
        if self.dirty_devs:
            names = self.fleet.devices
            order = sorted(self.dirty_devs, key=lambda d: names[d].name)
            self.dirty_devs = set()
            for d in order:
                uids = sorted(self.pool.get(d, ()))
                if not uids:
                    continue
                js = [self.jobs[u] for u in uids]
                for j in js:
                    j.stamp = self.stamp
                    self.stamp += 1
                fs = self.fleet.pool_factors(
                    [(j.pu, j.u, j.m) for j in js])
                for j, f in zip(js, fs):
                    W2 = max(0.0, j.W - j.rate * (t - j.t_last))
                    j.W, j.t_last = W2, t
                    j.rate = 1.0 / f
                    j.eta = t + W2 / j.rate
                flushed = True
        if self.dirty_links:
            aff: set = set()
            for ln in self.dirty_links:
                aff |= self.link_x.get(ln, set())
            self.dirty_links = set()
            for k in sorted(aff):
                x = self.xlive[k]
                x.stamp = self.stamp
                self.stamp += 1
                bw = min(self.fleet.bw[ln] / max(1, self.members[ln])
                         for ln in x.links)
                raw = x.W - x.rate * (t - x.t_last)
                x.W = 0.0 if math.isnan(raw) else max(0.0, raw)
                x.t_last = t
                x.rate = bw
                x.eta = t + x.W / bw if bw > 0 else math.inf
                flushed = True
        return flushed

    def _due(self, t: float) -> tuple[list, list]:
        jobs = sorted((j for j in self.running.values() if j.eta <= t),
                      key=lambda j: j.stamp)
        xs = sorted((x for x in self.xlive.values() if x.eta <= t),
                    key=lambda x: x.stamp)
        return jobs, xs

    def advance(self, until: float) -> None:
        """Every event at or before ``until``."""
        while True:
            t_next = self.heap[0][0] if self.heap else math.inf
            for j in self.running.values():
                if j.eta < t_next:
                    t_next = j.eta
            for x in self.xlive.values():
                if x.eta < t_next:
                    t_next = x.eta
            if t_next == math.inf or t_next > until:
                return
            if t_next > self.time:
                self.time = t_next
            t = self.time
            while True:
                while self.heap and self.heap[0][0] <= t:
                    _, _, kind, payload = heapq.heappop(self.heap)
                    if kind == _RELEASE:
                        j = self.jobs[payload]
                        if j.n_preds or not self._launch(j, j.origin,
                                                         j.in_bytes):
                            self._arrived(j)
                    elif kind == _ARRIVE:
                        self._arrived(self.jobs[payload])
                    else:
                        self._intervene(payload)
                jobs, xs = self._due(t)
                for j in jobs:
                    W2 = max(0.0, j.W - j.rate * (t - j.t_last))
                    j.W, j.t_last = W2, t
                    if W2 > CTOL:
                        j.eta = t + j.W / j.rate
                for j in jobs:
                    if j.W <= CTOL:
                        self._finish(j)
                for x in xs:
                    raw = x.W - x.rate * (t - x.t_last)
                    x.W = 0.0 if math.isnan(raw) else max(0.0, raw)
                    x.t_last = t
                    if x.W > XTOL:
                        x.eta = t + x.W / x.rate
                        continue
                    x.eta = math.inf
                    del self.xlive[x.k]
                    for ln in x.links:
                        self.members[ln] -= 1
                        self.link_x[ln].discard(x.k)
                        self.dirty_links.add(ln)
                    if x.lat > 0:
                        self._push(t + x.lat, _ARRIVE, x.uid)
                    else:
                        self._arrived(self.jobs[x.uid])
                if not self._flush():
                    break
                jobs, xs = self._due(t)
                if not jobs and not xs and not (self.heap
                                                and self.heap[0][0] <= t):
                    break


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(a), abs(b))


def load_testbed() -> dict:
    import json
    with open(TESTBED) as f:
        return json.load(f)


def stage_levels(stages: list[dict]) -> list[list[int]]:
    """Stage indices grouped by dependency depth: a stage's level is one
    past its deepest predecessor's."""
    depth: list[int] = []
    for st in stages:
        depth.append(1 + max((depth[p] for p in st["preds"]), default=-1))
    return [[i for i, d in enumerate(depth) if d == lv]
            for lv in range(max(depth) + 1)]


def compare(conf: dict, churn: list, trail: list, stop_at: float,
            finish_of) -> dict:
    """Follow the program's ``trail`` (see ``bench.cell.WaveRecord``) and
    return the compared numbers: ``choice_gap`` (widest relative gap by
    which the reference's total of the program's PU lies above the
    reference's least at the level the reference chose; inf where that PU
    lies outside the level or is infeasible there), ``predict_rel`` (the
    program's predicted total against the reference's, for the same PU),
    ``overhead_rel`` (the walk's charged overhead), ``verdicts`` (requests
    whose verdict differs), ``finish_rel`` (each task's finish time on the
    ground-truth timeline, up to the instant ``stop_at`` the program's run
    stopped at; ``finish_of(uid)`` is the program's, nan while
    unfinished)."""
    tb = load_testbed()
    fleet = Fleet(conf, tb)
    walker = Walker(fleet)
    stages = request_stages(conf)
    levels = stage_levels(stages)
    back = returns_to_pinned(stages)
    adm = conf["admission"]
    slack = float(adm["slack"])
    tl = Timeline(fleet, float(tb["slowdown"]["truth_noise"]),
                  int(conf["truth_seed"]),
                  [(t, tuple(e)) for t, e in churn])
    usage, irr = tb["usage"], tb["irregularity"]
    gap = p_rel = o_rel = f_rel = 0.0
    verdicts = 0
    injected: list[Job] = []
    for w in trail:
        now = w.now
        tl.advance(float(np.nextafter(now, -np.inf)))
        walker.drop(set(tl.drain()))
        walker.prune(now)
        # per request: (task, score, overhead, belief) of each stage
        wave = [(r, [None] * len(r.tasks)) for r in w.readings]
        for level in levels:
            todo = sorted(((r.tasks[i][0], i, r, mine)
                           for r, mine in wave for i in level),
                          key=lambda x: x[0])
            for uid, i, r, mine in todo:
                _, kind, pu_name, total, overhead = r.tasks[i]
                st = stages[i]
                origin = fleet.device_of(r.origin)
                pred_pus = [fleet.by_name.get(r.tasks[p][2], -1)
                            for p in st["preds"] if r.tasks[p][2] is not None]
                if -1 in pred_pus:     # a predecessor on a PU the testbed lacks
                    gap = math.inf
                srcs = {fleet.pus[g].dev for g in pred_pus if g >= 0}
                t = TaskIn(uid=uid, kind=kind, origin=origin,
                           u=float(usage[kind]["pu"]),
                           mem=float(usage[kind]["mem"]),
                           dl=stage_deadline(st, fleet.devices[origin].kind),
                           in_bytes=float(st["input_bytes"]),
                           srcs=tuple(sorted(srcs)),
                           ret_bytes=(float(st["output_bytes"]) if back[i]
                                      else 0.0),
                           pinned=bool(st.get("pinned")))
                want = fleet.by_name.get(pu_name, -1) \
                    if pu_name is not None else -1
                best, s, ov, in_scope = walker.walk(t, now, want)
                if s is None or best is None:
                    gap = math.inf
                    mine[i] = (t, None, 0.0, None)
                    continue
                if s.pu.gid != best.pu.gid:
                    gap = max(gap, (s.total - best.total) / best.total
                              if in_scope else math.inf)
                p_rel = max(p_rel, _rel(total, s.total))
                o_rel = max(o_rel, _rel(overhead, ov))
                mine[i] = (t, s, ov, walker.commit(t, s, now))
            # a level's overhead is charged once it is mapped, before the
            # next level's walk reads the ledger
            for _, i, _, mine in todo:
                if mine[i][1] is not None:
                    mine[i][3].rel = now + mine[i][2]
        for r, mine in wave:
            late = any(item[1] is None or item[1].total > item[0].dl * slack
                       for item in mine)
            if not late:
                want = "accepted"
            elif adm["defer_delay_s"] > 0 and r.defers < adm["max_defers"]:
                want = "deferred"
            else:
                want = "rejected"
            verdicts += want != r.verdict
            if r.verdict != "accepted":
                walker.drop({item[0].uid for item in mine})
                continue
            jobs: list = [None] * len(mine)
            for i, (t, s, ov, _) in enumerate(mine):
                if s is None:
                    continue
                st = stages[i]
                jobs[i] = Job(uid=t.uid, pu=s.pu,
                              sa=fleet.standalone(t.kind, s.pu), u=t.u,
                              m=min(t.mem, s.pu.mem_cap),
                              irr=float(irr.get(t.kind, 1.0)),
                              release=now + ov, origin=t.origin,
                              in_bytes=t.in_bytes,
                              out_bytes=float(st["output_bytes"]),
                              n_preds=len(st["preds"]))
                for p in st["preds"]:
                    if jobs[p] is not None:
                        jobs[p].succs.append(jobs[i])
            jobs = [j for j in jobs if j is not None]
            tl.inject(jobs)
            injected.extend(jobs)
    tl.advance(float(np.nextafter(stop_at, -np.inf)))
    for j in injected:
        fa = finish_of(j.uid)
        fb = j.finish
        if math.isnan(fa) and math.isnan(fb):
            continue
        if math.isnan(fa) or math.isnan(fb):
            f = fb if math.isnan(fa) else fa
            f_rel = max(f_rel, _rel(f, stop_at))
        else:
            f_rel = max(f_rel, _rel(fa, fb))
    return {"choice_gap": gap, "predict_rel": p_rel, "overhead_rel": o_rel,
            "verdicts": verdicts, "finish_rel": f_rel}
