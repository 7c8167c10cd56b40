"""Array-native discrete-event timeline engine (paper §3.4, Alg. 2).

``TimelineEngine`` is the struct-of-arrays successor of the seed's
per-job ``heapq`` event loop (kept verbatim as
``Traverser.traverse_reference`` — the parity oracle and the benchmark
baseline).  The contention-interval semantics are identical; what
changes is the representation and the unit of work:

* **Dense job tables** — every compute job and transfer lives in numpy
  columns (remaining virtual work ``W``, progress ``rate``, last-settle
  time ``t_last``, projected completion ``eta``, device/PU ordinals,
  dependency counts) instead of per-job Python objects with
  version-stamped heap events.  Completion detection is an array
  compare against the shared timestamp, not a heap pop per job — the
  seed's biggest scaling cost (a fresh completion event per pool member
  per reprice) disappears entirely.
* **Per-timestamp draining** — all events sharing one timestamp drain
  before a single flush reprices the devices/links they touched
  (frontier batching, as in the seed), but the settle of every
  completion across all devices is **one array op** (the rate-advance
  kernel), and the flush reprices *every* dirty device pool in **one**
  ``factor_batch_idx`` call: compute paths never cross device
  boundaries, so the joint factors of the union pool are exactly the
  per-device factors (block-diagonal by construction).
* **Batched link repricing** — concurrent transfers share link
  bandwidth; the bottleneck share of each affected transfer is a
  segment-min over its route edges (the segment-min kernel), evaluated
  for the whole dirty set at once.

The two inner loops run as float64 numpy by default on every backend —
the parity bound is a hard 1e-9 and the per-flush batches are
memory-bound — with Pallas twins in ``kernels/timeline_kernel.py``
(oracle-checked) for TPU-resident pipelines that accept fp32 settles:
``REPRO_TIMELINE_KERNEL=pallas`` routes the engine through them (jax is
never imported otherwise, so pure-DES workflows stay jax-free).

**Interventions** (topology churn mid-run): ``traverse(...,
interventions=[(t, fn), ...])`` applies each ``fn()`` (e.g.
``graph.set_bandwidth`` / ``mark_dead``) at simulated time ``t`` and
reprices every active device pool and link set at that instant.  Both
engines implement the hook identically, so churn runs stay pinned to
the 1e-9 parity bound.

**Resident mode** (the serving path): ``TimelineEngine.open(...)``
brings an engine live without draining it, ``advance(until)`` drains
every event up to a wall-clock ``now`` and parks there, and
``inject(tasks)`` lands newly mapped work in the live job/transfer
tables mid-run — new rows append to the struct-of-arrays columns
(growable, +inf eta fill), releases enter the same event heap, and any
output handed over by an already-finished producer is priced by the
same one-flush reprice path as churn interventions.  Submitting a full
workload upfront through a resident engine reproduces ``run()`` (and
therefore the seed loop) to 1e-9: ingest builds the identical tables
and event sequence.  ``drain_finished``/``finish_of``/
``timeline(partial=True)`` observe progress without disturbing it; see
``docs/serving.md``.

Noise semantics: the ground-truth engine draws per-task irregularity
noise at job start, in event order — the array engine preserves the
draw order of the seed loop (timed events in push order, completions in
key order; the reference's simultaneous-event tie-break is pinned to
the same key order).  A *noisy slowdown model* (rng-bearing
``DecoupledSlowdown``) additionally draws inside ``factor()`` in pool
order; ``Traverser.traverse`` routes that configuration to the
reference loop so the rng stream stays byte-identical.
"""
from __future__ import annotations

import heapq
import itertools
import math
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import numpy as np

from . import trace
from .hwgraph import EdgeAttr, ProcessingUnit
from .task import Task, TaskGraph

# settle tolerances of the seed event loop (virtual work residue below
# which a projected completion is real, not a stale float artifact)
CTOL = 1e-15        # compute jobs
XTOL = 1e-6         # transfers (bytes)


@dataclass
class Timeline:
    """Result of a CFG traverse."""

    start: dict[int, float] = field(default_factory=dict)      # task.uid -> t
    finish: dict[int, float] = field(default_factory=dict)
    ready: dict[int, float] = field(default_factory=dict)      # deps resolved at
    standalone: dict[int, float] = field(default_factory=dict)
    comm: dict[int, float] = field(default_factory=dict)       # inbound comm time
    queue_wait: dict[int, float] = field(default_factory=dict)
    mapping: dict[int, str] = field(default_factory=dict)
    n_intervals: int = 0
    n_events: int = 0        # drained DES events (timed + completions)

    @property
    def makespan(self) -> float:
        return max(self.finish.values(), default=0.0)

    def latency(self, task: Task) -> float:
        """Ready-to-finish latency (comm + queueing + slowdown + compute).

        'Ready' = dependencies resolved (or release time for roots) — the
        moment the paper's runtime hands the task to the Orchestrator."""
        t0 = self.ready.get(task.uid, task.release_time)
        return self.finish[task.uid] - t0

    def slowdown_of(self, task: Task) -> float:
        busy = self.finish[task.uid] - self.start[task.uid]
        sa = self.standalone[task.uid]
        return busy / sa if sa > 0 else 1.0

    def deadline_met(self, task: Task) -> bool:
        if task.deadline is None:
            return True
        return self.latency(task) <= task.deadline * (1 + 1e-9)


# ---------------------------------------------------------------------------
# kernel dispatch: rate-advance + segment-min (numpy refs inline so pure-DES
# workflows never import jax; Pallas on a live TPU backend)
# ---------------------------------------------------------------------------
def _rate_advance_np(W: np.ndarray, rate: np.ndarray, t_last: np.ndarray,
                     now: float) -> tuple[np.ndarray, np.ndarray]:
    """Settle virtual work to ``now`` and project completion times.

    Mirrors the seed's scalar ``settle`` + completion push exactly,
    including the float corner the scalar path has: ``max(0.0, W -
    inf*0.0)`` is ``0.0`` under Python's ``max`` (nan compares false),
    so nan residues clamp to zero here too.  ``eta`` is
    ``now + W'/rate`` where the rate is positive, +inf otherwise."""
    with np.errstate(invalid="ignore"):      # inf-rate x zero-dt corner
        raw = W - rate * (now - t_last)
    W2 = np.maximum(0.0, raw)
    nan = np.isnan(raw)
    if nan.any():
        W2[nan] = 0.0
    eta = np.divide(W2, rate, out=np.full(len(W2), np.inf),
                    where=rate > 0.0)
    eta += now
    return W2, eta


def _segment_min_np(values: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-segment min of ``values`` split into consecutive runs of
    ``counts[i]`` elements; empty segments yield +inf (an edgeless
    transfer is latency-only, i.e. unthrottled)."""
    out = np.full(len(counts), np.inf)
    nz = counts > 0
    if nz.any():
        starts = np.cumsum(counts) - counts
        out[nz] = np.minimum.reduceat(values, starts[nz])
    return out


_RATE_ADVANCE = None
_SEGMENT_MIN = None


def _select_kernels():
    """``auto`` keeps the float64 numpy settles on every backend: the DES
    parity contract is a hard 1e-9 bound against the seed loop, which the
    fp32 Pallas kernels cannot guarantee, and the per-flush batches are
    memory-bound (device offload is a round-trip, not a win).  The
    kernels remain reachable with ``REPRO_TIMELINE_KERNEL=pallas`` for
    TPU-resident pipelines that accept fp32 settles."""
    import os
    mode = os.environ.get("REPRO_TIMELINE_KERNEL", "auto").lower()
    if mode == "pallas":
        from ..kernels import timeline_kernel as tk
        return tk.rate_advance_forced, tk.segment_min_forced
    return _rate_advance_np, _segment_min_np


def _rate_advance(W, rate, t_last, now):
    global _RATE_ADVANCE, _SEGMENT_MIN
    if _RATE_ADVANCE is None:
        _RATE_ADVANCE, _SEGMENT_MIN = _select_kernels()
    return _RATE_ADVANCE(W, rate, t_last, now)


def _segment_min(values, counts):
    global _RATE_ADVANCE, _SEGMENT_MIN
    if _SEGMENT_MIN is None:
        _RATE_ADVANCE, _SEGMENT_MIN = _select_kernels()
    return _SEGMENT_MIN(values, counts)


def _settle_pos(W: np.ndarray, rate: np.ndarray, t_last: np.ndarray,
                now: float) -> np.ndarray:
    """Settle-only fast path for compute jobs: rates are 1/factor, always
    finite-positive, so the nan/inf corners of the full kernel cannot
    occur and eta is left to the caller."""
    return np.maximum(0.0, W - rate * (now - t_last))


def warm_transfer_routes(comp, cfg: TaskGraph, mapping: dict) -> int:
    """Batch-materialize every route row a traverse of ``cfg`` under
    ``mapping`` can touch: origins of root tasks with off-device initial
    payloads, and producer devices with off-device consumers.

    Both DES engines call this at traverse start, which restores the
    seed's frozen-route semantics under mid-run churn: all transfer
    routes are derived from the pre-churn topology, never lazily against
    a mutated graph (unroutable pairs stay quiet here and raise at
    launch time, as the seed did).  Returns the number of rows built."""
    srcs: set[str] = set()
    for t in cfg:
        dev = comp.device_name(mapping[t.uid])
        if (t.origin is not None and t.input_bytes > 0
                and not cfg.preds(t) and t.origin != dev):
            srcs.add(t.origin)
        if t.output_bytes > 0 and any(
                comp.device_name(mapping[s.uid]) != dev
                for s in cfg.succs(t)):
            srcs.add(dev)
    ensure = getattr(comp, "ensure_routes", None)
    if srcs and ensure is not None:
        return ensure(srcs)
    return 0


# timed-event kinds, ordered only by (time, push seq) like the seed heap
_INTERVENE, _RELEASE, _ARRIVE = 0, 1, 2

_ONE = np.ones(1)


class TimelineEngine:
    """A DES timeline over SoA state: one-shot (``run()``) or resident
    (``open``/``advance``/``inject``).

    Instantiated per ``Traverser.traverse`` call — or opened once per
    ``SchedulerSession`` for online serving; the engine freezes the
    compiled snapshot for transfer routes/device names (seed semantics)
    while slowdown factors read the *live* compiled snapshot through the
    model — exactly like the seed loop — so interventions that patch the
    topology take effect at the next contention-interval boundary.

    Representation notes: columns consumed by vectorized settles and the
    repricing kernels are numpy; columns only ever read one scalar at a
    time inside event handlers are plain Python lists (a numpy scalar
    index costs ~10x a list index, and handlers run once per event).
    """

    def __init__(self, traverser, cfg: TaskGraph, mapping: dict[int, str],
                 background: Sequence[tuple[Task, str, float]] = (),
                 interventions: Sequence[tuple[float, Callable[[], Any]]] = (),
                 ) -> None:
        self.trav = traverser
        self.graph = traverser.graph
        self.slowdown = traverser.slowdown
        self.noise = traverser.noise
        self.rng = traverser.rng
        self.cfg = cfg
        self.mapping = mapping
        self.background = list(background)
        self.interventions = list(interventions)
        self._opened = False

    # -- setup --------------------------------------------------------------
    _JCAP0 = 64         # initial job-table capacity (doubles on growth)

    def _jgrow(self, cap: int) -> None:
        """Grow the numpy job columns to ``cap`` slots.  The tail fill of
        ``eta`` is +inf so whole-array scans (``eta.min()``, the
        completion compare) never see unused capacity."""
        for col, fill in (("W", 0.0), ("rate", 1.0), ("t_last", 0.0),
                          ("eta", np.inf), ("U", 1.0), ("memraw", 1.0)):
            old = getattr(self, col, None)
            arr = np.full(cap, fill)
            if old is not None:
                arr[:len(old)] = old
            setattr(self, col, arr)
        for col in ("cstamp", "pu_i", "uid_col"):
            old = getattr(self, col, None)
            arr = np.zeros(cap, dtype=np.int64)
            if old is not None:
                arr[:len(old)] = old
            setattr(self, col, arr)

    def _init_state(self) -> None:
        g = self.graph
        comp = g.compiled()          # frozen: routes + device name space
        self.comp = comp
        self.n = 0
        self.slot_of: dict[int, int] = {}
        self._jgrow(self._JCAP0)
        self.pu_il: list[int] = []
        self.dev_ol: list[int] = []
        self.dev_name: list[str] = []
        self.pu_name: list[str] = []
        self.allt: list[Task] = []
        self.is_bg: list[bool] = []
        self.uidl: list[int] = []
        # generated workloads hand tasks over in uid order: slot order IS
        # uid order and the per-flush pool sorts drop the Python key fn
        self._uid_monotone = True
        self.irr: list[float] = []
        self.rel: list[float] = []
        self.in_bytes: list[float] = []
        self.sa: list[float] = []
        self.preds: list[list[int]] = []
        self.succs: list[list[int]] = []
        self.waiting: list[int] = []
        # reprice stamps emulate the reference heap's push sequence so
        # *simultaneous* completions settle in the seed's event order
        # (noise draw order is observable); see _complete_* argsorts
        self._stamp = 0
        # timeline columns
        self.start: list[float] = []
        self.finish: list[float] = []
        self.standalone: list[float] = []
        self.ready_t: list[float] = []
        self.comm_t: list[float] = []
        self.qwait: list[float] = []
        self.ready_at: list[float] = []
        # completion log for resident consumers (``drain_finished``)
        self._finish_log: list[int] = []
        self._finish_cursor = 0
        # tenancy
        self.pu_running = [0] * len(comp.pu_names)
        self.max_ten = comp.max_tenancy.tolist()
        self.pu_queue: dict[int, deque] = {}
        # device pools + repricing dirt
        self.dev_members: dict[int, set[int]] = {}
        self.dirty_devs: set[int] = set()
        self.dirty_edges: set[int] = set()
        self.n_intervals = 0
        self.n_events = 0
        # transfers (growable SoA) + edge table
        self.xcols = ("xW", "xrate", "xt_last", "xeta", "xlat")
        self._xgrow(64)
        self.xn = 0
        self.xlive = 0
        self.xconsumer: list[int] = []
        # per-transfer route edges in CSR form: xe_flat[xe_start[k] :
        # xe_start[k] + xe_cnt[k]] are transfer k's edge indices, so the
        # link-repricing flush gathers the whole dirty set's edge lists
        # with vectorized index math instead of per-transfer Python
        self.xe_flat = np.zeros(256, dtype=np.int64)
        self.xe_top = 0
        self.xe_start: list[int] = []
        self.xe_cnt: list[int] = []
        self._xe_start_arr: Optional[np.ndarray] = None
        self.edge_idx: dict[int, int] = {}
        self.edge_objs: list[EdgeAttr] = []
        self.edge_bw: list[float] = []
        self._edge_bw_arr: Optional[np.ndarray] = None
        self.edge_members: list[int] = []
        self.edge_xfers: dict[int, set[int]] = {}
        self.route_cache: dict[tuple[str, str], tuple[np.ndarray, float]] = {}
        # timed events
        self.heap: list[tuple[float, int, int, Any]] = []
        self.seq = itertools.count()
        self.time = 0.0
        # factor path: array-native when the model exposes ledger-column
        # scoring; otherwise per-device pools through the tuple surface
        self._fbi = getattr(self.slowdown, "factor_batch_idx", None)
        # memoized repricing: a pool's joint factors depend only on the
        # multiset of (PU, pu-usage, mem-usage) columns (uids are distinct
        # by construction — one job per task), so steady-state pools that
        # recur across readings/devices hit a canonical-order cache
        # instead of re-running the factor kernel.  Keyed per compiled
        # snapshot: topology churn drops the cache with the snapshot.
        self._fcache: dict = {}
        self._fcache_comp = None

    def _ingest(self, new_tasks: Sequence[Task]) -> None:
        """Append ``new_tasks`` to the live job tables.

        Dependencies must point at tasks in this batch or at ones already
        ingested (inject producers before — or together with — their
        consumers).  A producer that already *finished* hands its output
        over at the current instant: the cross-device transfer launches
        now and is priced by the caller's flush, exactly the churn
        repricing path."""
        cfg, mapping, g, comp = self.cfg, self.mapping, self.graph, self.comp
        base = self.n
        need = base + len(new_tasks)
        if need > len(self.W):
            cap = len(self.W)
            while cap < need:
                cap *= 2
            self._jgrow(cap)
        slot_of = self.slot_of
        last_uid = self.uidl[-1] if self.uidl else None
        mono = self._uid_monotone
        nan = float("nan")
        for i, t in enumerate(new_tasks):
            s = base + i
            if t.uid in slot_of:
                raise ValueError(f"{t} is already in the timeline")
            if t.uid not in mapping:
                raise KeyError(f"{t} has no mapping")
            pu_name = mapping[t.uid]
            pu = g.nodes[pu_name]
            assert isinstance(pu, ProcessingUnit), pu_name
            slot_of[t.uid] = s
            p = int(comp.pu_index[pu_name])
            self.pu_i[s] = p
            self.pu_il.append(p)
            d = int(comp.pu_dev_ord[p])
            self.dev_ol.append(d)
            self.dev_name.append(comp.dev_ord_names[d])
            self.pu_name.append(comp.pu_names[p])
            self.allt.append(t)
            self.is_bg.append(False)
            self.uid_col[s] = t.uid
            if mono and last_uid is not None and t.uid <= last_uid:
                mono = False
            last_uid = t.uid
            self.uidl.append(t.uid)
            self.U[s] = t.usage.get("pu", 1.0)
            self.memraw[s] = t.usage.get("mem", 1.0)
            self.irr.append(t.attrs.get("irregularity", 1.0))
            self.rel.append(t.release_time)
            self.in_bytes.append(t.input_bytes)
            # standalone predictions are pure per (task, PU)
            self.sa.append(g.nodes[pu_name].predict(t))
            self.W[s] = 0.0
            self.rate[s] = 1.0
            self.t_last[s] = 0.0
            self.eta[s] = np.inf
            self.cstamp[s] = 0
            for col in (self.start, self.finish, self.standalone,
                        self.ready_t, self.comm_t, self.qwait,
                        self.ready_at):
                col.append(nan)
        self._uid_monotone = mono
        self.n = need
        # dependency structure as slot lists: within-batch edges are wired
        # from cfg order (one-shot parity); cross-batch producers get this
        # consumer appended to their successor lists
        done_preds: list[tuple[int, int]] = []
        for i, t in enumerate(new_tasks):
            s = base + i
            pl: list[int] = []
            for pt in cfg.preds(t):
                ps = slot_of.get(pt.uid)
                if ps is None:
                    raise ValueError(
                        f"dependency {pt} of {t} is not in the timeline — "
                        "inject producers before (or together with) their "
                        "consumers")
                pl.append(ps)
                if ps < base:
                    self.succs[ps].append(s)
                    if self.finish[ps] == self.finish[ps]:   # already done
                        done_preds.append((s, ps))
            self.preds.append(pl)
            self.succs.append([slot_of[x.uid] for x in cfg.succs(t)
                               if slot_of.get(x.uid, -1) >= base])
            self.waiting.append(len(pl) + 1)   # +1: release event
        # pre-churn route freeze, batched per ingest (the incremental form
        # of warm_transfer_routes): origins of roots with off-device input
        # payloads, producer devices with off-device consumers
        srcs: set[str] = set()
        for i, t in enumerate(new_tasks):
            s = base + i
            dev = self.dev_name[s]
            if (t.origin is not None and t.input_bytes > 0
                    and not self.preds[s] and t.origin != dev):
                srcs.add(t.origin)
            if t.output_bytes > 0 and any(
                    self.dev_name[ss] != dev for ss in self.succs[s]):
                srcs.add(dev)
            for ps in self.preds[s]:
                if ps < base and self.allt[ps].output_bytes > 0 \
                        and self.dev_name[ps] != dev:
                    srcs.add(self.dev_name[ps])
        ensure = getattr(comp, "ensure_routes", None)
        if srcs and ensure is not None:
            ensure(srcs)
        # producers that finished before this batch arrived hand their
        # output over now; the release event still gates readiness (the
        # waiting floor is 1 until it drains), so a direct decrement never
        # starts compute early
        for s, ps in done_preds:
            ob = self.allt[ps].output_bytes
            if not self._launch(s, self.dev_name[ps], self.dev_name[s], ob):
                self.waiting[s] -= 1

    def _ingest_background(self) -> None:
        """Background jobs occupy their PU from t=0 with known remaining
        standalone work; they have no deps, releases, or successors."""
        comp = self.comp
        base = self.n
        need = base + len(self.background)
        if need > len(self.W):
            cap = len(self.W)
            while cap < need:
                cap *= 2
            self._jgrow(cap)
        last_uid = self.uidl[-1] if self.uidl else None
        mono = self._uid_monotone
        nan = float("nan")
        for k, (bt, bpu, brem) in enumerate(self.background):
            s = base + k
            self.slot_of[bt.uid] = s
            p = int(comp.pu_index[bpu])
            self.pu_i[s] = p
            self.pu_il.append(p)
            d = int(comp.pu_dev_ord[p])
            self.dev_ol.append(d)
            self.dev_name.append(comp.dev_ord_names[d])
            self.pu_name.append(comp.pu_names[p])
            self.allt.append(bt)
            self.is_bg.append(True)
            self.uid_col[s] = bt.uid
            if mono and last_uid is not None and bt.uid <= last_uid:
                mono = False
            last_uid = bt.uid
            self.uidl.append(bt.uid)
            self.U[s] = bt.usage.get("pu", 1.0)
            self.memraw[s] = bt.usage.get("mem", 1.0)
            self.irr.append(bt.attrs.get("irregularity", 1.0))
            self.rel.append(bt.release_time)
            self.in_bytes.append(0.0)
            self.sa.append(brem)
            self.preds.append([])
            self.succs.append([])
            self.waiting.append(0)
            # running from t=0: occupy the PU and dirty its device pool
            self.W[s] = brem
            self.rate[s] = 1.0
            self.t_last[s] = 0.0
            self.eta[s] = np.inf
            for col, v in ((self.start, 0.0), (self.finish, nan),
                           (self.standalone, brem), (self.ready_t, nan),
                           (self.comm_t, nan), (self.qwait, nan),
                           (self.ready_at, nan)):
                col.append(v)
            self.pu_running[p] += 1
            m = self.dev_members.get(d)
            if m is None:
                m = self.dev_members[d] = set()
            m.add(s)
            self.dirty_devs.add(d)
        self._uid_monotone = mono
        self.n = need

    def _xgrow(self, cap: int) -> None:
        for col in self.xcols:
            old = getattr(self, col, None)
            fill = np.inf if col == "xeta" else 0.0
            arr = np.full(cap, fill)
            if old is not None:
                arr[:len(old)] = old
            setattr(self, col, arr)
        old = getattr(self, "xstamp", None)
        self.xstamp = np.zeros(cap, dtype=np.int64)
        if old is not None:
            self.xstamp[:len(old)] = old

    def _push(self, t: float, kind: int, payload: Any) -> None:
        heapq.heappush(self.heap, (t, next(self.seq), kind, payload))

    # -- job lifecycle ------------------------------------------------------
    def _start_compute(self, s: int) -> None:
        p = self.pu_il[s]
        if self.pu_running[p] >= self.max_ten[p]:
            q = self.pu_queue.get(p)
            if q is None:
                q = self.pu_queue[p] = deque()
            q.append(s)
            return
        self.pu_running[p] = self.pu_running[p] + 1
        sa = self.sa[s]
        work = sa
        if self.noise > 0.0:
            work = sa * float(np.exp(self.rng.normal(
                0.0, self.noise * self.irr[s])))
        t = self.time
        self.W[s] = work
        self.rate[s] = 1.0
        self.t_last[s] = t
        self.start[s] = t
        self.standalone[s] = sa
        ra = self.ready_at[s]
        self.qwait[s] = t - (ra if ra == ra else self.rel[s])
        d = self.dev_ol[s]
        m = self.dev_members.get(d)
        if m is None:
            m = self.dev_members[d] = set()
        m.add(s)
        self.dirty_devs.add(d)

    def _route(self, src: str, dst: str) -> tuple[np.ndarray, float]:
        key = (src, dst)
        hit = self.route_cache.get(key)
        if hit is None:
            edges = self.comp.route_edges(src, dst)
            idxs = np.empty(len(edges), dtype=np.int64)
            lat = 0.0
            for i, e in enumerate(edges):
                ei = self.edge_idx.get(id(e))
                if ei is None:
                    ei = len(self.edge_objs)
                    self.edge_idx[id(e)] = ei
                    self.edge_objs.append(e)
                    self.edge_bw.append(e.bandwidth)
                    self.edge_members.append(0)
                    self._edge_bw_arr = None
                idxs[i] = ei
                lat += e.latency
            hit = self.route_cache[key] = (idxs, lat)
        return hit

    def _launch(self, consumer: int, src_dev: str, dst_dev: str,
                nbytes: float) -> bool:
        """Start a transfer for ``consumer``'s input; False = local/no data."""
        if src_dev == dst_dev or nbytes <= 0:
            return False
        eidx, lat = self._route(src_dev, dst_dev)
        k = self.xn
        if k == len(self.xW):
            self._xgrow(2 * k)
        self.xn = k + 1
        self.xlive += 1
        self.xW[k] = nbytes
        self.xrate[k] = 1.0
        self.xt_last[k] = self.time
        self.xeta[k] = np.inf          # priced at the flush
        self.xlat[k] = lat
        self.xconsumer.append(consumer)
        ne = len(eidx)
        top = self.xe_top
        if top + ne > len(self.xe_flat):
            buf = np.zeros(max(2 * len(self.xe_flat), top + ne),
                           dtype=np.int64)
            buf[:top] = self.xe_flat[:top]
            self.xe_flat = buf
        self.xe_flat[top:top + ne] = eidx
        self.xe_start.append(top)
        self.xe_cnt.append(ne)
        self.xe_top = top + ne
        self._xe_start_arr = None
        dirty = self.dirty_edges
        members = self.edge_members
        xfers = self.edge_xfers
        for e in eidx.tolist():
            members[e] += 1
            xs = xfers.get(e)
            if xs is None:
                xs = xfers[e] = set()
            xs.add(k)
            dirty.add(e)
        return True

    def _arrived(self, s: int) -> None:
        w = self.waiting[s] - 1
        self.waiting[s] = w
        if w == 0:
            t = self.time
            self.ready_at[s] = t
            dep = self.rel[s]
            for p in self.preds[s]:
                f = self.finish[p]
                if f > dep:
                    dep = f
            self.ready_t[s] = dep
            self.comm_t[s] = t - dep
            self._start_compute(s)

    def _finish(self, s: int) -> None:
        t = self.time
        self.eta[s] = np.inf
        p = self.pu_il[s]
        self.pu_running[p] = self.pu_running[p] - 1
        self.finish[s] = t
        d = self.dev_ol[s]
        self.dev_members[d].discard(s)
        self._finish_log.append(s)
        # successors: dependency bookkeeping + inter-device transfers
        # (background slots carry empty successor lists)
        out_bytes = self.allt[s].output_bytes
        src = self.dev_name[s]
        for ss in self.succs[s]:
            if not self._launch(ss, src, self.dev_name[ss], out_bytes):
                self._arrived(ss)
        q = self.pu_queue.get(p)
        if q:
            self._start_compute(q.popleft())
        self.dirty_devs.add(d)

    # -- repricing ----------------------------------------------------------
    def _pool_factors(self, members: np.ndarray) -> np.ndarray:
        if self._fbi is not None:
            P = self.pu_i[members]
            n = len(P)
            if n == 1:
                return _ONE        # a lone job has no co-runners
            U = self.U[members]
            mem = self.memraw[members]
            if n == 2:             # pair pools: scalar path beats the cache
                return self._fbi(P, U, mem, self.uid_col[members])
            comp = self.graph.compiled()
            if comp is not self._fcache_comp:
                self._fcache_comp = comp
                self._fcache = {}
            order = np.lexsort((mem, U, P))
            key = (P[order].tobytes(), U[order].tobytes(),
                   mem[order].tobytes())
            hit = self._fcache.get(key)
            if hit is not None:
                out = np.empty(len(hit))
                out[order] = hit
                return out
            f = np.asarray(self._fbi(P, U, mem, self.uid_col[members]),
                           dtype=np.float64)
            self._fcache[key] = f[order].copy()
            return f
        # tuple fallback (custom slowdown models): per-device pools, like
        # the seed — cross-device interactions are not assumed absent
        out = np.empty(len(members))
        fb = getattr(self.slowdown, "factor_batch", None)
        allt = self.allt
        devs = np.asarray([self.dev_ol[m] for m in members.tolist()])
        for d in np.unique(devs):
            sel = np.nonzero(devs == d)[0]
            pool = [(allt[m], self.pu_name[m]) for m in members[sel]]
            if fb is not None:
                out[sel] = np.asarray(fb(pool), dtype=np.float64)
            else:
                out[sel] = [self.slowdown.factor(tk, pu, pool)
                            for tk, pu in pool]
        return out

    def _flush(self) -> bool:
        """Reprice every dirty device pool (one factor call) and every
        dirty link set (one segment-min).  Returns True when any rate was
        re-projected — i.e. when same-timestamp work may now exist."""
        if not (self.dirty_devs or self.dirty_edges):
            return False
        with trace.span("timeline.flush"):
            return self._reprice()

    def _reprice(self) -> bool:
        t = self.time
        flushed = False
        if self.dirty_devs:
            self.n_intervals += len(self.dirty_devs)
            dm = self.dev_members
            # pool order replays the reference's completion-push sequence
            # (device name, then uid) so reprice stamps line up exactly
            names = self.comp.dev_ord_names
            uidl = self.uidl
            mem_list: list[int] = []
            if self._uid_monotone:
                for d in sorted(self.dirty_devs, key=names.__getitem__):
                    mem_list.extend(sorted(dm[d]))
            else:
                for d in sorted(self.dirty_devs, key=names.__getitem__):
                    mem_list.extend(sorted(dm[d], key=uidl.__getitem__))
            self.dirty_devs.clear()
            total = len(mem_list)
            if total:
                members = np.asarray(mem_list, dtype=np.int64)
                self.cstamp[members] = np.arange(
                    self._stamp, self._stamp + total)
                self._stamp += total
                factors = np.asarray(self._pool_factors(members),
                                     dtype=np.float64)
                W2 = _settle_pos(self.W[members], self.rate[members],
                                 self.t_last[members], t)
                rate = 1.0 / factors
                self.W[members] = W2
                self.t_last[members] = t
                self.rate[members] = rate
                self.eta[members] = t + W2 / rate
                flushed = True
        if self.dirty_edges:
            affected: set[int] = set()
            xfers = self.edge_xfers
            for e in self.dirty_edges:
                xs = xfers.get(e)
                if xs:
                    affected |= xs
            self.dirty_edges.clear()
            if affected:
                ks = np.fromiter(sorted(affected), dtype=np.int64,
                                 count=len(affected))
                self.xstamp[ks] = np.arange(self._stamp,
                                            self._stamp + len(ks))
                self._stamp += len(ks)
                if self._xe_start_arr is None:
                    self._xe_start_arr = np.asarray(self.xe_start,
                                                    dtype=np.int64)
                    self._xe_cnt_arr = np.asarray(self.xe_cnt,
                                                  dtype=np.int64)
                starts = self._xe_start_arr[ks]
                counts = self._xe_cnt_arr[ks]
                K = int(counts.sum())
                if K:
                    within = np.arange(K) - np.repeat(
                        np.cumsum(counts) - counts, counts)
                    flat = self.xe_flat[np.repeat(starts, counts) + within]
                else:
                    flat = np.zeros(0, dtype=np.int64)
                if self._edge_bw_arr is None:
                    self._edge_bw_arr = np.asarray(self.edge_bw)
                    self._edge_mem_arr = np.asarray(self.edge_members)
                else:
                    self._edge_mem_arr = np.asarray(self.edge_members)
                shares = self._edge_bw_arr[flat] / np.maximum(
                    1, self._edge_mem_arr[flat])
                bw = _segment_min(shares, counts)
                W2, _ = _rate_advance(self.xW[ks], self.xrate[ks],
                                      self.xt_last[ks], t)
                self.xW[ks] = W2
                self.xt_last[ks] = t
                self.xrate[ks] = bw
                eta = np.divide(W2, bw, out=np.full(len(ks), np.inf),
                                where=bw > 0.0)
                self.xeta[ks] = t + eta
                flushed = True
        return flushed

    def _intervene(self, fn) -> None:
        from .hwgraph import Churn
        is_churn = isinstance(fn, Churn)
        if is_churn:
            # declarative delta batch: apply through the consolidated
            # churn surface instead of calling into user code (bandwidth
            # entries coalesce into one snapshot overlay copy there)
            self.graph.apply_churn(fn)
        else:
            fn()
        # an intervention may mutate anything factors depend on (topology
        # OR model params): drop the memoized pool factors outright
        self._fcache = {}
        self._fcache_comp = None
        # churn boundary: reprice every occupied device pool and active
        # link set against the post-mutation model/bandwidths
        for d, members in self.dev_members.items():
            if members:
                self.dirty_devs.add(d)
        if is_churn and not (fn.dead or fn.alive):
            # bandwidth-only batch: the churn surface names exactly which
            # links moved (the snapshot overlay's dirty-link set), so
            # only those slots of the segment-min repricing input need a
            # refresh — every other edge's bandwidth is unchanged by
            # construction
            changed = {name for name, _ in fn.bandwidth}
            for i, e in enumerate(self.edge_objs):
                if e.name in changed:
                    self.edge_bw[i] = e.bandwidth
        else:
            for i, e in enumerate(self.edge_objs):
                self.edge_bw[i] = e.bandwidth
        self._edge_bw_arr = None
        for e, xs in self.edge_xfers.items():
            if xs:
                self.dirty_edges.add(e)

    # -- completions --------------------------------------------------------
    def _complete_compute(self, done: np.ndarray) -> None:
        t = self.time
        if len(done) > 1:   # simultaneous: settle in reprice-stamp order
            done = done[np.argsort(self.cstamp[done], kind="stable")]
        W2 = _settle_pos(self.W[done], self.rate[done],
                         self.t_last[done], t)
        self.W[done] = W2
        self.t_last[done] = t
        fin = W2 <= CTOL
        if not fin.all():   # float residue: keep running, fresh estimate
            resid = done[~fin]
            self.eta[resid] = t + self.W[resid] / self.rate[resid]
        self.n_events += len(done)
        for s in done[fin].tolist():
            self._finish(s)

    def _complete_transfers(self, done: np.ndarray) -> None:
        t = self.time
        if len(done) > 1:   # simultaneous: settle in reprice-stamp order
            done = done[np.argsort(self.xstamp[done], kind="stable")]
        W2, eta = _rate_advance(self.xW[done], self.xrate[done],
                                self.xt_last[done], t)
        self.xW[done] = W2
        self.xt_last[done] = t
        fin = W2 <= XTOL
        if not fin.all():
            resid = done[~fin]
            self.xeta[resid] = eta[~fin]
        self.n_events += len(done)
        members = self.edge_members
        for k in done[fin].tolist():
            self.xeta[k] = np.inf
            self.xlive -= 1
            st = self.xe_start[k]
            for e in self.xe_flat[st:st + self.xe_cnt[k]].tolist():
                members[e] -= 1
                self.edge_xfers[e].discard(k)
                self.dirty_edges.add(e)
            lat = float(self.xlat[k])
            if lat > 0:
                # latency tail: arrival after the fixed route latency
                self._push(t + lat, _ARRIVE, self.xconsumer[k])
            else:
                self._arrived(self.xconsumer[k])

    # -- lifecycle ----------------------------------------------------------
    def _start(self) -> None:
        """Bring the engine live: ingest the initial CFG + background jobs,
        price the opening intervals, and enqueue releases.  Event push
        order (interventions, then releases) replays the one-shot loop's
        sequence numbers exactly."""
        if self._opened:
            raise RuntimeError("TimelineEngine is already open")
        self._init_state()
        self._ingest(list(self.cfg))
        for t, fn in self.interventions:
            self._push(float(t), _INTERVENE, fn)
        self._ingest_background()
        self._flush()
        for t in self.cfg:
            self._push(t.release_time, _RELEASE, self.slot_of[t.uid])
        self._opened = True

    @classmethod
    def open(cls, traverser, cfg: Optional[TaskGraph] = None,
             mapping: Optional[dict[int, str]] = None,
             background: Sequence[tuple[Task, str, float]] = (),
             interventions: Sequence[tuple[float, Callable[[], Any]]] = (),
             ) -> "TimelineEngine":
        """Open a **session-resident** engine: live immediately, advanced
        incrementally (``advance``), and accepting ``inject`` mid-run.

        ``cfg``/``mapping`` may start empty (the serving case) or carry an
        initial workload; ``mapping`` is read live, so a dict shared with
        a ``SchedulerSession`` picks up later commits without copying.
        Noisy *slowdown models* (rng-bearing ``factor()``) are rejected:
        their draw stream only replays on the reference loop, which has
        no resident form."""
        eng = cls(traverser,
                  cfg if cfg is not None else TaskGraph("resident"),
                  mapping if mapping is not None else {},
                  background, interventions)
        noisy = getattr(eng.slowdown, "_noisy", None)
        if noisy is not None and noisy():
            raise ValueError(
                "resident timelines require a deterministic slowdown "
                "model (noisy factor() draws only replay on "
                "Traverser.traverse_reference)")
        eng._start()
        return eng

    def inject(self, tasks: Sequence[Task],
               mapping: Optional[dict[int, str]] = None) -> "TimelineEngine":
        """Land newly mapped work in the live job tables mid-run.

        Each task enters at its own ``release_time`` (>= the engine clock:
        injecting into the past would rewrite settled intervals).  Output
        handed over by an already-finished producer launches its transfer
        immediately and is priced by the same one-flush reprice path as
        churn interventions."""
        if not self._opened:
            raise RuntimeError(
                "inject() requires an open engine — TimelineEngine.open() "
                "or SchedulerSession.open_timeline()")
        tasks = list(tasks)
        if mapping:
            self.mapping.update(mapping)
        for t in tasks:
            if t.release_time < self.time:
                raise ValueError(
                    f"{t} releases at {t.release_time:.6g}, before the "
                    f"engine clock {self.time:.6g}")
        self._ingest(tasks)
        for t in tasks:
            self._push(t.release_time, _RELEASE, self.slot_of[t.uid])
        if self.dirty_devs or self.dirty_edges:
            self._flush()
        return self

    def schedule(self, t: float, fn) -> None:
        """Queue an intervention at simulated time ``t`` — the resident
        counterpart of the ``interventions=`` argument.  ``fn`` is either
        a zero-arg callable or a declarative :class:`~.hwgraph.Churn`
        delta batch."""
        self._push(float(t), _INTERVENE, fn)

    def apply_churn(self, churn) -> "TimelineEngine":
        """Apply a :class:`~.hwgraph.Churn` delta batch (or a zero-arg
        callable) at the current engine clock, through the same one-flush
        reprice path as scheduled interventions: mutate, drop memoized
        pool factors, reprice every occupied pool and active link set."""
        self._intervene(churn)
        self._flush()
        return self

    def finish_of(self, uid: int) -> float:
        """Finish time of task ``uid`` (nan while pending or running)."""
        s = self.slot_of.get(uid)
        return float("nan") if s is None else self.finish[s]

    def drain_finished(self) -> list[Task]:
        """Tasks that completed since the previous drain (background slots
        excluded) — the ledger-reconciliation feed for serving loops."""
        log = self._finish_log
        out = [self.allt[s] for s in log[self._finish_cursor:]
               if not self.is_bg[s]]
        self._finish_cursor = len(log)
        return out

    @property
    def live_jobs(self) -> int:
        """Compute jobs currently occupying a PU."""
        return int(sum(self.pu_running))

    def next_event_time(self) -> float:
        """Timestamp of the earliest pending event (compute finish,
        transfer finish, or heap entry), ``inf`` at quiescence — the same
        minimum :meth:`advance` computes before draining, so
        ``next_event_time() > until`` means ``advance(until)`` would only
        park the clock (serving loops use this to skip the call)."""
        em = float(self.eta.min()) if len(self.eta) else np.inf
        xm = float(self.xeta[:self.xn].min()) if self.xlive else np.inf
        t_next = self.heap[0][0] if self.heap else np.inf
        return min(em, xm, t_next)

    # -- main loop ----------------------------------------------------------
    def advance(self, until: float = np.inf) -> "TimelineEngine":
        """Drain every event with timestamp <= ``until``, then park the
        clock at ``until`` (when finite).  ``advance()`` with no bound
        drains to quiescence — the one-shot behaviour."""
        heap = self.heap
        eta = self.eta
        while True:
            em = float(eta.min()) if len(eta) else np.inf
            xm = float(self.xeta[:self.xn].min()) if self.xlive else np.inf
            t_next = heap[0][0] if heap else np.inf
            if em < t_next:
                t_next = em
            if xm < t_next:
                t_next = xm
            if t_next == np.inf or t_next > until:
                break
            if t_next > self.time:
                self.time = t_next
            time = self.time
            # all events at this timestamp drain before one flush reprices
            # what they touched; repeat while the flush re-projected rates
            # (zero-duration pileups surface as fresh same-time work)
            first = True
            while True:
                while heap and heap[0][0] <= time:
                    _, _, kind, payload = heapq.heappop(heap)
                    self.n_events += 1
                    if kind == _RELEASE:
                        s = payload
                        task = self.allt[s]
                        # initial input payload from the origin device
                        if (task.origin is not None and self.in_bytes[s] > 0
                                and not self.preds[s]):
                            if self._launch(s, task.origin, self.dev_name[s],
                                            self.in_bytes[s]):
                                continue
                        self._arrived(s)
                    elif kind == _ARRIVE:
                        self._arrived(payload)
                    else:
                        self._intervene(payload)
                if first or em <= time:
                    done = np.nonzero(eta <= time)[0]
                    if len(done):
                        self._complete_compute(done)
                if self.xlive and (first or xm <= time):
                    xdone = np.nonzero(self.xeta[:self.xn] <= time)[0]
                    if len(xdone):
                        self._complete_transfers(xdone)
                first = False
                if not self._flush():
                    break
                # a flush ran: re-projected rates may complete at `time`
                em = float(eta.min()) if len(eta) else np.inf
                xm = float(self.xeta[:self.xn].min()) if self.xlive \
                    else np.inf
                if em > time and xm > time and not (heap and
                                                    heap[0][0] <= time):
                    break
        if until != np.inf and until > self.time:
            self.time = until
        return self

    def run(self) -> Timeline:
        """One-shot traverse: open, drain to quiescence, report."""
        self._start()
        self.advance()
        return self._timeline()

    def timeline(self, partial: bool = False) -> Timeline:
        """Snapshot the timeline.  ``partial=True`` reports whatever has
        happened so far (pending/running tasks simply lack entries);
        ``partial=False`` asserts quiescence, as ``run()`` does."""
        return self._timeline(partial=partial)

    def _timeline(self, partial: bool = False) -> Timeline:
        if not partial:
            missing = [self.uidl[s] for s in range(self.n)
                       if not self.is_bg[s]
                       and self.finish[s] != self.finish[s]]
            if missing:
                raise RuntimeError(
                    f"traverse deadlock: unfinished {missing[:5]}")
        tl = Timeline(mapping=dict(self.mapping))
        tl.n_intervals = self.n_intervals
        tl.n_events = self.n_events
        for s in range(self.n):
            uid = self.uidl[s]
            if self.is_bg[s]:
                # background jobs may legitimately still be running; report
                # a projected finish assuming the final interval persists
                tl.start[uid] = self.start[s]
                tl.standalone[uid] = self.standalone[s]
                if not math.isnan(self.finish[s]):
                    tl.finish[uid] = self.finish[s]
                elif s in self.dev_members.get(self.dev_ol[s], ()):
                    tl.finish[uid] = self.time + float(self.W[s]
                                                       / self.rate[s])
                continue
            if not math.isnan(self.standalone[s]):
                tl.start[uid] = self.start[s]
                tl.standalone[uid] = self.standalone[s]
            if not math.isnan(self.finish[s]):
                tl.finish[uid] = self.finish[s]
            if not math.isnan(self.ready_t[s]):
                tl.ready[uid] = self.ready_t[s]
                tl.comm[uid] = self.comm_t[s]
            if not math.isnan(self.qwait[s]):
                tl.queue_wait[uid] = self.qwait[s]
        return tl
