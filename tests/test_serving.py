"""Online serving continuum: resident-timeline parity with the offline
batch path (1e-9), seeded arrival-stream determinism, admission-control
verdicts, ledger reconciliation, and tail-metric reporting."""
import itertools
from itertools import groupby

import numpy as np
import pytest

import repro.core.task as task_mod
from repro.core import (ClosedLoopClients, DiurnalArrivals, PoissonArrivals,
                        SchedulerSession, ServeLoop, TaskGraph, TenantSpec,
                        build_orchestrators, build_testbed,
                        ground_truth_traverser, heye_traverser,
                        mining_workload, single_task_request, vr_workload)
from repro.core.timeline import TimelineEngine
from repro.core.topology import make_task
from repro.serve.admission import (AdaptiveWindow, AdmissionController,
                                   Decision, Verdict, admit_all)

TOL = 1e-9


def _testbed(mult=1):
    return build_testbed(
        edge_counts={"orin_agx": 2 * mult, "xavier_agx": mult,
                     "orin_nano": mult, "xavier_nx": mult},
        server_counts={"server1": 1, "server2": 1})


def _mapped(workload_fn, seed_uid, mult=1):
    """Two identical (testbed, cfg, mapping) copies so each engine runs
    on untouched state; mapping comes from a real session drive."""
    out = []
    for _ in range(2):
        task_mod._task_counter = itertools.count(seed_uid)
        tb = _testbed(mult)
        cfg = workload_fn(tb)
        root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
        s = SchedulerSession(tb.graph, root)
        s.submit(cfg)
        s.map_pending()
        out.append((tb, cfg, dict(s.mapping)))
    return out


def _assert_parity(tl_ref, tl_arr, tol=TOL):
    assert set(tl_ref.finish) == set(tl_arr.finish)
    for k in tl_ref.finish:
        assert tl_ref.finish[k] == pytest.approx(tl_arr.finish[k],
                                                 abs=tol, rel=tol), k
    for k in tl_ref.start:
        assert tl_ref.start[k] == pytest.approx(tl_arr.start[k],
                                                abs=tol, rel=tol), k
    for k in tl_ref.queue_wait:
        assert tl_ref.queue_wait[k] == pytest.approx(
            tl_arr.queue_wait.get(k, 0.0), abs=tol, rel=tol), k
    for k in tl_ref.comm:
        assert tl_ref.comm[k] == pytest.approx(tl_arr.comm.get(k, 0.0),
                                               abs=tol, rel=tol), k


# ---------------------------------------------------------------------------
# online-vs-offline parity (the acceptance bar)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("noise_seed", [None, 0])
def test_upfront_resident_parity_mining(noise_seed):
    """Fig. 13 config: the full workload submitted upfront through a
    resident engine reproduces the seed heapq loop to 1e-9 (prediction
    and noisy-ground-truth models)."""
    (tb1, cfg1, m1), (tb2, cfg2, m2) = _mapped(
        lambda tb: mining_workload(tb, n_sensors=18, n_readings=2),
        seed_uid=600_000)
    mk1 = (heye_traverser(tb1.graph) if noise_seed is None
           else ground_truth_traverser(tb1.graph, noise_seed))
    mk2 = (heye_traverser(tb2.graph) if noise_seed is None
           else ground_truth_traverser(tb2.graph, noise_seed))
    tl_ref = mk1.traverse_reference(cfg1, m1)
    eng = TimelineEngine.open(mk2, cfg=cfg2, mapping=dict(m2))
    tl_on = eng.advance().timeline()
    _assert_parity(tl_ref, tl_on)


@pytest.mark.parametrize("noise_seed", [None, 3])
def test_upfront_resident_parity_vr(noise_seed):
    """Fig. 14-style VR chains: serial deps and cross-device transfers
    through the resident path."""
    (tb1, cfg1, m1), (tb2, cfg2, m2) = _mapped(
        lambda tb: vr_workload(tb, n_frames=5), seed_uid=610_000)
    mk1 = (heye_traverser(tb1.graph) if noise_seed is None
           else ground_truth_traverser(tb1.graph, noise_seed))
    mk2 = (heye_traverser(tb2.graph) if noise_seed is None
           else ground_truth_traverser(tb2.graph, noise_seed))
    _assert_parity(mk1.traverse_reference(cfg1, m1),
                   TimelineEngine.open(mk2, cfg=cfg2,
                                       mapping=dict(m2)).advance().timeline())


def test_wave_injection_parity():
    """Injecting the workload wave-by-wave (advance to just before each
    release instant, then inject that release cohort) is event-for-event
    identical to the one-shot run — the live-traffic core claim."""
    (tb1, cfg1, m1), (tb2, cfg2, m2) = _mapped(
        lambda tb: mining_workload(tb, n_sensors=18, n_readings=3),
        seed_uid=620_000)
    tl_ref = ground_truth_traverser(tb1.graph, 1).traverse_reference(cfg1, m1)
    eng = TimelineEngine.open(ground_truth_traverser(tb2.graph, 1),
                              mapping=dict(m2))
    eng.cfg = cfg2          # dependency edges resolve against the graph
    tasks = sorted(cfg2, key=lambda t: (t.release_time, t.uid))
    for rel, grp in groupby(tasks, key=lambda t: t.release_time):
        eng.advance(np.nextafter(rel, -np.inf))
        eng.inject(list(grp))
    tl_on = eng.advance().timeline()
    _assert_parity(tl_ref, tl_on)


@pytest.mark.parametrize("kind", ["bandwidth", "dead"])
def test_resident_churn_parity(kind):
    """mark_dead / set_bandwidth mid-stream: `schedule` on a resident
    engine matches `traverse(..., interventions=...)` while work is
    injected wave-by-wave around the churn instant."""
    (tb1, cfg1, m1), (tb2, cfg2, m2) = _mapped(
        lambda tb: mining_workload(tb, n_sensors=24, n_readings=2),
        seed_uid=630_000)

    def fns(tb):
        if kind == "bandwidth":
            return [(0.02, lambda: tb.graph.set_bandwidth(
                        f"link_{tb.edges[0]}", 1e6)),
                    (0.15, lambda: tb.graph.set_bandwidth(
                        f"link_{tb.edges[0]}", 1e9))]
        e = tb.edges[1]
        return [(0.03, lambda: tb.graph.mark_dead(e)),
                (0.12, lambda: tb.graph.mark_alive(e))]

    tl_ref = ground_truth_traverser(tb1.graph, 2).traverse_reference(
        cfg1, m1, interventions=fns(tb1))
    eng = TimelineEngine.open(ground_truth_traverser(tb2.graph, 2),
                              mapping=dict(m2))
    eng.cfg = cfg2
    for t, fn in fns(tb2):
        eng.schedule(t, fn)
    tasks = sorted(cfg2, key=lambda t: (t.release_time, t.uid))
    for rel, grp in groupby(tasks, key=lambda t: t.release_time):
        eng.advance(np.nextafter(rel, -np.inf))
        eng.inject(list(grp))
    _assert_parity(tl_ref, eng.advance().timeline())


def test_session_finalize_online_matches_execute():
    """The session-level wiring: open_timeline after mapping, drain, and
    the RunStats match the offline execute() to 1e-9 (overhead columns
    included).  Twin sessions so each path consumes a fresh noise
    stream."""
    def drive(online):
        task_mod._task_counter = itertools.count(640_000)
        tb = _testbed()
        cfg = mining_workload(tb, n_sensors=12, n_readings=2)
        root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
        s = SchedulerSession(tb.graph, root,
                             truth=ground_truth_traverser(tb.graph, 0))
        s.submit(cfg)
        s.map_pending()
        if not online:
            return s, s.execute()
        s.open_timeline()
        return s, s.finalize_online()

    s_off, off = drive(online=False)
    s_on, on = drive(online=True)
    assert s_on.engine_opens == 1
    _assert_parity(off.timeline, on.timeline)
    assert on.overhead == off.overhead
    assert on.mapping == off.mapping


# ---------------------------------------------------------------------------
# resident-engine API contracts
# ---------------------------------------------------------------------------
def test_inject_into_past_raises():
    tb = _testbed()
    eng = TimelineEngine.open(heye_traverser(tb.graph))
    eng.advance(0.5)
    late = make_task("dnn", origin=tb.edges[0], release_time=0.1)
    eng.cfg.add(late)
    with pytest.raises(ValueError):
        eng.inject([late], mapping={late.uid: f"{tb.edges[0]}.gpu"})


def test_drain_finished_and_finish_of():
    tb = _testbed()
    eng = TimelineEngine.open(heye_traverser(tb.graph))
    t1 = make_task("dnn", origin=tb.edges[0], release_time=0.0)
    t2 = make_task("dnn", origin=tb.edges[0], release_time=10.0)
    for t in (t1, t2):
        eng.cfg.add(t)
    eng.inject([t1, t2], mapping={t.uid: f"{tb.edges[0]}.gpu"
                                  for t in (t1, t2)})
    assert np.isnan(eng.finish_of(t1.uid))
    eng.advance(5.0)
    done = eng.drain_finished()
    assert [t.uid for t in done] == [t1.uid]
    assert eng.drain_finished() == []               # cursor moved
    assert eng.finish_of(t1.uid) > 0.0
    assert np.isnan(eng.finish_of(t2.uid))          # not yet released
    eng.advance()
    assert [t.uid for t in eng.drain_finished()] == [t2.uid]
    # partial snapshots never raised mid-run; the final one is complete
    assert set(eng.timeline().finish) == {t1.uid, t2.uid}


def test_timeline_partial_mid_run():
    tb = _testbed()
    eng = TimelineEngine.open(heye_traverser(tb.graph))
    t1 = make_task("dnn", origin=tb.edges[0], release_time=0.0)
    t2 = make_task("dnn", origin=tb.edges[0], release_time=10.0)
    for t in (t1, t2):
        eng.cfg.add(t)
    eng.inject([t1, t2], mapping={t.uid: f"{tb.edges[0]}.gpu"
                                  for t in (t1, t2)})
    eng.advance(5.0)
    snap = eng.timeline(partial=True)
    assert t1.uid in snap.finish and t2.uid not in snap.finish
    with pytest.raises(RuntimeError):
        eng.timeline()                              # t2 still pending


def test_noisy_slowdown_model_rejected_for_resident():
    from repro.core import DecoupledSlowdown, Traverser, truth_params
    tb = _testbed()
    noisy = Traverser(tb.graph, DecoupledSlowdown(
        tb.graph, truth_params(), rng=np.random.default_rng(0)))
    with pytest.raises(ValueError):
        TimelineEngine.open(noisy)


# ---------------------------------------------------------------------------
# arrival processes: determinism + shape
# ---------------------------------------------------------------------------
def test_poisson_stream_deterministic():
    a = PoissonArrivals(rate=500.0, seed=42)
    b = PoissonArrivals(rate=500.0, seed=42)
    ta, tb_ = a.times(2.0), b.times(2.0)
    np.testing.assert_array_equal(ta, tb_)
    np.testing.assert_array_equal(ta, a.times(2.0))    # re-entrant
    assert (np.diff(ta) > 0).all() and ta[-1] < 2.0
    # rate sanity: ~1000 arrivals over 2 s at 500 rps
    assert 800 < len(ta) < 1200
    assert len(PoissonArrivals(rate=500.0, seed=7).times(2.0)) != 0
    assert not np.array_equal(PoissonArrivals(rate=500.0, seed=7).times(2.0),
                              ta)


def test_diurnal_stream_deterministic_and_rate_shaped():
    d1 = DiurnalArrivals(base_rate=50.0, peak_rate=500.0, period=2.0,
                         seed=3, phase=0.0)
    d2 = DiurnalArrivals(base_rate=50.0, peak_rate=500.0, period=2.0,
                         seed=3, phase=0.0)
    t1 = d1.times(2.0)
    np.testing.assert_array_equal(t1, d2.times(2.0))
    assert (np.diff(t1) > 0).all()
    # phase 0: trough at t=0, peak at t=period/2 — the peak half must
    # carry clearly more arrivals than the trough quarters
    q = np.histogram(t1, bins=4, range=(0.0, 2.0))[0]
    assert q[1] + q[2] > 2.0 * (q[0] + q[3])
    assert float(d1.rate(0.0)) == pytest.approx(50.0)
    assert float(d1.rate(1.0)) == pytest.approx(500.0)


def test_serve_loop_replays_identically():
    """Same seeds, same testbed -> byte-identical serving outcomes."""
    def once():
        task_mod._task_counter = itertools.count(650_000)
        tb = _testbed()
        root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
        tenants = [TenantSpec(
            "m", PoissonArrivals(rate=300, seed=5),
            single_task_request("svm", origin=tb.edges[0], sla=0.1),
            sla=0.1)]
        loop = ServeLoop(tb.graph, root, tenants,
                         truth=ground_truth_traverser(tb.graph, 0),
                         admission=admit_all(), horizon=0.25)
        st = loop.run()
        return ([r.verdict for r in st.requests],
                [r.latency for r in st.accepted])
    v1, l1 = once()
    v2, l2 = once()
    assert v1 == v2 and l1 == l2


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------
class _FixedArrivals:
    """Test arrivals: explicit instants."""

    def __init__(self, instants):
        self.instants = np.asarray(instants, dtype=np.float64)

    def times(self, horizon):
        return self.instants[self.instants < horizon]


def _one_request_loop(tb, admission, sla, arrivals=(0.01,),
                      max_inflight=None):
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    tenants = [TenantSpec(
        "t0", _FixedArrivals(arrivals),
        single_task_request("svm", origin=tb.edges[0], sla=sla), sla=sla,
        max_inflight=max_inflight)]
    return ServeLoop(tb.graph, root, tenants,
                     truth=ground_truth_traverser(tb.graph, 0),
                     admission=admission, horizon=1.0)


def test_admission_projected_sla_reject():
    """A deadline far below any projected completion is refused up front
    with the projected_sla reason (or infeasible, if the walk itself
    refuses), and the ledger holds no belief for it afterwards."""
    task_mod._task_counter = itertools.count(660_000)
    tb = _testbed()
    loop = _one_request_loop(tb, AdmissionController(slack=1.0), sla=1e-7)
    st = loop.run()
    assert len(st.requests) == 1
    (req,) = st.requests
    assert req.verdict == "rejected"
    assert req.reject_reason in ("projected_sla", "infeasible")
    assert st.sla_attainment() == {"t0": 0.0}    # a reject is a miss
    assert len(loop.session.policy.ledger) == 0
    assert len(loop.session.cfg) == 0            # withdrawn from the CFG


def test_admission_defer_then_reject():
    """max_inflight=0 quota: each attempt defers until max_defers is
    exhausted, then rejects with the quota reason."""
    task_mod._task_counter = itertools.count(665_000)
    tb = _testbed()
    loop = _one_request_loop(
        tb, AdmissionController(defer_delay=0.01, max_defers=2),
        sla=0.5, max_inflight=0)
    st = loop.run()
    (req,) = st.requests
    assert req.verdict == "rejected"
    assert req.reject_reason == "inflight_cap"
    assert req.defers == 2 and st.deferrals == 2


def test_admission_defer_then_accept():
    """A deferred request retries later and is admitted once inflight
    drops; its latency includes the defer wait."""
    task_mod._task_counter = itertools.count(670_000)
    tb = _testbed()
    # two arrivals, cap 1: the second defers while the first runs
    loop = _one_request_loop(
        tb, AdmissionController(slack=float("inf"), defer_delay=0.2,
                                max_defers=10),
        sla=None, arrivals=(0.01, 0.011), max_inflight=1)
    st = loop.run()
    assert [r.verdict for r in st.requests] == ["accepted", "accepted"]
    second = st.requests[1]
    assert second.defers >= 1
    assert second.latency > 0.2 * second.defers     # waited out the defers
    assert st.engine_opens == 1


def test_admit_all_controller():
    task_mod._task_counter = itertools.count(675_000)
    tb = _testbed()
    loop = _one_request_loop(tb, admit_all(), sla=1e-7)   # absurd SLA
    st = loop.run()
    assert st.requests[0].verdict == "accepted"           # mapped => in
    assert st.sla_attainment() == {"t0": 0.0}             # but missed


def test_decision_constructors():
    assert Decision.accept().verdict is Verdict.ACCEPT
    d = Decision.defer("quota", retry_at=1.5)
    assert d.verdict is Verdict.DEFER and d.retry_at == 1.5
    assert Decision.reject("x").reason == "x"


# ---------------------------------------------------------------------------
# end-to-end loop + reporting
# ---------------------------------------------------------------------------
def test_serve_loop_end_to_end_multi_tenant():
    task_mod._task_counter = itertools.count(680_000)
    tb = _testbed()
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    tenants = [
        TenantSpec("mining", PoissonArrivals(rate=300, seed=1),
                   single_task_request("svm", origin=tb.edges[0], sla=0.1),
                   sla=0.1),
        TenantSpec("vision", DiurnalArrivals(base_rate=80, peak_rate=240,
                                             period=0.25, seed=2),
                   single_task_request("mlp", origin=tb.edges[1], sla=0.15),
                   sla=0.15),
    ]
    loop = ServeLoop(tb.graph, root, tenants,
                     truth=ground_truth_traverser(tb.graph, 0),
                     admission=AdmissionController(slack=3.0),
                     horizon=0.25)
    st = loop.run()
    s = st.summary()
    assert s["engine_opens"] == 1                   # zero rebuilds
    assert s["requests"] == s["accepted"] + s["rejected"]
    assert s["requests"] > 20
    # every accepted request finished once the loop drained
    assert all(r.finish == r.finish for r in st.accepted)
    # tail ordering + shared percentile definitions
    assert s["p50_ms"] <= s["p99_ms"] <= s["p999_ms"]
    for ten, att in st.sla_attainment().items():
        assert 0.0 <= att <= 1.0
    per = st.latency_percentiles_by_tenant()
    assert set(per) == {"mining", "vision"}
    # inflight accounting returned to zero
    assert all(v == 0 for v in loop._inflight.values())
    # tenant stamps landed on the tasks
    assert all(t.attrs["tenant"] == r.tenant
               for r in st.accepted for t in r.tasks)


def test_serve_loop_with_mid_run_churn():
    """Topology churn under live traffic: the loop keeps serving across
    a mark_dead/mark_alive cycle with zero engine rebuilds."""
    task_mod._task_counter = itertools.count(690_000)
    tb = _testbed()
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    e = tb.edges[1]
    tenants = [TenantSpec(
        "m", PoissonArrivals(rate=200, seed=9),
        single_task_request("svm", origin=tb.edges[0], sla=0.2), sla=0.2)]
    loop = ServeLoop(tb.graph, root, tenants,
                     truth=ground_truth_traverser(tb.graph, 0),
                     admission=admit_all(), horizon=0.3,
                     interventions=[(0.1, lambda: tb.graph.mark_dead(e)),
                                    (0.2, lambda: tb.graph.mark_alive(e))])
    st = loop.run()
    assert st.engine_opens == 1
    assert len(st.accepted) > 10


# ---------------------------------------------------------------------------
# offline tail metrics (RunStats) + session withdraw + ledger retire
# ---------------------------------------------------------------------------
def test_runstats_latency_percentiles_and_tenants():
    task_mod._task_counter = itertools.count(700_000)
    tb = _testbed()
    cfg = mining_workload(tb, n_sensors=12, n_readings=2)
    for i, t in enumerate(cfg):
        t.attrs["tenant"] = f"g{i % 2}"
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    s = SchedulerSession(tb.graph, root,
                         truth=ground_truth_traverser(tb.graph, 0))
    stats = s.run(cfg)
    pct = stats.latency_percentiles(cfg)
    assert set(pct) == {50.0, 99.0, 99.9}
    assert pct[50.0] <= pct[99.0] <= pct[99.9]
    lats = stats.latencies(cfg)
    assert len(lats) == len(list(cfg))
    assert pct[99.9] <= max(lats) + 1e-12
    per = stats.latency_percentiles_by_tenant(cfg)
    assert set(per) == {"g0", "g1"}
    att = stats.sla_attainment(cfg)
    assert set(att) == {"g0", "g1"}
    for v in att.values():
        assert 0.0 <= v <= 1.0


def test_percentiles_helper_empty_and_exact():
    from repro.core.session import percentiles
    out = percentiles([])
    assert all(np.isnan(v) for v in out.values())
    out = percentiles([1.0, 2.0, 3.0], qs=(0.0, 50.0, 100.0))
    assert out[0.0] == 1.0 and out[50.0] == 2.0 and out[100.0] == 3.0


def test_session_withdraw_restores_state():
    task_mod._task_counter = itertools.count(710_000)
    tb = _testbed()
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    s = SchedulerSession(tb.graph, root)
    g = TaskGraph("req")
    t = make_task("svm", origin=tb.edges[0], release_time=0.05)
    g.add(t)
    rel0 = t.release_time
    s.submit(g)
    res = s.map_pending(fallback=False)[t.uid]
    assert res is not None
    assert len(root.ledger) == 1
    s.withdraw(t)
    assert t.release_time == rel0              # overhead charge reverted
    assert t.assigned_pu is None
    assert len(root.ledger) == 0
    assert t.uid not in s.mapping and len(s.cfg) == 0
    # the same task can be resubmitted and mapped again
    g2 = TaskGraph("req2")
    g2.add(t)
    s.submit(g2)
    assert s.map_pending()[t.uid] is not None


def test_ledger_retire_batch():
    task_mod._task_counter = itertools.count(720_000)
    tb = _testbed()
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    s = SchedulerSession(tb.graph, root)
    cfg = mining_workload(tb, n_sensors=4, n_readings=1)
    s.submit(cfg)
    s.map_pending()
    uids = [t.uid for t in cfg]
    n0 = len(root.ledger)
    assert n0 == len(uids)
    killed = root.ledger.retire(uids[:5])
    assert killed == 5
    assert len(root.ledger) == n0 - 5
    assert root.ledger.retire([999_999_999]) == 0    # unknown: no-op
    assert root.ledger.retire([]) == 0


def test_taskgraph_remove_drops_edges():
    g = TaskGraph()
    a = make_task("svm")
    b = make_task("svm")
    g.add(a)
    g.add(b, deps=[a])
    g.remove(a)
    assert len(g) == 1 and g.preds(b) == []
    g.remove(b)
    assert len(g) == 0


# ---------------------------------------------------------------------------
# small-wave serving fast path: whole-run oracle parity
# ---------------------------------------------------------------------------
def _serve_run(seed_uid, interventions=None, slack=4.0, horizon=0.3,
               batch_window=0.0):
    task_mod._task_counter = itertools.count(seed_uid)
    tb = _testbed()
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    tenants = [
        TenantSpec("mining", PoissonArrivals(rate=250, seed=21),
                   single_task_request("svm", origin=tb.edges[0], sla=0.1),
                   sla=0.1),
        TenantSpec("vision", DiurnalArrivals(base_rate=60, peak_rate=180,
                                             period=horizon, seed=22),
                   single_task_request("mlp", origin=tb.edges[1], sla=0.15),
                   sla=0.15),
    ]
    iv = []
    if interventions is not None:
        iv = [(t, fn(tb)) for t, fn in interventions]
    loop = ServeLoop(tb.graph, root, tenants,
                     truth=ground_truth_traverser(tb.graph, 0),
                     admission=AdmissionController(slack=slack,
                                                   defer_delay=0.005,
                                                   max_defers=1),
                     batch_window=batch_window,
                     horizon=horizon, interventions=iv)
    return loop.run()


def _assert_request_parity(fast, cold, tol=TOL):
    assert len(fast.requests) == len(cold.requests)
    for a, b in zip(fast.requests, cold.requests):
        assert a.verdict == b.verdict, a.rid
        assert a.reject_reason == b.reject_reason, a.rid
        if np.isnan(a.finish) and np.isnan(b.finish):
            continue
        assert a.finish == pytest.approx(b.finish, abs=tol, rel=tol), a.rid


@pytest.mark.parametrize("slack", [4.0, 0.35])
def test_serve_fastpath_oracle_parity(monkeypatch, slack):
    """The session-resident fast path reproduces the cold per-wave walk
    request for request — verdicts, reject reasons and finish times to
    1e-9 — for both an all-accept mix and a tight-slack mix that
    exercises refusal and withdraw."""
    fast = _serve_run(730_000, slack=slack)
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "0")
    cold = _serve_run(730_000, slack=slack)
    assert fast.requests and fast.engine_opens == 1
    if slack == 0.35:      # the tight mix must actually refuse something
        assert any(r.verdict == "rejected" for r in fast.requests)
    _assert_request_parity(fast, cold)


def test_serve_fastpath_parity_with_churn(monkeypatch):
    """Mid-run churn (death + revival under live traffic) invalidates
    exactly the persistent state it must: the fast path still matches
    the oracle walk whole-run."""
    iv = [(0.08, lambda tb: (lambda e=tb.edges[1]:
                             tb.graph.mark_dead(e))),
          (0.18, lambda tb: (lambda e=tb.edges[1]:
                             tb.graph.mark_alive(e)))]
    fast = _serve_run(740_000, interventions=iv)
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "0")
    cold = _serve_run(740_000, interventions=iv)
    assert fast.engine_opens == 1
    _assert_request_parity(fast, cold)


# ---------------------------------------------------------------------------
# overload-adaptive admission coalescing
# ---------------------------------------------------------------------------
def test_adaptive_window_math():
    w = AdaptiveWindow(max_window=0.01, depth_hi=10, proj_hi=2.0)
    assert w.window(0, 0.0) == 0.0                 # idle -> per-arrival
    assert w.window(0, 1.0) == 0.0                 # at-deadline: no pressure
    assert w.window(5, 0.0) == pytest.approx(0.005)
    assert w.window(10, 0.0) == pytest.approx(0.01)
    assert w.window(40, 0.0) == pytest.approx(0.01)    # capped
    assert w.window(0, 1.5) == pytest.approx(0.005)    # slowdown pressure
    assert w.window(0, 3.0) == pytest.approx(0.01)
    # max of the two pressures, not the sum
    assert w.window(5, 1.5) == pytest.approx(0.005)
    lo = AdaptiveWindow(max_window=0.01, min_window=0.002)
    assert lo.window(0, 0.0) == 0.002


def test_adaptive_window_loop_deterministic():
    """Adaptive coalescing keeps the loop deterministic: same seeds give
    identical wave boundaries and outcomes, and pressure actually widens
    waves beyond one request under load."""
    bw = AdaptiveWindow(max_window=0.01, depth_hi=4)
    a = _serve_run(750_000, batch_window=bw, slack=float("inf"))
    b = _serve_run(750_000, batch_window=bw, slack=float("inf"))
    assert a.wave_sizes == b.wave_sizes
    assert [r.verdict for r in a.requests] == \
        [r.verdict for r in b.requests]
    assert [r.finish for r in a.accepted] == [r.finish for r in b.accepted]
    assert max(a.wave_sizes) > 1           # pressure coalesced something
    # every arrival pops in exactly one wave; each deferral re-pops once
    assert sum(a.wave_sizes) == len(a.requests) + a.deferrals


# ---------------------------------------------------------------------------
# closed-loop clients
# ---------------------------------------------------------------------------
def test_closed_loop_clients_validation_and_streams():
    with pytest.raises(ValueError):
        ClosedLoopClients(clients=0, think_mean=0.1)
    with pytest.raises(ValueError):
        ClosedLoopClients(clients=2, think_mean=0.0)
    c = ClosedLoopClients(clients=8, think_mean=0.05, seed=3)
    first = c.initial_arrivals(10.0)
    assert len(first) == 8 and all(t >= 0.0 for t, _ in first)
    d1 = c.think(0)
    # re-seeding restores every substream: same first arrivals, same draws
    again = c.initial_arrivals(10.0)
    assert again == first
    assert c.think(0) == d1


def test_closed_loop_serving_deterministic_and_self_clocked():
    """A closed-loop population issues its next request only after the
    previous one completes (or is refused): two runs replay identically
    and per-client request streams never overlap in time."""
    def once():
        task_mod._task_counter = itertools.count(760_000)
        tb = _testbed()
        root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
        tenants = [TenantSpec(
            "cl", ClosedLoopClients(clients=6, think_mean=0.02, seed=7),
            single_task_request("svm", origin=tb.edges[0], sla=0.2),
            sla=0.2)]
        loop = ServeLoop(tb.graph, root, tenants,
                         truth=ground_truth_traverser(tb.graph, 0),
                         admission=admit_all(), horizon=0.4)
        return loop.run()
    a = once()
    b = once()
    assert len(a.requests) > 6              # completions spawned new ones
    assert a.engine_opens == 1
    assert [r.verdict for r in a.requests] == \
        [r.verdict for r in b.requests]
    assert [(r.client, r.arrival, r.finish) for r in a.requests] == \
        [(r.client, r.arrival, r.finish) for r in b.requests]
    # per client: next arrival only after the previous request resolved
    by_client: dict = {}
    for r in sorted(a.requests, key=lambda r: r.arrival):
        by_client.setdefault(r.client, []).append(r)
    for reqs in by_client.values():
        for prev, nxt in zip(reqs, reqs[1:]):
            bound = prev.finish if prev.finish == prev.finish \
                else prev.arrival
            assert nxt.arrival >= bound - TOL


# ---------------------------------------------------------------------------
# the loop's spans and the walk's cache counters (repro.core.trace)
# ---------------------------------------------------------------------------
def test_loop_spans_feed_phase_walls_and_count_caches():
    """``phase_wall`` is read off the loop's own spans: one ``serve.wave``
    per admission wave, and the map and advance phases equal to the
    ``serve.map`` / ``timeline.advance`` walls.  The walk's caches count
    their lookups on the serving fast path."""
    from repro.core import trace
    s0 = trace.snapshot()
    stats = _serve_run(760_000)
    s1 = trace.snapshot()

    def span(name):
        a = s0["spans"].get(name, (0, 0.0, 0.0))
        return tuple(x - y for x, y in zip(s1["spans"][name], a))

    pw = stats.phase_wall
    assert span("serve.wave")[0] == len(stats.wave_sizes)
    assert span("serve.admit")[0] == len(stats.wave_sizes)
    assert span("serve.map")[1] == pytest.approx(pw["map"], rel=1e-9)
    assert span("timeline.advance")[1] == pytest.approx(pw["advance"],
                                                        rel=1e-9)
    assert span("serve.sync")[1] == pytest.approx(pw["sync"], rel=1e-9)
    assert span("serve.admit")[1] == pytest.approx(pw["admit"] + pw["map"],
                                                   rel=1e-9)
    # a wave holds its admission (and one sync); waves and the advances
    # between them never overlap, so together they fit in the run's wall
    assert span("serve.admit")[1] <= span("serve.wave")[1]
    assert 0.0 <= span("serve.wave")[2] <= span("serve.wave")[1]
    top = span("serve.wave")[1] + span("timeline.advance")[1]
    assert top <= stats.wall_s
    assert span("timeline.flush")[0] > 0
    assert span("slowdown.score")[0] > 0
    for cache in ("eff", "ident", "canon"):
        looked = sum(s1["counters"].get(f"cache.{cache}.{k}", 0)
                     - s0["counters"].get(f"cache.{cache}.{k}", 0)
                     for k in ("hit", "miss"))
        assert looked > 0, cache
