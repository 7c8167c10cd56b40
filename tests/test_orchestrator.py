"""Orchestrator tests (paper §3.5, Alg. 1): hierarchy construction,
local-first mapping, escalation, constraint protection, overhead ledger;
plus whole-session parity of the fused wave-batched walk against the
sequential per-task oracle (``REPRO_FUSED_WALK=0``)."""
import pytest

from repro.core import (ActiveLedger, OrcConfig, Orchestrator, Traverser,
                        build_orchestrators, build_testbed, heye_traverser,
                        trace)
from repro.core.topology import make_task


@pytest.fixture()
def setup():
    tb = build_testbed(edge_counts={"orin_agx": 1, "orin_nano": 1},
                       server_counts={"server1": 1, "server2": 1})
    trav = heye_traverser(tb.graph)
    root = build_orchestrators(tb.graph, trav)
    return tb, trav, root


def test_hierarchy_matches_fig4b(setup):
    tb, _, root = setup
    # root has two cluster ORCs (edge + server), each with device children
    assert len(root.children) == 2
    groups = sorted(c.group for c in root.children)
    assert groups == ["edge_cluster", "server_cluster"]
    devices = [o.group for c in root.children for o in c.children]
    assert set(devices) == set(tb.edges) | set(tb.servers)
    # device ORCs know their own PUs only (resource segregation)
    for c in root.children:
        for dev in c.children:
            assert dev.leaf_pus
            assert all(p.startswith(dev.group + ".") for p in dev.leaf_pus)
    # cluster and root ORCs hold no PUs directly
    assert not root.leaf_pus
    assert all(not c.leaf_pus for c in root.children)


def test_local_first_assignment(setup):
    tb, _, root = setup
    e = tb.edges[0]
    orc = root.find_device_orc(e)
    t = make_task("capture", origin=e, deadline=0.1)
    res = orc.map_batch([t])[0]
    assert res is not None
    assert res.pu.startswith(e + ".")       # stayed local
    assert res.hops == 0                    # no remote queries
    assert t.assigned_pu == res.pu


def test_escalation_to_server(setup):
    tb, _, root = setup
    e = tb.edges[1]                         # orin_nano: render at 90 ms
    orc = root.find_device_orc(e)
    t = make_task("render", origin=e, deadline=0.030, input_bytes=4e3)
    res = orc.map_batch([t])[0]
    assert res is not None
    dev = tb.graph.device_of(res.pu).name
    assert dev in tb.servers                # escalated off-device
    assert res.hops > 0                     # remote messages counted
    assert res.overhead > 0.0


def test_pinned_stays_local(setup):
    tb, _, root = setup
    e = tb.edges[1]
    orc = root.find_device_orc(e)
    t = make_task("capture", origin=e, deadline=0.1)
    t.attrs["pinned"] = True
    res = orc.map_batch([t])[0]
    assert tb.graph.device_of(res.pu).name == e


def test_existing_task_constraints_protected(setup):
    """Alg. 1 l.15: a new task must not break a resident task's deadline."""
    tb, trav, root = setup
    e = tb.edges[0]
    orc = root.find_device_orc(e)
    gpu = f"{e}.gpu"
    # resident: a GPU task with a deadline it barely meets
    sa = tb.graph.nodes[gpu].predict(make_task("dnn"))
    resident = make_task("dnn", origin=e, deadline=sa * 1.05)
    pred = trav.predict_task(resident, gpu, [])
    orc.ledger.add(resident, gpu, pred, now=0.0)
    # a new heavy task on the same GPU would slow the resident beyond 1.05x
    newbie = make_task("dnn", origin=e, deadline=10.0)
    ok, _ = orc._check_constraints(newbie, gpu, now=0.0)
    assert not ok
    # but a task on a PU that does not contend hard is fine
    ok2, _ = orc._check_constraints(
        make_task("capture", origin=e, deadline=10.0), f"{e}.cpu0", now=0.0)
    assert ok2


def test_best_effort_when_nothing_fits(setup):
    tb, _, root = setup
    e = tb.edges[0]
    orc = root.find_device_orc(e)
    t = make_task("render", origin=e, deadline=1e-9)   # impossible deadline
    res = orc.map_batch([t])[0]
    assert res is not None                  # degraded, not dropped
    t2 = make_task("render", origin=e, deadline=1e-9)
    cfg = OrcConfig(allow_best_effort=False)
    orc2 = build_orchestrators(tb.graph, heye_traverser(tb.graph),
                               config=cfg).find_device_orc(e)
    assert orc2.map_batch([t2])[0] is None


def test_ledger_prune_and_remove(setup):
    tb, trav, root = setup
    e = tb.edges[0]
    led = ActiveLedger()
    t = make_task("dnn", origin=e)
    led.add(t, f"{e}.gpu", trav.predict_task(t, f"{e}.gpu", []), now=0.0)
    assert led.count(f"{e}.gpu") == 1
    led.prune(now=1e9)
    assert led.count(f"{e}.gpu") == 0
    led.add(t, f"{e}.gpu", trav.predict_task(t, f"{e}.gpu", []), now=0.0)
    led.remove(t)
    assert led.count(f"{e}.gpu") == 0


def test_first_fit_cheaper_than_best_fit(setup):
    tb, trav, _ = setup
    e = tb.edges[0]
    t_bf = make_task("pose_pred", origin=e, deadline=0.5)
    t_ff = make_task("pose_pred", origin=e, deadline=0.5)
    best = build_orchestrators(tb.graph, trav, config=OrcConfig())
    first = build_orchestrators(tb.graph, trav,
                                config=OrcConfig(objective="first_fit"))
    r_bf = best.find_device_orc(e).map_batch([t_bf])[0]
    r_ff = first.find_device_orc(e).map_batch([t_ff])[0]
    assert r_ff.queries <= r_bf.queries


def test_dead_pu_not_assigned(setup):
    tb, trav, _ = setup
    e = tb.edges[0]
    tb.graph.mark_dead(f"{e}.gpu")
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    orc = root.find_device_orc(e)
    t = make_task("dnn", origin=e, deadline=1.0)
    res = orc.map_batch([t])[0]
    assert res is not None and res.pu != f"{e}.gpu"
    tb.graph.mark_alive(f"{e}.gpu")


def test_overhead_scales_with_remote_search(setup):
    tb, _, root = setup
    e = tb.edges[1]
    orc = root.find_device_orc(e)
    local = orc.map_batch([make_task("capture", origin=e, deadline=1.0)])[0]
    remote = orc.map_batch([make_task("render", origin=e, deadline=0.030,
                                      input_bytes=4e3)])[0]
    assert remote.overhead > local.overhead


# ---------------------------------------------------------------------------
# fused wave-batched walk vs the sequential per-task oracle
# ---------------------------------------------------------------------------
# ``REPRO_FUSED_WALK=1`` (default) lowers every mapping wave to array scans
# over the compiled ORC tree; ``=0`` keeps the seed's Python object walk.
# The contract is bit-identical *decisions*: pu, standalone, factor, comm,
# queries and hops match exactly, overhead to 1e-9 (the fused reduce sums
# the same terms in a different association order).

_PARITY_EDGES = {"orin_agx": 2, "xavier_agx": 1, "orin_nano": 2,
                 "xavier_nx": 1}
_PARITY_SERVERS = {"server1": 1, "server2": 1}


def _run_mode(monkeypatch, mode, workload, churn=None, counts=None):
    """Map ``workload(tb)``'s batches through a fresh session in one walk
    mode — ``"sharded"`` (group-parallel driver), ``"fused"``
    (single-shard fused walk), ``"oracle"`` (sequential object walk);
    ``True``/``False`` alias fused/oracle — with optional ``churn(tb, i)``
    graph mutations between batches.  Returns one list of result rows per
    batch, in sorted-uid order (uids differ between twin sessions;
    creation order does not)."""
    from repro.core import SchedulerSession
    if mode is True:
        mode = "fused"
    elif mode is False:
        mode = "oracle"
    monkeypatch.setenv("REPRO_FUSED_WALK",
                       "0" if mode == "oracle" else "1")
    monkeypatch.setenv("REPRO_SHARDED_WALK",
                       "1" if mode == "sharded" else "0")
    tb = build_testbed(edge_counts=dict(counts[0] if counts
                                        else _PARITY_EDGES),
                       server_counts=dict(counts[1] if counts
                                          else _PARITY_SERVERS))
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    sess = SchedulerSession(tb.graph, root)
    batches = []
    for i, batch in enumerate(workload(tb)):
        sess.submit(batch)
        res = sess.map_pending()
        batches.append([
            (res[u].pu, res[u].prediction.standalone,
             res[u].prediction.factor, res[u].prediction.comm,
             res[u].queries, res[u].hops, res[u].overhead)
            for u in sorted(res)])
        if churn is not None:
            churn(tb, i)
    return batches


def _assert_parity(fused_batches, oracle_batches):
    assert len(fused_batches) == len(oracle_batches)
    for fb, ob in zip(fused_batches, oracle_batches):
        assert len(fb) == len(ob)
        for f, o in zip(fb, ob):
            assert f[:6] == o[:6]                     # exact decisions
            assert f[6] == pytest.approx(o[6], rel=1e-9, abs=1e-12)


def test_fused_walk_matches_oracle_mining(monkeypatch):
    """Fig. 13 workload: parallel sensor readings, deadline-driven
    escalation off the weak edges, two readings -> two release waves."""
    from repro.core import mining_workload
    wl = lambda tb: [mining_workload(tb, n_sensors=18, n_readings=2)]
    _assert_parity(_run_mode(monkeypatch, True, wl),
                   _run_mode(monkeypatch, False, wl))


def test_fused_walk_matches_oracle_vr(monkeypatch):
    """Fig. 7 workload: serial CFGs with pinned stages and inter-device
    src_devices provenance flowing producer -> consumer."""
    from repro.core import vr_workload
    wl = lambda tb: [vr_workload(tb, n_frames=3)]
    _assert_parity(_run_mode(monkeypatch, True, wl),
                   _run_mode(monkeypatch, False, wl))


def test_fused_walk_parity_across_churn(monkeypatch):
    """mark_dead + set_bandwidth between mapping batches: the apply_delta'd
    snapshot bumps device epochs, so every fused-side cache (scan plans,
    core states, canonical factor entries) must refresh — parity with the
    oracle, which re-reads the graph per task, proves none went stale."""
    from repro.core import mining_workload

    def wl(tb):
        return [mining_workload(tb, n_sensors=12, n_readings=1),
                mining_workload(tb, n_sensors=12, n_readings=1)]

    dead = {}

    def churn(tb, i):
        if i == 0:
            dead["pu"] = f"{tb.edges[0]}.gpu"
            tb.graph.mark_dead(dead["pu"])
            tb.graph.set_bandwidth(f"link_{tb.edges[1]}", 1e6)

    fused = _run_mode(monkeypatch, True, wl, churn=churn)
    oracle = _run_mode(monkeypatch, False, wl, churn=churn)
    _assert_parity(fused, oracle)
    # and the churn actually bit: nothing lands on the dead PU afterwards
    assert all(row[0] != dead["pu"] for row in fused[1])


def test_set_bandwidth_invalidates_fused_comm(monkeypatch):
    """An identical escalating task mapped before and after a bandwidth
    collapse must see the new comm cost through the fused path (caches are
    keyed per compiled snapshot, not per graph)."""

    def wl(tb):
        e = next(x for x in tb.edges if tb.edge_kind[x] == "orin_nano")
        mk = lambda: [make_task("render", origin=e, deadline=0.030,
                                input_bytes=4e3)]
        return [mk(), mk()]

    def churn(tb, i):
        if i == 0:
            e = next(x for x in tb.edges if tb.edge_kind[x] == "orin_nano")
            tb.graph.set_bandwidth(f"link_{e}", 1e6)

    fused = _run_mode(monkeypatch, True, wl, churn=churn)
    oracle = _run_mode(monkeypatch, False, wl, churn=churn)
    _assert_parity(fused, oracle)
    before, after = fused[0][0], fused[1][0]
    assert after[3] != before[3]            # comm reflects the new network


# ---------------------------------------------------------------------------
# group-sharded walk vs the fused single-shard walk
# ---------------------------------------------------------------------------
# ``REPRO_SHARDED_WALK=1`` (default) partitions the snapshot and ledger per
# root-child ORC group and drives independent groups' walks on host threads,
# reconciling only at the root (NCR) boundary; ``=0`` keeps the fused
# single-shard walk.  The contract is **bit-identical mappings** — stricter
# than the fused-vs-oracle 1e-9 overhead tolerance, because the sharded
# driver runs the very same reduces over the very same arrays, only
# partitioned.

# Fig. 13 mining topology at mult=64 (mining_counts(64) in
# benchmarks/scaling.py): the scale ROADMAP item 2 targets
_X64_EDGES = {"orin_agx": 192, "xavier_agx": 192, "orin_nano": 128,
              "xavier_nx": 128}
_X64_SERVERS = {"server1": 64, "server2": 64, "server3": 64}


def _assert_bit_identical(sharded_batches, fused_batches):
    assert len(sharded_batches) == len(fused_batches)
    for sb, fb in zip(sharded_batches, fused_batches):
        assert sb == fb


def test_sharded_walk_matches_fused_mining_x64(monkeypatch):
    """Whole-session Fig. 13 mining at mult=64: the group-sharded driver
    must reproduce the fused single-shard mappings bit for bit."""
    from repro.core import mining_workload
    wl = lambda tb: [mining_workload(tb, n_sensors=256, n_readings=1)]
    _assert_bit_identical(
        _run_mode(monkeypatch, "sharded", wl,
                  counts=(_X64_EDGES, _X64_SERVERS)),
        _run_mode(monkeypatch, "fused", wl,
                  counts=(_X64_EDGES, _X64_SERVERS)))


def test_sharded_walk_matches_fused_vr_x64(monkeypatch):
    """Fig. 7 VR (serial CFGs, pinned stages, src_devices provenance) at
    the mult=64 fleet, bit-identical across the sharded driver."""
    from repro.core import vr_workload
    wl = lambda tb: [vr_workload(tb, n_frames=2)]
    _assert_bit_identical(
        _run_mode(monkeypatch, "sharded", wl,
                  counts=(_X64_EDGES, _X64_SERVERS)),
        _run_mode(monkeypatch, "fused", wl,
                  counts=(_X64_EDGES, _X64_SERVERS)))


def test_sharded_walk_parity_across_churn(monkeypatch):
    """mark_dead + set_bandwidth between waves: apply_delta clones the
    snapshot, so the sharded views and ledger shard maps must re-derive
    against the new clone — bit-identical to the fused walk throughout."""
    from repro.core import mining_workload

    def wl(tb):
        return [mining_workload(tb, n_sensors=12, n_readings=1),
                mining_workload(tb, n_sensors=12, n_readings=1)]

    dead = {}

    def churn(tb, i):
        if i == 0:
            dead["pu"] = f"{tb.edges[0]}.gpu"
            tb.graph.mark_dead(dead["pu"])
            tb.graph.set_bandwidth(f"link_{tb.edges[1]}", 1e6)

    sharded = _run_mode(monkeypatch, "sharded", wl, churn=churn)
    fused = _run_mode(monkeypatch, "fused", wl, churn=churn)
    _assert_bit_identical(sharded, fused)
    assert all(row[0] != dead["pu"] for row in sharded[1])


def test_sharded_cross_group_escalation(monkeypatch):
    """A deadline only servers can meet forces the walk out of the edge
    group: the escalation must cross the ORC boundary through the root's
    cross-group scan (serial boundary reconciliation) and still match the
    fused walk bit for bit."""

    def wl(tb):
        e = next(x for x in tb.edges if tb.edge_kind[x] == "orin_nano")
        return [[make_task("render", origin=e, deadline=0.030,
                           input_bytes=4e3) for _ in range(3)]]

    sharded = _run_mode(monkeypatch, "sharded", wl)
    fused = _run_mode(monkeypatch, "fused", wl)
    _assert_bit_identical(sharded, fused)
    # the mapping actually crossed groups (edge origin -> server PU)
    assert all(row[0].split(".")[0].startswith("server")
               for row in sharded[0])
    assert all(row[5] > 0 for row in sharded[0])       # hops charged


def test_sharded_session_state(monkeypatch):
    """The sharded session installs a ShardedLedger over the root-child
    groups, and the shared counters (engine opens, recompiles, factor
    cache) aggregate across shards exactly as in the monolithic setup."""
    from repro.core import SchedulerSession, mining_workload
    from repro.core.orchestrator import ShardedLedger
    monkeypatch.setenv("REPRO_FUSED_WALK", "1")
    monkeypatch.setenv("REPRO_SHARDED_WALK", "1")
    tb = build_testbed(edge_counts=dict(_PARITY_EDGES),
                       server_counts=dict(_PARITY_SERVERS))
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    sess = SchedulerSession(tb.graph, root)
    assert isinstance(root.ledger, ShardedLedger)
    assert len(root.ledger.shards) == len(root.children) >= 2
    # every device ORC routes through the same sharded ledger facade
    assert all(o.ledger is root.ledger for o in root.iter_tree())
    c0 = trace.snapshot()["counters"]
    sess.submit(mining_workload(tb, n_sensors=8, n_readings=1))
    res = sess.map_pending()
    assert res and all(r is not None for r in res.values())
    # ledger totals aggregate across shards
    assert len(root.ledger) == sum(len(s) for s in root.ledger.shards)
    assert len(root.ledger) == len(res)
    # shared counters see the whole run, not one shard's slice
    c1 = trace.snapshot()["counters"]
    assert sum(c1.get(k, 0) - c0.get(k, 0)
               for k in ("cache.canon.hit", "cache.canon.miss")) > 0
    # sharding never forces extra snapshot recompiles
    assert tb.graph.recompile_count <= 1
    stats = sess.execute()
    assert sess.engine_opens <= 1
    assert stats is not None


def test_sharded_hwgraph_slicing():
    """ShardedHWGraph unit surface: PU index remap, per-group NCR blocks,
    block-diagonal validation, and device -> shard lookup."""
    import numpy as np
    from repro.core.compiled import ShardedHWGraph
    tb = build_testbed(edge_counts=dict(_PARITY_EDGES),
                       server_counts=dict(_PARITY_SERVERS))
    comp = tb.graph.compiled()
    groups = {"edge_cluster": list(tb.edges),
              "server_cluster": list(tb.servers)}
    sh = comp.sharded(groups)
    assert isinstance(sh, ShardedHWGraph)
    assert sh.n_shards == 2
    assert comp.sharded(groups) is sh          # cached per partition
    names = set()
    for shard in sh.shards:
        # remap: local PU names are exactly the global names at pu_idx
        assert [comp.pu_names[i] for i in shard.pu_idx] == shard.pu_names
        assert all(shard.local_index[n] == j
                   for j, n in enumerate(shard.pu_names))
        # per-group NCR block matches the global matrix's slice
        np.testing.assert_array_equal(
            shard.ncr_res, comp.ncr_res[np.ix_(shard.pu_idx, shard.pu_idx)])
        names.update(shard.pu_names)
        for d in shard.devices:
            assert sh.shard_of(d) == shard.name
    assert names == set(comp.pu_names)         # partition covers the fleet
    # cross-shard NCR entries are empty (-1): the partition is
    # block-diagonal by construction
    a, b = sh.shards
    assert (comp.ncr_res[np.ix_(a.pu_idx, b.pu_idx)] == -1).all()
    # a partition that splits one shared-resource device across groups
    # must be rejected
    e = tb.edges[0]
    bad = {"g1": [e], "g2": [d for d in tb.edges if d != e] + tb.servers}
    pus = [p for p in comp.pu_names if p.startswith(e + ".")]
    if len(pus) > 1 and not (
            comp.ncr_res[np.ix_(
                [comp.pu_index[pus[0]]],
                [comp.pu_index[p] for p in pus[1:]])] == -1).all():
        bad2 = {"g1": [e], "g2": [e]}          # overlapping groups
        with pytest.raises(ValueError):
            comp.sharded(bad2)
    with pytest.raises(ValueError):
        comp.sharded({"g1": [e], "g2": [e, *tb.servers]})


# ---------------------------------------------------------------------------
# Serving fast path (``REPRO_SERVE_FASTPATH``, default on): waves reuse one
# session-resident batch context — persistent scan states, canonical factor
# splices and incremental ledger views — instead of a cold per-wave rebuild,
# and single-task waves take the fused walk too.  The contract is the same
# bit-identical-decision parity as the fused walk itself, now across calls.


def test_resident_context_matches_cold_walk(monkeypatch):
    """Steady-state serving shape — a stream of single-task waves at
    advancing release instants — mapped through one resident context
    matches the cold per-wave object walk exactly."""
    kinds = ["svm", "mlp", "svm", "dnn", "svm", "mlp", "render", "svm"]

    def wl(tb):
        return [[make_task(k, origin=tb.edges[i % len(tb.edges)],
                           deadline=0.5, release_time=0.004 * i)]
                for i, k in enumerate(kinds)]

    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "1")
    fast = _run_mode(monkeypatch, "fused", wl)
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "0")
    cold = _run_mode(monkeypatch, "fused", wl)
    _assert_parity(fast, cold)


def test_resident_context_parity_across_bandwidth_churn(monkeypatch):
    """A bandwidth-only delta between waves rebases the resident context
    (comm caches drop, core scan state survives); a kill between waves
    dirties the device.  Decisions still match the cold walk."""

    def wl(tb):
        return [[make_task("svm", origin=tb.edges[0], deadline=0.5,
                           release_time=0.01 * i),
                 make_task("mlp", origin=tb.edges[1], deadline=0.5,
                           release_time=0.01 * i)]
                for i in range(4)]

    def churn(tb, i):
        tb.graph.set_bandwidth(f"link_{tb.edges[1]}", 3e6 + 1e6 * i)

    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "1")
    fast = _run_mode(monkeypatch, "fused", wl, churn=churn)
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "0")
    cold = _run_mode(monkeypatch, "fused", wl, churn=churn)
    _assert_parity(fast, cold)


def test_resident_context_identity_and_oracle_off(monkeypatch):
    """The root orchestrator keeps one ``_BatchContext`` across
    ``map_batch`` calls; ``REPRO_SERVE_FASTPATH=0`` restores the per-batch
    cold behaviour (no resident state is retained at all)."""
    monkeypatch.setenv("REPRO_FUSED_WALK", "1")
    monkeypatch.setenv("REPRO_SHARDED_WALK", "0")
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "1")
    tb = build_testbed(edge_counts={"orin_agx": 1, "orin_nano": 1},
                       server_counts={"server1": 1})
    root = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    root.map_batch([make_task("svm", origin=tb.edges[0], deadline=0.5)],
                   now=0.0, route=True)
    ctx = root._resident_ctx
    assert ctx is not None
    root.map_batch([make_task("mlp", origin=tb.edges[1], deadline=0.5)],
                   now=0.01, route=True)
    assert root._resident_ctx is ctx       # reused, not rebuilt
    # bandwidth-only churn rebases the same context onto the new snapshot
    tb.graph.set_bandwidth(f"link_{tb.edges[0]}", 5e6)
    root.map_batch([make_task("svm", origin=tb.edges[0], deadline=0.5)],
                   now=0.02, route=True)
    assert root._resident_ctx is ctx
    assert ctx.comp is tb.graph.compiled()
    # the oracle switch disables residency entirely
    monkeypatch.setenv("REPRO_SERVE_FASTPATH", "0")
    root2 = build_orchestrators(tb.graph, heye_traverser(tb.graph))
    root2.map_batch([make_task("svm", origin=tb.edges[0], deadline=0.5)],
                    now=0.0, route=True)
    assert root2._resident_ctx is None
