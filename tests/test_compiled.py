"""CompiledHWGraph: exact parity with the object-graph reference path.

The compiled arrays (core/compiled.py) must reproduce the authoring-layer
algorithms bit-for-bit (tolerance 1e-9): nearest common resources, transfer
times over the routable nodes, pairwise and pooled slowdown factors, and
the Orchestrator's batched candidate checks — plus snapshot invalidation
on every topology mutation hook.
"""
import numpy as np
import pytest

from repro.core import (DecoupledSlowdown, Traverser, build_testbed,
                        heye_params, truth_params)
from repro.core.hwgraph import ProcessingUnit
from repro.core.topology import make_task

TOL = 1e-9


@pytest.fixture(autouse=True)
def _strict_f64_aggregation():
    """1e-9 parity is a float64 contract: pin the numpy aggregation path so
    these tests hold even on a TPU host (where the fp32 Pallas kernel would
    otherwise be auto-selected; its own tolerance is tested separately)."""
    from repro.core import slowdown as sdmod
    prev = sdmod._AGGREGATE
    sdmod._AGGREGATE = sdmod._aggregate_np
    yield
    sdmod._AGGREGATE = prev


@pytest.fixture(scope="module")
def tb():
    # the paper's Orin/Xavier testbed: every edge kind + all three servers
    return build_testbed(edge_counts={"orin_agx": 1, "xavier_agx": 1,
                                      "orin_nano": 1, "xavier_nx": 2},
                         server_counts={"server1": 1, "server2": 1,
                                        "server3": 1})


def _pus(g):
    return [n.name for n in g.nodes.values() if isinstance(n, ProcessingUnit)]


def _pool(tb, n_servers=True):
    kinds = ("dnn", "mm", "knn", "svm", "render", "encode", "reproject")
    pool = []
    for i, e in enumerate(tb.edges):
        for short in ("cpu0", "cpu1", "gpu", "dla", "pva", "vic"):
            pool.append((make_task(kinds[(i + len(pool)) % len(kinds)]),
                         f"{e}.{short}"))
    if n_servers:
        for s in tb.servers:
            pool.append((make_task("knn"), f"{s}.gpu"))
            pool.append((make_task("mlp"), f"{s}.cpu"))
    return pool


# ---------------------------------------------------------------------------
# nearest common resource
# ---------------------------------------------------------------------------
def test_ncr_matrix_matches_object_paths(tb):
    g = tb.graph
    comp = g.compiled()
    pus = _pus(g)
    for a in pus:
        pa = g.nodes[a].get_compute_path()
        for b in pus:
            pb = set(g.nodes[b].get_compute_path())
            expected = next((r for r in pa if r in pb), None)
            assert comp.nearest_common_resource(a, b) == expected, (a, b)


def test_ncr_known_contention_points(tb):
    comp = tb.graph.compiled()
    e = tb.edges[0]
    # Fig. 4: DLA and PVA meet at the vision SRAM; same-device CPU clusters
    # meet at L3; CPU and GPU meet at the LLC; cross-device pairs share nothing
    assert comp.nearest_common_resource(f"{e}.dla", f"{e}.pva") == f"{e}.sram"
    assert comp.nearest_common_resource(f"{e}.cpu0", f"{e}.cpu1") == f"{e}.l3"
    assert comp.nearest_common_resource(f"{e}.cpu0", f"{e}.gpu") == f"{e}.llc"
    e2 = tb.edges[1]
    assert comp.nearest_common_resource(f"{e}.gpu", f"{e2}.gpu") is None


# ---------------------------------------------------------------------------
# transfer matrices
# ---------------------------------------------------------------------------
def test_transfer_time_parity(tb):
    g = tb.graph
    comp = g.compiled()
    names = tb.edges + tb.servers
    for nbytes in (0.0, 1e3, 5e6):
        for s in names:
            for d in names:
                assert comp.transfer_time(s, d, nbytes) == pytest.approx(
                    g.transfer_time(s, d, nbytes), abs=TOL, rel=TOL)


def test_transfer_unreachable_raises_like_object_path(tb):
    g = tb.graph
    comp = g.compiled()
    # cluster GROUPs have no interconnects: both layers must raise
    with pytest.raises(KeyError):
        g.transfer_time(tb.edges[0], "edge_cluster", 1.0)
    with pytest.raises(KeyError):
        comp.transfer_time(tb.edges[0], "edge_cluster", 1.0)


def test_route_edges_identity(tb):
    g = tb.graph
    comp = g.compiled()
    e, s = tb.edges[0], tb.servers[0]
    # the Traverser's bandwidth sharing keys transfers by id(edge): the
    # compiled routes must hand out the *same* EdgeAttr objects
    assert [id(x) for x in comp.route_edges(e, s)] == \
        [id(x) for x in g.route_edges(e, s)]


# ---------------------------------------------------------------------------
# slowdown factors
# ---------------------------------------------------------------------------
def test_factor_batch_parity(tb):
    sd = DecoupledSlowdown(tb.graph, heye_params())
    pool = _pool(tb)
    got = sd.factor_batch(pool)
    want = np.array([sd.factor(t, p, pool) for t, p in pool])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_slowdown_matrix_pairwise_parity(tb):
    sd = DecoupledSlowdown(tb.graph, truth_params(noise=0.0))
    pool = _pool(tb, n_servers=False)
    mat = sd.slowdown_matrix(pool)
    assert mat.shape == (len(pool), len(pool))
    for i, (ti, pi) in enumerate(pool):
        for j, (tj, pj) in enumerate(pool):
            assert mat[i, j] == pytest.approx(
                sd.factor(ti, pi, [(tj, pj)]), abs=TOL, rel=TOL)
    np.testing.assert_allclose(np.diag(mat), 1.0)


def test_factors_with_candidates_parity(tb):
    sd = DecoupledSlowdown(tb.graph, heye_params())
    task = make_task("render", origin=tb.edges[0])
    active = _pool(tb)[:14]
    cands = [f"{tb.edges[0]}.{s}" for s in ("cpu0", "cpu1", "gpu", "vic")] \
        + [f"{tb.servers[0]}.gpu"]
    new_f, act_f = sd.factors_with_candidates(task, cands, active)
    for c, p in enumerate(cands):
        assert new_f[c] == pytest.approx(sd.factor(task, p, list(active)),
                                         abs=TOL, rel=TOL)
        pool_c = list(active) + [(task, p)]
        for a, (t, q) in enumerate(active):
            assert act_f[c, a] == pytest.approx(sd.factor(t, q, pool_c),
                                                abs=TOL, rel=TOL)


def test_predict_active_with_parity(tb):
    trav = Traverser(tb.graph)
    active = _pool(tb)[:10]
    new = make_task("dnn", origin=tb.edges[0])
    pu = f"{tb.edges[0]}.gpu"
    got = trav.predict_active_with(new, pu, active)
    pool = list(active) + [(new, pu)]
    for t, p in active:
        others = [(t2, p2) for t2, p2 in pool if t2.uid != t.uid]
        assert got[t.uid] == pytest.approx(
            trav.slowdown.factor(t, p, others), abs=TOL, rel=TOL)


def test_noisy_truth_model_still_batches_deterministically(tb):
    """The ground-truth params carry noise>0 but no rng: the batch path must
    stay on the vectorized branch and match the scalar path exactly."""
    sd = DecoupledSlowdown(tb.graph, truth_params())
    assert sd.rng is None
    pool = _pool(tb, n_servers=False)[:12]
    got = sd.factor_batch(pool)
    want = np.array([sd.factor(t, p, pool) for t, p in pool])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# invalidation on topology mutation
# ---------------------------------------------------------------------------
def test_mark_dead_invalidates_and_reconverges():
    tb = build_testbed(edge_counts={"orin_agx": 2},
                       server_counts={"server1": 1})
    g = tb.graph
    e = tb.edges[0]
    before = g.compiled()
    assert g.compiled() is before           # snapshot is reused while valid
    g.mark_dead(e)
    after = g.compiled()
    assert after is not before
    assert not after.pu_alive[after.pu_index[f"{e}.gpu"]]
    g.mark_alive(e)
    revived = g.compiled()
    assert revived is not after
    assert revived.pu_alive[revived.pu_index[f"{e}.gpu"]]
    # parity holds against the freshly mutated object graph
    sd = DecoupledSlowdown(g, heye_params())
    a, b = make_task("dnn"), make_task("dnn")
    pool = [(a, f"{e}.gpu"), (b, f"{e}.dla")]
    np.testing.assert_allclose(
        sd.factor_batch(pool),
        [sd.factor(a, f"{e}.gpu", pool), sd.factor(b, f"{e}.dla", pool)],
        atol=TOL, rtol=TOL)


@pytest.mark.parametrize("n,r", [(1, 3), (5, 8), (9, 6), (130, 6),
                                 (300, 6), (2160, 6)])
def test_slowdown_kernel_matches_numpy_oracle(n, r):
    """Pallas factor-aggregation kernel (interpret mode) vs ref oracle, at
    pool sizes that fill their bucket and sizes that pad up to it."""
    from repro.kernels.ref import slowdown_factors_ref
    from repro.kernels.slowdown_kernel import slowdown_factors_pallas
    rng = np.random.default_rng(n * 10 + r)
    x = rng.uniform(0.0, 3.0, (n, r)) * (rng.random((n, r)) > 0.4)
    beta = rng.uniform(0.0, 0.5, r)
    beta[0] = 0.0                           # inactive-resource branch
    mem = rng.uniform(0.0, 1.0, n)
    mt = rng.uniform(0.0, 1.0, n) * (rng.random(n) > 0.5)
    ref = slowdown_factors_ref(x, beta, mem, mt, 0.12)
    pal = slowdown_factors_pallas(x, beta, mem, mt, 0.12, interpret=True)
    assert pal.shape == (n,)
    np.testing.assert_allclose(pal, ref, rtol=1e-6, atol=0)   # fp32


def test_slowdown_kernel_slices_pools_wider_than_its_widest_bucket(
        monkeypatch):
    """A pool wider than ``MAX_BUCKET`` goes through in slices of that
    width: the same factors as one call, and no bucket above the cap."""
    from repro.kernels import slowdown_kernel as sk
    rng = np.random.default_rng(3)
    n, r = 150, 6
    x = rng.uniform(0.0, 3.0, (n, r)) * (rng.random((n, r)) > 0.4)
    beta = rng.uniform(0.0, 0.5, r)
    mem = rng.uniform(0.0, 1.0, n)
    mt = rng.uniform(0.0, 1.0, n) * (rng.random(n) > 0.5)
    whole = sk.slowdown_factors_pallas(x, beta, mem, mt, 0.12,
                                       interpret=True)
    widths = []
    call = sk.factors_call

    def recorded(xt, *a, **kw):
        widths.append(xt.shape[1])
        return call(xt, *a, **kw)

    monkeypatch.setattr(sk, "MAX_BUCKET", 64)
    monkeypatch.setattr(sk, "factors_call", recorded)
    sliced = sk.slowdown_factors_pallas(x, beta, mem, mt, 0.12,
                                        interpret=True)
    assert widths == [64, 64, 32]
    np.testing.assert_array_equal(sliced, whole)


@pytest.mark.parametrize("jax_loaded", [True, False])
def test_aggregate_selects_numpy_on_cpu(monkeypatch, jax_loaded):
    """Off-TPU the selector picks the float64 numpy path, whatever has
    been imported already."""
    monkeypatch.delenv("REPRO_SLOWDOWN_KERNEL", raising=False)
    if jax_loaded:
        import jax
        assert jax.default_backend() == "cpu"
        from repro.core import slowdown as sdmod
        assert sdmod._select_aggregate() is sdmod._aggregate_np
        return
    import os
    import subprocess
    import sys
    code = ("import sys\n"
            "from repro.core import slowdown as sd\n"
            "assert 'jax' not in sys.modules\n"
            "assert sd._select_aggregate() is sd._aggregate_np\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_walk_reduce_selects_numpy_on_cpu(monkeypatch):
    from repro.kernels import walk_kernel
    monkeypatch.delenv("REPRO_WALK_KERNEL", raising=False)
    monkeypatch.setattr(walk_kernel, "_AUTO_JAX", None)
    assert walk_kernel._use_jax() is False


def test_slowdown_kernel_shapes_bounded_over_serve_run():
    """The mult=8 serve run's pools come in many sizes; the kernel pads
    them to power-of-two buckets and compiles at most one shape each."""
    from benchmarks.serve import _serve_once
    from repro.core import slowdown as sdmod
    from repro.kernels import slowdown_kernel as sk
    sizes, shapes = set(), set()

    def recorded(x, *args):
        sizes.add(len(x))
        shapes.add((sk.bucket(len(x)), x.shape[1]))
        return sk.slowdown_factors_pallas(x, *args, interpret=True)

    sdmod._AGGREGATE = recorded
    before = sk.factors_call._cache_size()
    stats, _ = _serve_once(8)
    compiled = sk.factors_call._cache_size() - before
    assert stats.engine_opens == 1
    # powers of two from 8 up to the largest pool
    n_buckets = int(np.log2(sk.bucket(max(sizes)))) - 2
    assert len(shapes) <= n_buckets < len(sizes)
    assert compiled <= len(shapes)


def test_set_bandwidth_invalidates_transfer_matrices():
    tb = build_testbed(edge_counts={"orin_agx": 1},
                       server_counts={"server1": 1})
    g = tb.graph
    e, s = tb.edges[0], tb.servers[0]
    before = g.compiled()
    t0 = before.transfer_time(e, s, 10e6)
    g.set_bandwidth(f"link_{e}", 1e6)
    after = g.compiled()
    assert after is not before
    t1 = after.transfer_time(e, s, 10e6)
    assert t1 > t0
    assert t1 == pytest.approx(g.transfer_time(e, s, 10e6), abs=TOL, rel=TOL)


# ---------------------------------------------------------------------------
# layered COW route tables: topology layer vs bandwidth overlay
# ---------------------------------------------------------------------------
def _route_parity(patched, fresh, names, nb=5e6, tol=TOL):
    """Every routable pair must price identically on the delta-patched
    snapshot and a fresh recompile (KeyError behaviour included)."""
    for s in names:
        for d in names:
            try:
                want = fresh.transfer_time(s, d, nb)
            except KeyError:
                with pytest.raises(KeyError):
                    patched.transfer_time(s, d, nb)
                continue
            got = patched.transfer_time(s, d, nb)
            assert got == pytest.approx(want, abs=tol, rel=tol), (s, d)


@pytest.mark.parametrize("seed", range(4))
def test_layered_cow_random_interleaving_parity(seed):
    """Property-style oracle: a random interleaving of bandwidth batches,
    deaths and revivals over lazily part-built route rows — with every
    intermediate snapshot kept alive as a sharer — must stay bit-identical
    to a fresh recompile of the final graph."""
    import random

    from repro.core import Churn
    from repro.core.compiled import CompiledHWGraph
    rng = random.Random(seed)
    tb = build_testbed(edge_counts={"orin_agx": 1, "xavier_agx": 1,
                                    "orin_nano": 1},
                       server_counts={"server1": 1, "server2": 1})
    g = tb.graph
    names = tb.edges + tb.servers
    links = [f"link_{n}" for n in names]
    nominal = {}
    for adj in g._adj.values():
        for _, e in adj:
            if e.name in links:
                nominal.setdefault(e.name, e.bandwidth)
    sharers = [g.compiled()]                 # >= 2 sharers at every step
    for _ in range(12):
        comp = g.compiled()
        # lazily build a few rows on the current snapshot
        for s in rng.sample(names, 2):
            try:
                comp.transfer_time(s, rng.choice(names), 5e6)
            except KeyError:
                pass
        op = rng.random()
        if op < 0.55:
            entries = tuple((ln, nominal[ln] * rng.uniform(0.05, 1.5))
                            for ln in (rng.choice(links)
                                       for _ in range(rng.randint(1, 3))))
            g.apply_churn(Churn(bandwidth=entries))
        elif op < 0.8:
            alive = [n for n in names if g.nodes[n].alive]
            if len(alive) > 2:
                g.apply_churn(Churn(dead=(rng.choice(alive),)))
        else:
            dead = [n for n in names if not g.nodes[n].alive]
            if dead:
                g.apply_churn(Churn(alive=(rng.choice(dead),)))
        sharers.append(g.compiled())
    _route_parity(g.compiled(), CompiledHWGraph(g), names)


def test_bandwidth_overlay_shares_topology_layer():
    from repro.core import Churn
    tb = build_testbed(edge_counts={"orin_agx": 2},
                       server_counts={"server1": 1})
    g = tb.graph
    e0, e1, s = tb.edges[0], tb.edges[1], tb.servers[0]
    old = g.compiled()
    t_before = old.transfer_time(e0, s, 10e6)     # lazy row build
    h0, o0 = g.route_holder_copies, g.route_overlay_copies
    g.apply_churn(Churn(bandwidth=((f"link_{e0}", 2e6),)))
    new = g.compiled()
    assert new is not old and new._rt is not old._rt
    assert new._rt.topo is old._rt.topo           # topology layer shared
    assert g.route_holder_copies == h0            # no O(D^2) copy
    assert g.route_overlay_copies == o0 + 1
    # the stale sharer keeps its pre-churn pricing on built rows; the
    # patched snapshot prices the degraded uplink
    assert old.transfer_time(e0, s, 10e6) == pytest.approx(
        t_before, abs=TOL, rel=TOL)
    assert new.transfer_time(e0, s, 10e6) > t_before
    # a row built lazily on the stale sharer writes through to the shared
    # topology layer: the patched snapshot resolves it without rebuilding
    t_e1 = old.transfer_time(e1, s, 10e6)
    assert new.transfer_time(e1, s, 10e6) == pytest.approx(
        t_e1, abs=TOL, rel=TOL)


def test_bandwidth_delta_on_unreferenced_links_shares_whole_table():
    from repro.core import Churn
    tb = build_testbed(edge_counts={"orin_agx": 2},
                       server_counts={"server1": 1})
    g = tb.graph
    comp = g.compiled()                           # no rows built yet
    o0, h0 = g.route_overlay_copies, g.route_holder_copies
    g.apply_churn(Churn(bandwidth=((f"link_{tb.edges[1]}", 5e6),)))
    new = g.compiled()
    assert new is not comp
    assert new._rt is comp._rt                    # zero-copy share
    assert (g.route_overlay_copies, g.route_holder_copies) == (o0, h0)
    # rows built after the share price the post-churn bandwidths
    from repro.core.compiled import CompiledHWGraph
    _route_parity(new, CompiledHWGraph(g), tb.edges + tb.servers)


def test_sharded_slices_share_topology_after_bandwidth_delta():
    from repro.core import Churn
    from repro.core.compiled import CompiledHWGraph, ShardedHWGraph
    tb = build_testbed(edge_counts={"orin_agx": 2},
                       server_counts={"server1": 1})
    g = tb.graph
    e0, s = tb.edges[0], tb.servers[0]
    comp = g.compiled()
    comp.transfer_time(e0, s, 5e6)
    sh = comp.sharded({"edge": list(tb.edges), "server": list(tb.servers)})
    assert isinstance(sh, ShardedHWGraph)
    assert sh.routes is comp._rt
    g.apply_churn(Churn(bandwidth=((f"link_{e0}", 3e6),)))
    comp2 = g.compiled()
    # the sharded view and the patched snapshot still share one topology
    # layer; only the bandwidth overlay diverged
    assert comp2._rt.topo is sh.routes.topo
    assert g.route_holder_copies == 0
    _route_parity(comp2, CompiledHWGraph(g), tb.edges + tb.servers)


def test_overlay_compaction_bounds_dirty_on_long_runs():
    """A long bandwidth-volatile run keeps the overlay bounded: once the
    dirty-link set reaches the compaction threshold and no other snapshot
    shares the topology layer, the overlay folds into it (counter bumps),
    and pricing stays bit-identical to a fresh recompile."""
    import gc

    from repro.core import Churn
    from repro.core.compiled import _OVERLAY_COMPACT_DIRTY, CompiledHWGraph
    tb = build_testbed(edge_counts={"orin_agx": 40, "xavier_agx": 30},
                       server_counts={"server1": 1})
    g = tb.graph
    s = tb.servers[0]
    links = [f"link_{e}" for e in tb.edges]
    assert len(links) > _OVERLAY_COMPACT_DIRTY
    # materialize one route per edge so every uplink's link is crossed by
    # a built row (deltas must overlay-copy, not zero-copy share)
    for e in tb.edges:
        g.compiled().transfer_time(e, s, 5e6)
    c0 = g.route_overlay_compactions
    peak = 0
    for k, ln in enumerate(links):
        gc.collect()      # drop dead sharers so sole ownership is exact
        g.apply_churn(Churn(bandwidth=((ln, 4e6 + 1e3 * k),)))
        peak = max(peak, len(g.compiled()._rt.dirty))
    assert g.route_overlay_compactions > c0
    assert peak <= _OVERLAY_COMPACT_DIRTY          # bounded, not monotone
    assert len(g.compiled()._rt.dirty) < len(links)
    _route_parity(g.compiled(), CompiledHWGraph(g),
                  tb.edges[:6] + [tb.edges[-1], s])
