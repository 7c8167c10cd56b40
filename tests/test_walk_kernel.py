"""Walk-kernel reduce parity (kernels/walk_kernel.py): the scalar
small-scan path, the vectorized numpy path, and the jitted jax path must
agree on winner/queries/hops exactly and on overhead to float tolerance,
across feasibility patterns including all-infeasible roots, key ties and
inf keys (unroutable comm)."""
import numpy as np
import pytest

from repro.kernels.walk_kernel import scan_reduce, scan_reduce_ref

LQC = 5e-6


def _spec_oracle(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth, lqc):
    """The documented closed forms, computed the obvious way."""
    cs = np.concatenate(([0], np.cumsum(ok.astype(np.int64))))
    feas = cs[pu_hi] > cs[pu_lo]
    if not feas[0]:
        return -1, 0, 0, 0.0
    ok_idx = np.flatnonzero(ok)
    w = int(ok_idx[np.argmin(key[ok_idx])])
    return (w, int(leafcnt[feas].sum()), int(nchild[feas].sum()),
            float((hopsum[feas] + lqc * leafcnt[feas] * (depth[feas] + 1.0))
                  .sum()))


def _random_plan(rng, n_pus, n_nodes, p_ok):
    ok = rng.random(n_pus) < p_ok
    key = rng.random(n_pus) * 1e-2
    key[rng.random(n_pus) < 0.1] = np.inf          # unroutable comm
    key[rng.random(n_pus) < 0.2] = 1e-3            # force exact ties
    lo = rng.integers(0, n_pus, n_nodes)
    hi = lo + rng.integers(0, n_pus // 2 + 1, n_nodes)
    np.clip(hi, None, n_pus, out=hi)
    lo[0], hi[0] = 0, n_pus                        # node 0 is the scan root
    return (ok, key, lo.astype(np.int64), hi.astype(np.int64),
            rng.integers(0, 5, n_nodes), rng.integers(0, 4, n_nodes),
            rng.random(n_nodes) * 1e-4, rng.integers(0, 4, n_nodes)
            .astype(np.float64))


@pytest.mark.parametrize("n_pus,n_nodes", [
    (3, 2),        # device scan: scalar path
    (40, 11),      # cluster scan: scalar path
    (200, 31),     # fleet scan: vectorized path
])
@pytest.mark.parametrize("p_ok", [0.0, 0.05, 0.5, 1.0])
def test_scalar_and_array_paths_match_spec(n_pus, n_nodes, p_ok):
    rng = np.random.default_rng(n_pus * 7 + int(p_ok * 10))
    for _ in range(20):
        plan = _random_plan(rng, n_pus, n_nodes, p_ok)
        got = scan_reduce_ref(*plan, LQC)
        want = _spec_oracle(*plan, LQC)
        assert got[:3] == want[:3]
        assert got[3] == pytest.approx(want[3], rel=1e-9, abs=1e-15)


def test_jax_path_matches_ref(monkeypatch):
    jax = pytest.importorskip("jax")
    del jax
    monkeypatch.setenv("REPRO_WALK_KERNEL", "jax")
    rng = np.random.default_rng(0)
    for n_pus, n_nodes in [(6, 3), (200, 31)]:
        for p_ok in (0.0, 0.4, 1.0):
            plan = _random_plan(rng, n_pus, n_nodes, p_ok)
            got = scan_reduce(*plan, LQC)
            monkeypatch.setenv("REPRO_WALK_KERNEL", "ref")
            want = scan_reduce(*plan, LQC)
            monkeypatch.setenv("REPRO_WALK_KERNEL", "jax")
            assert got[:3] == want[:3]
            # jitted reduce may run f32 without jax_enable_x64
            assert got[3] == pytest.approx(want[3], rel=1e-5, abs=1e-9)


# -- the device path: packed operands, resident plan constants -------------
@pytest.fixture
def jax_path(monkeypatch):
    """The jitted entries on the CPU, with an empty plan cache."""
    pytest.importorskip("jax")
    from repro.kernels import walk_kernel
    monkeypatch.delenv("REPRO_WALK_KERNEL", raising=False)
    monkeypatch.setattr(walk_kernel, "_AUTO_JAX", True)
    monkeypatch.setattr(walk_kernel, "_PLANS",
                        walk_kernel._ResidentPlans(64))
    return walk_kernel


def _fp32_plan(rng, n_pus, n_nodes, p_ok, keys=None):
    """A random plan whose keys are fp32 values, so that the fp32 program
    and the float64 reference order them alike."""
    plan = list(_random_plan(rng, n_pus, n_nodes, p_ok))
    plan[1] = plan[1].astype(np.float32).astype(np.float64)
    if keys == "inf":
        plan[1][:] = np.inf                        # every feasible key inf
    return tuple(plan)


def _fp32_programs():
    """Today's reduce over its nine operands, single and vmapped: the fp32
    program the packed entries must reproduce bit for bit."""
    import jax
    from repro.kernels import walk_kernel
    scan = walk_kernel._scan_math()
    return jax.jit(scan), jax.jit(jax.vmap(scan, in_axes=(0,) * 8 + (None,)))


def _counts(s0, s1, names):
    c0, c1 = s0["counters"], s1["counters"]
    return {k: c1.get(k, 0) - c0.get(k, 0) for k in names}


@pytest.mark.parametrize("n_pus,n_nodes", [(3, 2), (40, 11), (200, 31)])
@pytest.mark.parametrize("p_ok,keys", [(0.0, None), (0.05, None),
                                       (0.5, None), (1.0, None),
                                       (0.5, "inf")])
def test_jax_entries_match_ref_and_fp32_program(jax_path, n_pus, n_nodes,
                                                 p_ok, keys):
    one, batch = _fp32_programs()
    rng = np.random.default_rng(n_pus * 31 + int(p_ok * 100))
    plans = [_fp32_plan(rng, n_pus, n_nodes, p_ok, keys) for _ in range(6)]
    for plan in plans:
        got = jax_path.scan_reduce(*plan, LQC)
        want = scan_reduce_ref(*plan, LQC)
        assert got[:3] == want[:3]
        assert got[3] == pytest.approx(want[3], rel=1e-5, abs=1e-9)
        w, q, h, ov = one(*plan, LQC)
        assert got == (int(w), int(q), int(h), float(ov))
    for rows in (2, 3):
        stack = tuple(np.stack([p[i] for p in plans[:rows]])
                      for i in range(8))
        got = jax_path.scan_reduce_batch(*stack, LQC)
        want = [scan_reduce_ref(*(c[r] for c in stack), LQC)
                for r in range(rows)]
        for j in range(3):
            assert got[j].dtype == np.int64
            assert got[j].tolist() == [x[j] for x in want]
        assert got[3].dtype == np.float64
        ref = batch(*stack, LQC)
        for j in range(4):
            assert np.array_equal(got[j], np.asarray(ref[j]).astype(
                got[j].dtype))


def test_rebuilt_plan_gets_new_answers(jax_path):
    """Plans with the same shapes and new values, in new arrays and in the
    same arrays written over, never read stale constants."""
    rng = np.random.default_rng(5)
    for n_pus, n_nodes in [(6, 1), (40, 11)]:
        first = _fp32_plan(rng, n_pus, n_nodes, 0.6)
        jax_path.scan_reduce(*first, LQC)
        for _ in range(5):
            plan = _fp32_plan(rng, n_pus, n_nodes, 0.6)
            del first
            first = plan
            assert jax_path.scan_reduce(*plan, LQC)[:3] \
                == scan_reduce_ref(*plan, LQC)[:3]
        cols = [np.array(c) for c in first[2:]]
        for _ in range(5):
            new = _fp32_plan(rng, n_pus, n_nodes, 0.6)
            for c, v in zip(cols, new[2:]):
                c[...] = v                         # same ids, new values
            args = (new[0], new[1], *cols)
            assert jax_path.scan_reduce(*args, LQC)[:3] \
                == scan_reduce_ref(*args, LQC)[:3]
            stack = tuple(np.stack([a, a]) for a in args)
            assert jax_path.scan_reduce_batch(*stack, LQC)[0].tolist() \
                == [scan_reduce_ref(*args, LQC)[0]] * 2
    # lqc is part of the key
    plan = _fp32_plan(rng, 40, 11, 1.0)
    for lqc in (LQC, 2 * LQC):
        assert jax_path.scan_reduce(*plan, lqc)[3] == pytest.approx(
            scan_reduce_ref(*plan, lqc)[3], rel=1e-5)


def test_plan_cache_and_transfer_counts(jax_path):
    from repro.core import trace
    names = ("cache.plan_dev.hit", "cache.plan_dev.miss", "device.h2d",
             "device.fetch")
    rng = np.random.default_rng(9)
    plan = _fp32_plan(rng, 40, 11, 0.5)
    stack = tuple(np.stack([c, c, c]) for c in plan)
    for call, args in ((jax_path.scan_reduce, plan),
                       (jax_path.scan_reduce_batch, stack)):
        s0 = trace.snapshot()
        call(*args, LQC)                           # miss: one more upload
        s1 = trace.snapshot()
        call(*args, LQC)                           # hit
        s2 = trace.snapshot()
        assert _counts(s0, s1, names) == {
            "cache.plan_dev.hit": 0, "cache.plan_dev.miss": 1,
            "device.h2d": 2, "device.fetch": 1}
        assert _counts(s1, s2, names) == {
            "cache.plan_dev.hit": 1, "cache.plan_dev.miss": 0,
            "device.h2d": 1, "device.fetch": 1}
    # equal columns share one copy whatever arrays hold them
    s0 = trace.snapshot()
    jax_path.scan_reduce(plan[0], plan[1], *(c.copy() for c in plan[2:]),
                         LQC)
    assert _counts(s0, trace.snapshot(), names)["cache.plan_dev.hit"] == 1


def test_new_plan_contents_compile_nothing(jax_path):
    import jax
    seen = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    rng = np.random.default_rng(11)
    # shapes of this test alone, so the warm calls must compile
    shapes = [(37, 13), (9, 4)]
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for n_pus, n_nodes in shapes:
            plan = _fp32_plan(rng, n_pus, n_nodes, 0.5)
            jax_path.scan_reduce(*plan, LQC)
            jax_path.scan_reduce_batch(*(np.stack([c, c]) for c in plan),
                                       LQC)
        assert len(seen) >= 2
        del seen[:]
        for _ in range(4):
            for n_pus, n_nodes in shapes:
                plan = _fp32_plan(rng, n_pus, n_nodes, 0.5)
                other = _fp32_plan(rng, n_pus, n_nodes, 0.5)
                jax_path.scan_reduce(*plan, 3 * LQC)
                jax_path.scan_reduce_batch(
                    *(np.stack([a, b]) for a, b in zip(plan, other)), LQC)
        assert seen == []
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def test_host_path_makes_no_device_call(monkeypatch):
    from repro.core import trace
    from repro.kernels import walk_kernel

    class Refuse:
        def __call__(self, *a, **kw):
            raise AssertionError("the host path reached the device")

        get = __call__

    monkeypatch.delenv("REPRO_WALK_KERNEL", raising=False)
    monkeypatch.setattr(walk_kernel, "_AUTO_JAX", False)
    monkeypatch.setattr(walk_kernel, "_JAX_REDUCE", Refuse())
    monkeypatch.setattr(walk_kernel, "_JAX_REDUCE_BATCH", Refuse())
    monkeypatch.setattr(walk_kernel, "_PLANS", Refuse())
    rng = np.random.default_rng(13)
    plan = _fp32_plan(rng, 40, 11, 0.5)
    s0 = trace.snapshot()
    assert walk_kernel.scan_reduce(*plan, LQC) == scan_reduce_ref(*plan, LQC)
    stack = tuple(np.stack([c, c]) for c in plan)
    w, q, h, ov = walk_kernel.scan_reduce_batch(*stack, LQC)
    assert w.tolist() == [scan_reduce_ref(*plan, LQC)[0]] * 2
    assert _counts(s0, trace.snapshot(), (
        "cache.plan_dev.hit", "cache.plan_dev.miss", "device.h2d",
        "device.fetch")) == dict.fromkeys((
            "cache.plan_dev.hit", "cache.plan_dev.miss", "device.h2d",
            "device.fetch"), 0)


def test_resident_plans_evict_least_recent_first():
    pytest.importorskip("jax")
    from repro.kernels.walk_kernel import _ResidentPlans
    cache = _ResidentPlans(2)
    cols = [tuple(np.array([k, k + 1]) for _ in range(6)) for k in range(3)]
    a = cache.get(cols[0], LQC)
    cache.get(cols[1], LQC)
    assert cache.get(cols[0], LQC) is a            # cols[0] now most recent
    cache.get(cols[2], LQC)                        # evicts cols[1]
    assert len(cache._dev) == 2
    assert cache.get(cols[0], LQC) is a
    packed = np.asarray(cache.get(cols[2], LQC))
    assert packed[:8].tolist() == [2, 3] * 4
    assert packed[8:].view(np.float32).tolist() == [2, 3, 2, 3,
                                                    np.float32(LQC)]


def test_resident_plans_under_threads():
    """Group threads of the sharded walk share the cache: every lookup
    returns its own content's copy and the bound holds."""
    pytest.importorskip("jax")
    import sys
    import threading
    from repro.kernels.walk_kernel import _ResidentPlans
    cache = _ResidentPlans(8)
    errors = []

    def work(t):
        try:
            for i in range(60):
                k = (t * 7 + i) % 20
                cols = tuple(np.array([k, 1]) for _ in range(6))
                got = np.asarray(cache.get(cols, LQC))
                if got[:2].tolist() != [k, 1]:
                    errors.append((k, got.tolist()))
        except Exception as e:                     # reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    assert len(cache._dev) <= 8


def test_plan_dev_hit_pct_reads_the_capture(jax_path, tmp_path):
    """The benchmark's reader of the cache: hits over lookups inside the
    profiler capture, None where the capture made no lookup."""
    import jax
    from types import SimpleNamespace

    from bench.run import RunRecord, load_reader
    read = load_reader("plan_dev_hit_pct")
    rec = RunRecord(cell=None, window=SimpleNamespace(decisions=1), phase={},
                    calls={}, work={}, device_kind="cpu", setup_s=0.0)
    rng = np.random.default_rng(17)
    plans = [_fp32_plan(rng, 40, 11, 0.5) for _ in range(2)]
    jax_path.scan_reduce(*plans[0], LQC)           # before the capture
    with jax.profiler.trace(str(tmp_path / "a")):
        for plan in (plans[0], plans[1], plans[1], plans[0]):
            jax_path.scan_reduce(*plan, LQC)
    assert read(rec) == pytest.approx(75.0)
    from repro.core import trace
    with jax.profiler.trace(str(tmp_path / "b")):
        with trace.span("test.host_scan"):        # marks the capture
            scan_reduce_ref(*plans[0], LQC)
    assert read(rec) is None
