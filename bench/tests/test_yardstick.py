"""The benchmark's frozen generators give what the program's own give, at
a small fleet (mult=2)."""
import numpy as np
import pytest

from bench import yardstick


def test_poisson_stream_matches_program():
    from repro.core import PoissonArrivals
    for rate, seed in ((240.0, 7), (7680.0, 2**31 + 5)):
        a = yardstick.PoissonArrivals(rate, seed=seed).times(3.0)
        b = PoissonArrivals(rate, seed=seed).times(3.0)
        np.testing.assert_array_equal(a, b)


def test_fleet_counts_match_program():
    from benchmarks.scaling import mining_counts
    for mult in (2, 8, 64):
        assert yardstick.mining_counts(mult) == mining_counts(mult)


@pytest.fixture(scope="module")
def testbed2():
    from repro.core import build_testbed
    ec, sc = yardstick.mining_counts(2)
    return ec, build_testbed(edge_counts=ec, server_counts=sc)


def test_edge_names_and_sensor_ring_match_program(testbed2):
    from repro.core import mining_workload
    ec, tb = testbed2
    edges = yardstick.edge_names(ec)
    assert [n for n, _ in edges] == tb.edges
    assert dict(edges) == tb.edge_kind
    ring = yardstick.capability_ring(edges)
    # the program walks its ring in order: one sensor per slot reads it out
    cfg = mining_workload(tb, n_sensors=len(ring), n_readings=1)
    by_sensor = {t.attrs["sensor"]: t.origin for t in cfg}
    assert ring == [by_sensor[s] for s in range(len(ring))]
    # the benchmark spreads sensors along it, in proportion to the weights
    kind = dict(edges)
    for n_sensors in (25, 50, len(ring)):
        got = yardstick.sensor_edges(edges, n_sensors)
        for name, _ in edges:
            share = yardstick.MINING_RING_WEIGHTS[kind[name]] \
                * n_sensors / len(ring)
            assert abs(got.count(name) - share) < 1.0


def test_churn_schedule_matches_program(testbed2):
    from repro.core import wireless_churn_schedule
    ec, tb = testbed2
    up = {f"link_{n}": 1e9 for n, _ in yardstick.edge_names(ec)}
    for seed in (0, 123456789):
        ours = yardstick.wireless_churn_schedule(up, 6, seed=seed)
        theirs = wireless_churn_schedule(tb, 6, seed=seed)
        assert [tuple(w.bandwidth) for w in theirs] == list(ours)


def test_zipf_ranking_keeps_kinds_and_permutes_edges():
    ec, _ = yardstick.mining_counts(2)
    edges = yardstick.edge_names(ec)
    kind = dict(edges)
    a = yardstick.zipf_ranked_edges(edges, np.random.default_rng(1))
    b = yardstick.zipf_ranked_edges(edges, np.random.default_rng(2))
    assert sorted(a) == sorted(n for n, _ in edges)
    assert [kind[x] for x in a] == [kind[x] for x in b]
    assert a != b


def test_zipf_draw_law():
    rng = np.random.default_rng(0)
    r = yardstick.zipf_draw(640, 0.99, 200_000, rng)
    assert r.min() >= 0 and r.max() < 640
    w = 1.0 / np.arange(1, 641) ** 0.99
    share0 = np.mean(r == 0)
    assert abs(share0 - w[0] / w.sum()) < 0.005
