"""The benchmark's frozen generators and data give what the program's own
give, at a small fleet (mult=2): the streams, the VR frame as the harness
builds it from its configuration, and the testbed's VR tables."""
from pathlib import Path

import numpy as np
import pytest

from bench import yardstick
from bench.cell import load_json, request_builder, request_rate
from bench.loop_reference import load_testbed

VR = load_json(Path(__file__).resolve().parents[1] / "configs" / "vr-x64.json")


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


def _deps(tasks, graph):
    pos = {t.uid: i for i, t in enumerate(tasks)}
    return [[pos[p.uid] for p in graph.preds(t)] for t in tasks]


def _proportional_shares(edge_kind):
    """Section 5.3.2's rule over the program's Fig. 9 tables: each stage's
    share of the period is its latency on the headset kind's fastest PU
    over the sum of the seven."""
    from repro.core.topology import _VR_EDGE
    from repro.core.workloads import VR_TASKS
    lat = {k: min(_VR_EDGE[k][edge_kind].values()) for k in VR_TASKS}
    total = sum(lat.values())
    return {k: v / total for k, v in lat.items()}


@pytest.mark.parametrize("kind", ["orin_agx", "xavier_agx", "orin_nano",
                                  "xavier_nx"])
def test_vr_deadlines_divide_the_period_by_edge_latency(kind):
    tb = load_testbed()
    stages = VR["request"]["stages"]
    lat = [min(tb["standalone_ms"][st["kind"]][kind].values())
           for st in stages]
    period = 1.0 / VR["sources"]["fps"][kind]
    got = [st["deadline_s"][kind] for st in stages]
    assert got == [x / sum(lat) * period for x in lat]
    assert sum(got) == pytest.approx(period, rel=1e-12)


@pytest.mark.parametrize("kind", ["orin_agx", "xavier_agx", "orin_nano",
                                  "xavier_nx"])
def test_vr_frame_built_from_the_config_matches_program(kind):
    from repro.core import TaskGraph, vr_frame
    edge = next(n for n, k in yardstick.edge_names(VR["fleet"]["edges"])
                if k == kind)
    ours = request_builder(VR, [edge])(0, 0.0)
    cfg = TaskGraph("frame")
    theirs = vr_frame(cfg, edge, kind, 0, shares=_proportional_shares(kind))
    a, b = list(ours), list(theirs)
    assert len(a) == len(b) == 7
    for x, y in zip(a, b):
        assert (x.kind, x.origin, x.deadline, x.input_bytes, x.output_bytes,
                x.release_time, x.usage, x.attrs["irregularity"]) == \
            (y.kind, y.origin, y.deadline, y.input_bytes, y.output_bytes,
             y.release_time, y.usage, y.attrs["irregularity"])
        assert x.attrs["pinned"] == y.attrs["pinned"]
        assert x.attrs.get("succ_pinned_bytes") == \
            y.attrs.get("succ_pinned_bytes")
    assert _deps(a, ours) == _deps(b, cfg)


def test_vr_sources_match_program_rates():
    from repro.core.topology import EDGE_FPS
    assert VR["sources"]["fps"] == EDGE_FPS
    assert request_rate(VR) == 7296.0
    edges = yardstick.edge_names(VR["fleet"]["edges"])
    t, origins = yardstick.periodic_sources(
        edges, EDGE_FPS, 0.5, np.random.default_rng(3))
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 0.5
    kind = dict(edges)
    for name in (edges[0][0], edges[-1][0]):
        mine = t[[o == name for o in origins]]
        period = 1.0 / EDGE_FPS[kind[name]]
        assert mine[0] < period
        np.testing.assert_allclose(np.diff(mine), period)
        assert len(mine) == int((0.5 - mine[0]) // period) + 1


def test_testbed_vr_tables_match_program():
    from repro.core.topology import (TASK_IRREGULARITY, TASK_USAGE,
                                     _VR_EDGE, _VR_SERVER)
    from repro.core.workloads import VR_TASKS
    tb = load_testbed()
    for k in VR_TASKS:
        want = dict(_VR_EDGE[k])
        want.update(_VR_SERVER.get(k, {}))
        assert tb["standalone_ms"][k] == want
        assert tb["usage"][k] == TASK_USAGE[k]
        assert tb["irregularity"][k] == TASK_IRREGULARITY.get(k, 1.0)
