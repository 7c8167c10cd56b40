"""The plain reference of the serving loop, on hand-sized cases whose
answers follow from the testbed's numbers alone."""
import math

import pytest

from bench import loop_reference as lr
from bench import yardstick


@pytest.fixture(scope="module")
def fleet():
    ec, sc = yardstick.mining_counts(1)
    return lr.Fleet({"fleet": {"edges": ec, "servers": sc}},
                    lr.load_testbed())


def _pu(fleet, name):
    return fleet.pus[fleet.by_name[name]]


def _task(fleet, kind, origin, uid=1):
    tb = lr.load_testbed()
    return lr.TaskIn(uid=uid, kind=kind, origin=fleet.device_of(origin),
                     u=tb["usage"][kind]["pu"], mem=tb["usage"][kind]["mem"],
                     dl=0.1, in_bytes=64000.0)


def test_fleet_shape_and_network(fleet):
    assert len(fleet.devices) == 13 and len(fleet.pus) == 10 * 6 + 3 * 2
    a, b = fleet.device_of("orin_agx_e0"), fleet.device_of("orin_agx_e1")
    s = fleet.device_of("server1_s0")
    assert fleet.transfer_time(a, b, 64000.0) == pytest.approx(
        0.0006 + 64000.0 / 1e9)
    assert fleet.transfer_time(a, s, 64000.0) == pytest.approx(
        0.0023 + 64000.0 / 1e9)
    # cpu clusters meet at the L3, a cpu and the gpu at the LLC
    d = fleet.devices[a]
    pos = {fleet.pus[g].name.split(".")[1]: fleet.pus[g].pos for g in d.pus}
    assert d.ncr[pos["cpu0"]][pos["cpu1"]] == "l3"
    assert d.ncr[pos["gpu"]][pos["cpu0"]] == "llc"


def test_walk_stays_on_an_idle_origin(fleet):
    w = lr.Walker(fleet)
    t = _task(fleet, "svm", "orin_agx_e0")
    best, mine, ov, in_scope = w.walk(t, 0.0, fleet.by_name["orin_agx_e0.gpu"])
    assert best.pu.name == "orin_agx_e0.gpu"
    assert best.total == pytest.approx(0.008) and best.factor == 1.0
    assert in_scope and ov == pytest.approx(6 * 5e-6)


def test_walk_escalates_past_a_full_origin(fleet):
    w = lr.Walker(fleet)
    origin = fleet.device_of("xavier_nx_e9")
    # every PU of the origin runs its tenancy of tasks that end late
    for g in fleet.devices[origin].pus:
        pu = fleet.pus[g]
        for k in range(pu.tenancy):
            w.belief[origin].append(lr.Belief(
                uid=1000 + 10 * g + k, pu=pu, u=1.0, m=0.9, est=0.5,
                fac=1.0, dl=math.inf, rel=0.0))
    t = _task(fleet, "knn", "xavier_nx_e9")
    best, _, ov, _ = w.walk(t, 0.0, fleet.by_name["orin_agx_e0.gpu"])
    # the fastest idle gpu one LAN hop away, the first orin_agx: 14 ms
    # and the transfer
    assert best.pu.name == "orin_agx_e0.gpu"
    assert best.total == pytest.approx(0.014 + 0.0006 + 64000.0 / 1e9)
    assert ov == pytest.approx(6 * 5e-6)


def test_timeline_transfer_then_shared_compute(fleet):
    tl = lr.Timeline(fleet, noise=0.0, seed=0)
    gpu = _pu(fleet, "orin_agx_e1.gpu")
    origin = fleet.device_of("orin_agx_e0")
    jobs = [lr.Job(uid=u, pu=gpu, sa=0.008, u=1.0, m=0.6, irr=1.4,
                   release=0.0, origin=origin, in_bytes=64000.0)
            for u in (1, 2)]
    tl.inject(jobs)
    tl.advance(1.0)
    # both inputs share the two LAN links, then the route's latency
    land = 2 * 64000.0 / 1e9 + 0.0006
    # two tenants of one gpu: 1 + 0.4598 * 1 * (1 + 0.12) each
    f = 1 + 0.4598 * 1.0 * 1.12
    for j in jobs:
        assert j.finish == pytest.approx(land + 0.008 * f, rel=1e-12)
    assert sorted(tl.drain()) == [1, 2]
