"""The benchmark's own traffic generators, frozen here so that a change to
the program's versions cannot move a cell.

Each function is a copy of the program's generator of the same job
(``PoissonArrivals`` in ``core/serving.py``, ``mining_counts`` in
``benchmarks/scaling.py``, the capability ring of ``mining_workload`` and
``wireless_churn_schedule`` in ``core/workloads.py``); the tests hold each
copy to the original at a small fleet.  Sensors are spread along the ring
(``sensor_edges``) where the program fills it in order, and the Zipf
ranking and the phased periodic sources (``periodic_sources``, where the
program's ``vr_workload`` starts every headset at 0) are the benchmark's
own.  Nothing here imports the program.
"""
from __future__ import annotations

import itertools
import math
import random

import numpy as np

MINING_RING_WEIGHTS = {"orin_agx": 4, "xavier_agx": 3, "orin_nano": 2,
                       "xavier_nx": 1}


class PoissonArrivals:
    """Homogeneous Poisson stream at ``rate`` arrivals per simulated
    second, deterministic per ``(rate, seed)``: gaps are drawn in blocks of
    ``batch`` from a generator re-seeded on every ``times`` call."""

    def __init__(self, rate: float, seed: int = 0, batch: int = 4096) -> None:
        if rate <= 0:
            raise ValueError(f"rate must be positive, got {rate}")
        self.rate = float(rate)
        self.seed = int(seed)
        self.batch = int(batch)

    def times(self, horizon: float) -> np.ndarray:
        """All arrival instants in ``[0, horizon)``, ascending."""
        rng = np.random.default_rng(self.seed)
        out = []
        t = 0.0
        while t < horizon:
            ts = t + np.cumsum(rng.exponential(1.0 / self.rate, self.batch))
            out.append(ts)
            t = float(ts[-1])
        arr = np.concatenate(out)
        return arr[arr < horizon]


def mining_counts(mult: int) -> tuple[dict, dict]:
    """Fig. 13 mining fleet at 1/8th of the paper's ratios, times ``mult``
    (``mult=8`` is the paper's 100-sensor / 80-edge / 24-server scale)."""
    ec = {"orin_agx": 3 * mult, "xavier_agx": 3 * mult,
          "orin_nano": 2 * mult, "xavier_nx": 2 * mult}
    sc = {"server1": mult, "server2": mult, "server3": mult}
    return ec, sc


def edge_names(edge_counts: dict) -> list[tuple[str, str]]:
    """``(name, kind)`` of every edge device in fleet order, named as the
    testbed builder names them (``<kind>_e<ordinal>``)."""
    out = []
    for kind, n in edge_counts.items():
        for _ in range(n):
            out.append((f"{kind}_e{len(out)}", kind))
    return out


def capability_ring(edges: list[tuple[str, str]],
                    weights: dict = MINING_RING_WEIGHTS) -> list[str]:
    """The program's capability-weighted ring: each edge, in fleet order,
    repeated as many times as its kind's weight."""
    ring = list(itertools.chain.from_iterable(
        [name] * weights.get(kind, 1) for name, kind in edges))
    return ring or [name for name, _ in edges]


def sensor_edges(edges: list[tuple[str, str]], n_sensors: int,
                 weights: dict = MINING_RING_WEIGHTS) -> list[str]:
    """The edge each sensor uplinks through: sensors spaced evenly along
    the capability-weighted ring, so every edge carries sensors in
    proportion to its kind's weight (paper section 5.6: sensors are
    connected to the edges by their computing capability)."""
    ring = capability_ring(edges, weights)
    return [ring[(s * len(ring)) // n_sensors] for s in range(n_sensors)]


def periodic_sources(edges: list[tuple[str, str]], fps: dict,
                     horizon: float, rng: np.random.Generator
                     ) -> tuple[np.ndarray, list[str]]:
    """Arrival instants in ``[0, horizon)`` and origin edges of one
    periodic source on every edge, in time order: each source (a headset)
    sends one request per period ``1 / fps[kind]`` from a phase drawn
    uniformly over its first period, sources taken in fleet order.  Every
    seed gives each source the same rate."""
    times, owner = [], []
    for e, (_, kind) in enumerate(edges):
        period = 1.0 / fps[kind]
        phase = rng.uniform(0.0, period)
        t = phase + period * np.arange(
            max(0, math.ceil((horizon - phase) / period)))
        t = t[t < horizon]
        times.append(t)
        owner.append(np.full(len(t), e))
    t = np.concatenate(times)
    order = np.argsort(t, kind="stable")
    names = [name for name, _ in edges]
    return t[order], [names[e] for e in np.concatenate(owner)[order].tolist()]


def zipf_ranked_edges(edges: list[tuple[str, str]],
                      rng: np.random.Generator) -> list[str]:
    """Edges in Zipf rank order: the kind at each rank is fixed by the
    fleet order, and the seed permutes the edges within each kind, so every
    seed draws the same mix of device kinds at each rank in a different
    placement."""
    by_kind: dict[str, list[str]] = {}
    for name, kind in edges:
        by_kind.setdefault(kind, []).append(name)
    kinds = list(by_kind)
    shuffled = {k: [by_kind[k][i] for i in rng.permutation(len(by_kind[k]))]
                for k in kinds}
    # interleave the kinds in proportion (largest deficit first, ties to
    # the kind listed first), so the head of the ranking holds every kind
    # in its fleet share rather than one kind alone
    pos = {k: 0 for k in kinds}
    total = len(edges)
    out = []
    for r in range(total):
        open_kinds = [k for k in kinds if pos[k] < len(by_kind[k])]
        kind = max(open_kinds, key=lambda k: (
            len(by_kind[k]) * (r + 1) / total - pos[k], -kinds.index(k)))
        out.append(shuffled[kind][pos[kind]])
        pos[kind] += 1
    return out


def zipf_draw(n_items: int, s: float, size: int,
              rng: np.random.Generator) -> np.ndarray:
    """``size`` ranks in ``[0, n_items)`` drawn from a bounded Zipf law
    with exponent ``s`` (P(rank k) proportional to 1 / (k+1)**s)."""
    w = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      n_items - 1)


def wireless_churn_schedule(uplinks: dict[str, float], n_waves: int,
                            seed: int = 0, churn_frac: float = 0.25,
                            min_scale: float = 0.05,
                            max_scale: float = 0.5) -> list[tuple]:
    """Seeded bandwidth-volatility schedule over the edge uplinks.

    ``uplinks`` maps each uplink name, in fleet order, to its nominal
    bandwidth.  Each wave first restores every degraded uplink to nominal,
    then degrades a fresh ``churn_frac`` sample of them to
    ``uniform(min_scale, max_scale)`` of nominal.  Returns one tuple of
    ``(link, bandwidth)`` entries per wave."""
    rng = random.Random(seed)
    links = list(uplinks)
    k = max(1, int(len(links) * churn_frac))
    degraded: dict[str, float] = {}
    waves = []
    for _ in range(n_waves):
        entries = [(name, uplinks[name]) for name in sorted(degraded)]
        degraded.clear()
        for name in rng.sample(links, k):
            bw = uplinks[name] * rng.uniform(min_scale, max_scale)
            degraded[name] = bw
            entries.append((name, bw))
        waves.append(tuple(entries))
    return waves
