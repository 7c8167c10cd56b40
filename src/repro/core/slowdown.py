"""Decoupled shared-resource slowdown models (paper §3.4).

The paper's accuracy insight: *decouple* standalone performance from the
slowdown caused by shared-resource use.  Once per system, each shareable
resource is characterized for the slowdown it induces per amount of
concurrent use; each task is characterized by its generalized usage of each
resource; at runtime ``slowdown()`` combines the two.

Two contention mechanisms (paper §2.2, Fig. 2):

* **Shared-memory contention across PUs** — discovered via the HW-GRAPH:
  the *nearest common resource* on the two PUs' compute paths is the
  contention point (e.g. two cores in one cluster meet at L2; cores in
  different clusters meet at L3; GPU and DLA meet at DRAM).  Using the
  nearest common point (rather than every shared node) reflects that an
  upstream shared cache merges/filters traffic before it reaches deeper
  levels, and is what reproduces the paper's Fig. 2 ordering
  (L2 0.91x > L3 0.87x).

* **Multi-tenancy on one PU** — co-tenant tasks on the same PU slow each
  other down by a PU-class-specific factor (GPU 0.66x for 2 DNNs, etc.).

Calibration below reproduces the paper's Orin AGX measurements:
  same-cluster CPU MMs (L2)          -> 0.91x   => beta_l2  = 0.099
  cross-cluster CPU MMs (L3)         -> 0.87x   => beta_l3  = 0.149
  2 DNNs on one GPU (multi-tenancy)  -> 0.66x   => mt_gpu   = 0.515
  GPU + DLA via shared DRAM          -> 0.68x   => beta_dram= 0.47
  CPU + GPU via shared 4MB LLC       -> 0.89x   => beta_llc = 0.124

The ground-truth simulator uses the same structure with a superlinear term
and task-kind-specific irregular-access noise (``truth_params``), so that the
H-EYE predictor (linear, noise-free) exhibits a small but honest error while
contention-blind baselines (ACE-like) err by the full contention amount.

Batched evaluation: the per-pair helpers (``nearest_shared``, ``factor``)
now read the ``CompiledHWGraph`` snapshot (nearest-common-resource matrix,
per-PU caps/classes), and three vectorized entry points evaluate whole
pools at once over the same arrays — ``factor_batch`` (joint factors of a
co-running pool, used by the Traverser at contention-interval boundaries),
``slowdown_matrix`` (all pairwise co-run factors in one shot) and
``factors_with_candidates`` (the Orchestrator's one-shot constraint check
over every candidate PU).  The factor-aggregation inner loop runs the
Pallas kernel (kernels/slowdown_kernel.py) when ``jax.default_backend()``
is ``tpu`` and the equivalent float64 numpy reference on every other
backend.  The numpy path matches the scalar path to 1e-9; the TPU kernel
computes in fp32 (within 1e-6 relative of the reference).
``REPRO_SLOWDOWN_KERNEL=ref`` forces the numpy path on any backend,
``=pallas`` forces the kernel (interpret mode off-TPU).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from . import trace
from .hwgraph import HWGraph
from .task import Task

# resource classes a STORAGE/CONTROLLER node may declare in attrs["rclass"]
RCLASSES = ("l2", "l3", "llc", "sram", "dram", "hbm", "vmem", "nic")

# beta for rclasses absent from SlowdownParams.beta (matches the scalar
# path's ``p.beta.get(rclass, 0.3)``)
_DEFAULT_BETA = 0.3

# pools at or below this size route through the exact scalar loop in
# ``factor_batch_idx``: below ~n=10 the array path's fixed call overhead
# (bincount/nonzero/broadcast setup) dominates the actual math, and up
# to 7 co-runner rows the sequential scalar sums match BLAS's dot
# reductions bit-for-bit (8-wide rows start SIMD-reordering the adds).
# tests/test_slowdown assert both the dispatch boundary and bit-equality.
_SMALL_POOL_MAX = 7


@dataclass
class SlowdownParams:
    # sensitivity of each resource class to one unit of co-runner pressure,
    # normalized so that beta * 1.12 reproduces Fig. 2 at x=1 co-runner
    # (the 1.12 = 1 + superlinear accounts for the profiled curvature)
    beta: dict[str, float] = field(default_factory=lambda: {
        "l2": 0.0884, "l3": 0.1330, "llc": 0.1107, "sram": 0.1786,
        "dram": 0.4196, "hbm": 0.2679, "vmem": 0.0, "nic": 0.0893,
    })
    # multi-tenancy sensitivity per PU class
    mt_beta: dict[str, float] = field(default_factory=lambda: {
        "cpu": 0.3125, "gpu": 0.4598, "dla": 0.3571, "vic": 0.2232,
        "pva": 0.2679, "tpu": 0.4018, "default": 0.3571,
    })
    superlinear: float = 0.12   # kappa: factor term beta*x*(1+kappa*x)
    noise: float = 0.0          # rel. sigma of task-irregularity noise (truth only)

    def mt(self, pu_class: str) -> float:
        return self.mt_beta.get(pu_class, self.mt_beta["default"])


def heye_params() -> SlowdownParams:
    """The calibrated model H-EYE's Traverser uses for prediction.

    The paper's step (1) profiles each shared resource "for the slowdown
    they will experience per the amount of concurrent use" — i.e. the
    calibration covers every concurrency level, so the predictor carries
    the same superlinear shape as the system it was profiled on.  What it
    can NOT know is the per-execution irregular-access noise (§5.2 names
    exactly this as the source of H-EYE's residual 3.2% error)."""
    return SlowdownParams(superlinear=0.12)


def truth_params(noise: float = 0.035, superlinear: float = 0.12) -> SlowdownParams:
    """Ground-truth behaviour: profiled contention + irregular-access noise.

    These produce the paper-reported gap: H-EYE predicts within a few
    percent (missing only the noise) while a contention-blind model misses
    the entire slowdown (tens of percent under heavy sharing)."""
    return SlowdownParams(superlinear=superlinear, noise=noise)


# ---------------------------------------------------------------------------
# batched factor aggregation: numpy fast path + Pallas kernel on TPU
# ---------------------------------------------------------------------------
def _pterm_arr(beta: np.ndarray, x: np.ndarray, kappa: float) -> np.ndarray:
    """Vectorized ``_pressure_term``: beta*x*(1+kappa*x), 0 where inactive."""
    return np.where((x > 0.0) & (beta > 0.0),
                    beta * x * (1.0 + kappa * x), 0.0)


def _aggregate_np(x: np.ndarray, beta: np.ndarray, mem: np.ndarray,
                  mt_term: np.ndarray, kappa: float) -> np.ndarray:
    """factors[i] = (1+mt_term[i]) * prod_r(1 + pterm(beta[r], x[i,r])*mem[i]).

    Same formula as ``kernels.ref.slowdown_factors_ref`` (the Pallas
    oracle); kept inline so the CPU path never imports the kernels."""
    term = _pterm_arr(beta[None, :], x, kappa)
    return np.maximum(1.0, (1.0 + mt_term)
                      * np.prod(1.0 + term * mem[:, None], axis=-1))


_AGGREGATE = None


def _aggregate(x, beta, mem, mt_term, kappa):
    """Batched factor-aggregation inner loop, through the implementation
    :func:`_select_aggregate` picks once per process."""
    global _AGGREGATE
    if _AGGREGATE is None:
        _AGGREGATE = _select_aggregate()
    return _AGGREGATE(x, beta, mem, mt_term, kappa)


def _select_aggregate():
    """The Pallas kernel on a TPU backend, the numpy reference elsewhere.

    ``REPRO_SLOWDOWN_KERNEL`` overrides the choice (``ref`` | ``pallas`` |
    ``auto``).  The kernel runs in fp32, so deployments that need
    bit-stable scheduling across backends pin ``ref``.  A kernel that
    fails raises; nothing falls back to the host in silence."""
    mode = os.environ.get("REPRO_SLOWDOWN_KERNEL", "auto").lower()
    if mode == "ref":
        return _aggregate_np
    import jax
    if mode == "pallas" or jax.default_backend() == "tpu":
        from ..kernels.slowdown_kernel import slowdown_factors_pallas
        return slowdown_factors_pallas
    return _aggregate_np


class DecoupledSlowdown:
    """slowdown(task on pu | co-running tasks) -> multiplicative factor >= 1."""

    def __init__(self, graph: HWGraph, params: Optional[SlowdownParams] = None,
                 rng: Optional[np.random.Generator] = None) -> None:
        self.graph = graph
        self.params = params or heye_params()
        self.rng = rng
        # (snapshot, (beta_vec, mt_vec)) — rebuilt when the graph compiles
        # a new snapshot; holding the snapshot itself makes the identity
        # check safe (it cannot be freed and its id reused while cached)
        self._tables_cache: Optional[tuple] = None
        # canonical-pattern result cache for single-device constraint
        # checks (see _canon_key); keyed per snapshot identity like the
        # tables.  The sharded walk's group threads share it: fills are
        # idempotent equal values, and its hits and misses count in
        # ``trace`` (``cache.canon.hit`` / ``.miss``)
        self._canon_cache: Optional[tuple] = None

    # -- helpers -----------------------------------------------------------
    def nearest_shared(self, pu_a: str, pu_b: str) -> Optional[str]:
        """Nearest common resource on the compute paths of two PUs (or None
        if the PUs share nothing, e.g. they sit in different devices).

        Reads the compiled nearest-common-resource matrix, which tracks
        topology mutations automatically (no manual cache invalidation)."""
        return self.graph.compiled().nearest_common_resource(pu_a, pu_b)

    def invalidate(self) -> None:
        """Kept for API compatibility: the compiled snapshot invalidates
        itself on topology mutation, so there is no cache to clear."""
        self._tables_cache = None
        self._canon_cache = None

    def _pressure_term(self, beta: float, x: float) -> float:
        if x <= 0.0 or beta <= 0.0:
            return 0.0
        return beta * x * (1.0 + self.params.superlinear * x)

    def _mem_usage(self, task: Task, pu_name: str) -> float:
        """Effective shared-memory pressure of ``task`` when run on ``pu``.
        PUs with private data storage (e.g. VIC, §5.3.1) cap it."""
        u = task.usage.get("mem", 1.0)
        cap = self.graph.nodes[pu_name].attrs.get("mem_usage_cap")
        return min(u, cap) if cap is not None else u

    # -- per-snapshot model tables ----------------------------------------
    @staticmethod
    def _factor_state(comp) -> tuple:
        """The snapshot columns the factor model actually reads.  Two
        snapshots whose columns are the *same objects* (a bandwidth-only
        ``apply_delta`` clone shares everything but the route table) are
        kin: cached tables and canonical factors carry over verbatim."""
        return (comp.rclass_names, comp.pu_class_kind,
                getattr(comp, "ncr_rclass", None),
                getattr(comp, "mem_cap", None),
                getattr(comp, "pu_index", None))

    @classmethod
    def _factor_kin(cls, a, b) -> bool:
        return all(x is y for x, y in
                   zip(cls._factor_state(a), cls._factor_state(b)))

    def _tables(self, comp) -> tuple[np.ndarray, np.ndarray]:
        """(beta per compiled rclass, mt-beta per compiled PU); cached per
        snapshot identity, so a topology mutation (new snapshot) rebuilds
        them and stale coefficients can never leak across versions.
        Bandwidth-only delta clones are rebased, not rebuilt."""
        cached = self._tables_cache
        if cached is not None and cached[0] is not comp \
                and self._factor_kin(cached[0], comp):
            cached = (comp, cached[1])
            self._tables_cache = cached
        if cached is None or cached[0] is not comp:
            p = self.params
            beta_vec = np.array([p.beta.get(rc, _DEFAULT_BETA)
                                 for rc in comp.rclass_names])
            mt_vec = np.array([p.mt_beta.get(cls, p.mt_beta["default"])
                               for cls in comp.pu_class_kind])
            cached = (comp, (beta_vec, mt_vec))
            self._tables_cache = cached
        return cached[1]

    def _pool_arrays(self, comp, pool: Sequence[tuple[Task, str]]):
        n = len(pool)
        P = np.fromiter((comp.pu_index[p] for _, p in pool),
                        dtype=np.int64, count=n)
        U = np.fromiter((t.usage.get("pu", 1.0) for t, _ in pool),
                        dtype=np.float64, count=n)
        mem = np.fromiter((t.usage.get("mem", 1.0) for t, _ in pool),
                          dtype=np.float64, count=n)
        M = np.minimum(mem, comp.mem_cap[P])
        uid = np.fromiter((t.uid for t, _ in pool), dtype=np.int64, count=n)
        return P, U, M, uid

    def _noisy(self) -> bool:
        return self.params.noise > 0.0 and self.rng is not None

    def _apply_noise(self, task: Task, f: float) -> float:
        irregularity = task.attrs.get("irregularity", 1.0)
        return f * float(np.exp(self.rng.normal(
            0.0, self.params.noise * irregularity)))

    # -- the model (scalar reference path) ---------------------------------
    def factor(self, task: Task, pu_name: str,
               coruns: list[tuple[Task, str]]) -> float:
        """Multiplicative slowdown of ``task`` running on ``pu_name`` while
        each (other_task, other_pu) in ``coruns`` runs concurrently."""
        p = self.params
        f = 1.0
        pu = self.graph.nodes[pu_name]
        pu_class = pu.attrs.get("pu_class_kind", pu.attrs.get("pu_class", "default"))
        # split co-runners: same-PU tenants vs other-PU resource sharers
        mt_pressure = 0.0
        res_pressure: dict[str, float] = {}
        for other, other_pu in coruns:
            if other.uid == task.uid:
                continue
            if other_pu == pu_name:
                mt_pressure += other.usage.get("pu", 1.0)
            else:
                shared = self.nearest_shared(pu_name, other_pu)
                if shared is None:
                    continue
                rclass = self.graph.nodes[shared].attrs.get("rclass", "dram")
                res_pressure[rclass] = (res_pressure.get(rclass, 0.0)
                                        + self._mem_usage(other, other_pu))
        if mt_pressure > 0.0:
            f *= 1.0 + self._pressure_term(p.mt(pu_class), mt_pressure
                                           ) * task.usage.get("pu", 1.0)
        for rclass, x in res_pressure.items():
            f *= 1.0 + self._pressure_term(p.beta.get(rclass, _DEFAULT_BETA), x
                                           ) * self._mem_usage(task, pu_name)
        if p.noise > 0.0 and self.rng is not None and f > 1.0:
            f = self._apply_noise(task, f)
        return max(1.0, f)

    # -- vectorized entry points -------------------------------------------
    def factor_batch(self, pool: Sequence[tuple[Task, str]]) -> np.ndarray:
        """Joint slowdown factor of every (task, pu) in ``pool`` given all
        the others — the quantity the Traverser recomputes at each
        contention-interval boundary, in one shot instead of O(n^2) Python
        pair loops.  Matches ``factor(t, p, pool)`` per entry to 1e-9."""
        n = len(pool)
        if n == 0:
            return np.ones(0)
        if self._noisy():
            # the scalar path draws rng noise per factor call in pool
            # order; preserve the exact stream
            return np.array([self.factor(t, p, list(pool)) for t, p in pool])
        comp = self.graph.compiled()
        P, U, M, uid = self._pool_arrays(comp, pool)
        return self._factor_batch_arrays(comp, P, U, M, uid)

    def factor_batch_idx(self, P: np.ndarray, U: np.ndarray,
                         mem: np.ndarray, uid: np.ndarray) -> np.ndarray:
        """Array-native :meth:`factor_batch` over ledger-style columns
        (compiled PU index, pu-usage, raw mem-usage, uid) — the DES
        timeline engine reprices every dirty device pool in one call
        through this entry, with no tuple building.  Because compute
        paths never cross device boundaries, a pool spanning several
        devices factors exactly as the per-device pools would
        (cross-device pairs share nothing by construction).  Noise-free
        path only (the engine routes noisy models to the tuple surface)."""
        with trace.span("slowdown.score"):
            n = len(P)
            if n == 0:
                return np.ones(0)
            comp = self.graph.compiled()
            if n == 1:
                return np.ones(1)          # a lone job has no co-runners
            M = np.minimum(mem, comp.mem_cap[P])
            if n == 2:
                # scalar pair path: light-load DES pools are mostly pairs, and
                # the float ops replicate the array path bit-for-bit (a row's
                # product over inactive rclasses multiplies exact 1.0s)
                return self._factor_pair(comp, P, U, M)
            if n <= _SMALL_POOL_MAX:
                # light-load pools floor on array-path call overhead (bincount,
                # nonzero, broadcasting all cost more than the math below this
                # size); the scalar loop replicates the array path bit-for-bit
                return self._factor_small(comp, P, U, M)
            # DES pools hold one job per task, so uids are pairwise distinct:
            # self-interaction reduces to the diagonal and the uid mask work
            # is skipped entirely
            return self._factor_batch_arrays(comp, P, U, M, uid, distinct=True)

    def _factor_pair(self, comp, P, U, M) -> np.ndarray:
        beta_vec, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        out = np.empty(2)
        p0, p1 = int(P[0]), int(P[1])
        for i, (pi, pj, j) in enumerate(((p0, p1, 1), (p1, p0, 0))):
            mt_term = 0.0
            res = 0.0
            if pi == pj:
                x = float(U[j])
                mtb = float(mt_vec[pi])
                if x > 0.0 and mtb > 0.0:
                    mt_term = mtb * x * (1.0 + kappa * x) * float(U[i])
            else:
                r = int(comp.ncr_rclass[pi, pj])
                if r >= 0:
                    x = float(M[j])
                    b = float(beta_vec[r])
                    if x > 0.0 and b > 0.0:
                        res = b * x * (1.0 + kappa * x)
            f = (1.0 + mt_term) * (1.0 + res * float(M[i]))
            out[i] = f if f > 1.0 else 1.0
        return out

    def _factor_small(self, comp, P, U, M) -> np.ndarray:
        """Exact scalar path for distinct-uid pools of a few members.

        Pressure accumulation runs in ascending co-runner order and the
        per-rclass product in ascending rclass order — the same orders the
        bincount / prod reductions of ``_factor_batch_arrays`` use — so
        the result is bit-identical to the array path (inactive rclasses
        multiply exact 1.0s there and are simply skipped here)."""
        beta_vec, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        n = len(P)
        Pi = [int(p) for p in P]
        Uf = [float(u) for u in U]
        Mf = [float(m) for m in M]
        out = np.empty(n)
        for i in range(n):
            pi = Pi[i]
            mt_p = 0.0
            res: dict[int, float] = {}
            for j in range(n):
                if j == i:
                    continue
                if Pi[j] == pi:
                    mt_p += Uf[j]
                else:
                    r = int(comp.ncr_rclass[pi, Pi[j]])
                    if r >= 0:
                        res[r] = res.get(r, 0.0) + Mf[j]
            mt_term = 0.0
            mtb = float(mt_vec[pi])
            if mt_p > 0.0 and mtb > 0.0:
                mt_term = mtb * mt_p * (1.0 + kappa * mt_p) * Uf[i]
            prod = 1.0
            for r in sorted(res):
                x = res[r]
                b = float(beta_vec[r])
                if x > 0.0 and b > 0.0:
                    prod *= 1.0 + b * x * (1.0 + kappa * x) * Mf[i]
            f = (1.0 + mt_term) * prod
            out[i] = f if f > 1.0 else 1.0
        return out

    def _factor_batch_arrays(self, comp, P, U, M, uid,
                             distinct: bool = False) -> np.ndarray:
        n = len(P)
        beta_vec, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        same_pu = P[:, None] == P[None, :]
        r = comp.ncr_rclass[P[:, None], P[None, :]]
        valid = ~same_pu & (r >= 0)
        if distinct:
            np.fill_diagonal(same_pu, False)
        else:
            diff_uid = uid[:, None] != uid[None, :]
            same_pu &= diff_uid
            valid &= diff_uid
        mtp = same_pu.astype(np.float64) @ U
        R = len(comp.rclass_names)
        ii, jj = np.nonzero(valid)
        if len(ii):
            # bincount over flattened (row, rclass) bins accumulates in
            # input order, exactly like the add.at it replaces
            X = np.bincount(ii * R + r[ii, jj], weights=M[jj],
                            minlength=n * R).reshape(n, R)
        else:
            X = np.zeros((n, R))
        mt_term = _pterm_arr(mt_vec[P], mtp, kappa) * U
        return _aggregate(X, beta_vec, M, mt_term, kappa)

    def slowdown_matrix(self, pool: Sequence[tuple[Task, str]]) -> np.ndarray:
        """All pairwise co-run factors in one shot: entry [i, j] is the
        factor of pool[i] when co-running with pool[j] alone (1.0 on the
        diagonal / for non-interfering pairs)."""
        n = len(pool)
        if n == 0:
            return np.ones((0, 0))
        if self._noisy():
            return np.array([[self.factor(ti, pi, [(tj, pj)])
                              for tj, pj in pool] for ti, pi in pool])
        comp = self.graph.compiled()
        beta_vec, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        P, U, M, uid = self._pool_arrays(comp, pool)
        diff_uid = uid[:, None] != uid[None, :]
        same_pu = (P[:, None] == P[None, :]) & diff_uid
        r = comp.ncr_rclass[P[:, None], P[None, :]]
        cross = diff_uid & (P[:, None] != P[None, :]) & (r >= 0)
        mt_f = 1.0 + _pterm_arr(mt_vec[P][:, None],
                                np.where(same_pu, U[None, :], 0.0),
                                kappa) * U[:, None]
        res_term = np.where(cross,
                            _pterm_arr(beta_vec[r.clip(0)],
                                       np.broadcast_to(M[None, :], (n, n)),
                                       kappa),
                            0.0)
        return np.maximum(1.0, mt_f * (1.0 + res_term * M[:, None]))

    def factors_with_candidates(
            self, task: Task, candidate_pus: Sequence[str],
            active: Sequence[tuple[Task, str]],
    ) -> tuple[np.ndarray, np.ndarray]:
        """One-shot Orchestrator constraint check over candidate PUs.

        Returns ``(new_f, act_f)`` where ``new_f[c]`` is the factor of
        ``task`` placed on ``candidate_pus[c]`` amid ``active``, and
        ``act_f[c, a]`` is the updated factor of ``active[a]`` if the task
        joins on candidate ``c`` (Alg. 1 line 15's "existing tasks keep
        their constraints" re-check, for every candidate at once)."""
        C = len(candidate_pus)
        A = len(active)
        comp = self.graph.compiled()
        if self._noisy() or C == 0:
            new_f = np.array([self.factor(task, p, list(active))
                              for p in candidate_pus])
            act_f = np.empty((C, A))
            for c, p in enumerate(candidate_pus):
                pool = list(active) + [(task, p)]
                for a, (t, q) in enumerate(active):
                    act_f[c, a] = self.factor(t, q, pool)
            return new_f, act_f
        Pc = np.fromiter((comp.pu_index[p] for p in candidate_pus),
                         dtype=np.int64, count=C)
        Pa, Ua, Ma, uid_a = self._pool_arrays(comp, active)
        return self.factors_with_candidates_idx(comp, task, Pc,
                                                Pa, Ua, Ma, uid_a)

    def factors_with_candidates_idx(
            self, comp, task: Task, Pc: np.ndarray, Pa: np.ndarray,
            Ua: np.ndarray, Ma: np.ndarray, uid_a: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Array-native core of :meth:`factors_with_candidates`.

        Candidates arrive as compiled PU indices and the active set as
        struct-of-arrays ledger columns (PU index, pu-usage, capped
        mem-usage, uid), so the Orchestrator's batched constraint checks
        feed the ledger straight in without building object tuples.
        Noise-free path only — callers with a noisy model use the tuple
        entry point, which preserves the scalar rng stream."""
        C = len(Pc)
        A = len(Pa)
        beta_vec, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        R = len(comp.rclass_names)
        u_new = task.usage.get("pu", 1.0)
        mem_new = task.usage.get("mem", 1.0)
        Mc = np.minimum(mem_new, comp.mem_cap[Pc])
        if A == 0:
            return np.ones(C), np.ones((C, 0))
        # co-runners sharing the placed task's uid never interact with it
        # (the scalar path skips them); mask them out of its pressures and
        # never add its contribution to theirs
        live = uid_a != task.uid

        # --- the new task's factor under each candidate -------------------
        same_ca = (Pc[:, None] == Pa[None, :]) & live[None, :]     # (C, A)
        mt_c = same_ca.astype(np.float64) @ Ua
        r_ca = comp.ncr_rclass[Pc[:, None], Pa[None, :]]
        valid_ca = live[None, :] & (Pc[:, None] != Pa[None, :]) & (r_ca >= 0)
        Xc = np.zeros((C, R))
        ci, ai = np.nonzero(valid_ca)
        np.add.at(Xc, (ci, r_ca[ci, ai]), Ma[ai])
        mt_term_c = _pterm_arr(mt_vec[Pc], mt_c, kappa) * u_new
        new_f = _aggregate(Xc, beta_vec, Mc, mt_term_c, kappa)

        # --- each active's factor if the task joins on candidate c --------
        diff_aa = uid_a[:, None] != uid_a[None, :]
        same_aa = (Pa[:, None] == Pa[None, :]) & diff_aa
        mt_base = same_aa.astype(np.float64) @ Ua                  # (A,)
        r_aa = comp.ncr_rclass[Pa[:, None], Pa[None, :]]
        valid_aa = diff_aa & (Pa[:, None] != Pa[None, :]) & (r_aa >= 0)
        Xa = np.zeros((A, R))
        i2, j2 = np.nonzero(valid_aa)
        np.add.at(Xa, (i2, r_aa[i2, j2]), Ma[j2])
        join_same = (Pa[None, :] == Pc[:, None]) & live[None, :]   # (C, A)
        mt_ca = mt_base[None, :] + np.where(join_same, u_new, 0.0)
        r_ac = comp.ncr_rclass[Pa[None, :], Pc[:, None]]           # (C, A)
        join_cross = live[None, :] & (Pa[None, :] != Pc[:, None]) & (r_ac >= 0)
        X_full = np.repeat(Xa[None, :, :], C, axis=0)              # (C, A, R)
        c3, a3 = np.nonzero(join_cross)
        X_full[c3, a3, r_ac[c3, a3]] += Mc[c3]
        mt_term_a = _pterm_arr(np.broadcast_to(mt_vec[Pa][None, :], (C, A)),
                               mt_ca, kappa) * Ua[None, :]
        act_f = _aggregate(X_full.reshape(C * A, R), beta_vec,
                           np.tile(Ma, C), mt_term_a.reshape(C * A),
                           kappa).reshape(C, A)
        return new_f, act_f

    def factors_same_device(
            self, comp, task: Task, Pc: np.ndarray, Dc: np.ndarray,
            Pa: np.ndarray, Ua: np.ndarray, Ma: np.ndarray,
            uid_a: np.ndarray, Da: np.ndarray, astart: np.ndarray,
            na: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Block-diagonal constraint-check kernel over *many devices* at once.

        Compute paths never cross device boundaries, so PUs on different
        devices share no resources and a candidate only interacts with the
        actives of its own device.  One call scores every candidate of an
        arbitrary mixed-device set against a device-sorted active ledger
        (``Da`` ascending, ``astart``/``na`` the per-device-ordinal segment
        offsets/lengths), materializing only the same-device
        (candidate, active) pairs instead of a dense C x A block.

        Returns ``(new_f, ci, ai, act_pf)``: the newcomer's factor per
        candidate, and flat same-device pair arrays where ``act_pf[k]`` is
        the updated factor of active ``ai[k]`` if the task joins candidate
        ``ci[k]`` (the Alg. 1 l.15 inputs).  Noise-free path only.

        Structured as a pure row builder (:meth:`_same_device_rows`) plus
        one aggregation, so :meth:`factors_same_device_multi` can stack
        the rows of every distinct task signature in a mapping wave and
        aggregate the whole frontier in a single kernel call.
        """
        with trace.span("slowdown.score"):
            empty = np.zeros(0, dtype=np.int64)
            if len(Pc) == 0 or len(Pa) == 0:
                return np.ones(len(Pc)), empty, empty, np.ones(0)
            key, base = self._canon_key(comp, task, Pc, Dc, Pa, Ua, Ma, uid_a,
                                        astart, na)
            if key is not None:
                hit = self._canon_lookup(comp, key, base)
                if hit is not None:
                    return hit
            rows = self._same_device_rows(comp, task, Pc, Dc, Pa, Ua, Ma,
                                          uid_a, Da, astart, na)
            if rows is None:
                # no active shares a device with any candidate: all factors 1
                out = (np.ones(len(Pc)), empty, empty, np.ones(0))
            else:
                X, mem, mt_term, ci, ai = rows
                beta_vec, _ = self._tables(comp)
                C = len(Pc)
                f = _aggregate(X, beta_vec, mem, mt_term,
                               self.params.superlinear)
                out = (f[:C], ci, ai, f[C:])
            if key is not None:
                self._canon_store(key, base, out)
            return out

    def factors_same_device_multi(self, comp, items: Sequence[tuple]):
        """Score many newcomers (one per distinct wave signature) in one
        aggregation call.  ``items`` holds the positional argument tuples
        of :meth:`factors_same_device`; the result list holds that
        method's return tuple per item, bit-for-bit identical to calling
        it per item (the kernel is elementwise per row, so stacking and
        splitting is exact)."""
        with trace.span("slowdown.score"):
            empty = np.zeros(0, dtype=np.int64)
            built: list = []
            blocks: list = []
            keys: list = []
            for it in items:
                if len(it[1]) == 0 or len(it[3]) == 0:
                    built.append(None)
                    keys.append(None)
                    continue
                key, base = self._canon_key(comp, it[0], it[1], it[2], it[3],
                                            it[4], it[5], it[6], it[8], it[9])
                if key is not None:
                    hit = self._canon_lookup(comp, key, base)
                    if hit is not None:
                        built.append(hit)
                        keys.append(None)       # already cached
                        continue
                keys.append((key, base))
                rows = self._same_device_rows(comp, *it)
                built.append(rows)
                if rows is not None:
                    blocks.append(rows)
            if blocks:
                beta_vec, _ = self._tables(comp)
                f = _aggregate(np.concatenate([b[0] for b in blocks]),
                               beta_vec,
                               np.concatenate([b[1] for b in blocks]),
                               np.concatenate([b[2] for b in blocks]),
                               self.params.superlinear)
            pos = 0
            out = []
            for it, rows, kb in zip(items, built, keys):
                C = len(it[1])
                if isinstance(rows, tuple) and len(rows) == 4:
                    out.append(rows)            # cache hit, already final
                    continue
                if rows is None:
                    res = (np.ones(C), empty, empty, np.ones(0))
                else:
                    k = len(rows[1])
                    fi = f[pos:pos + k]
                    pos += k
                    res = (fi[:C], rows[3], rows[4], fi[C:])
                if kb is not None and kb[0] is not None:
                    self._canon_store(kb[0], kb[1], res)
                out.append(res)
            return out

    # -- canonical-pattern cache (single-device constraint checks) ---------
    def _canon_key(self, comp, task: Task, Pc, Dc, Pa, Ua, Ma, uid_a,
                   astart, na):
        """Structural cache key of one single-device constraint check.

        Two checks share a key iff every input the kernel math reads is
        identical *up to PU identity*: the candidate/active PU-equality
        pattern, the nearest-common-resource classes over all pairs, the
        per-PU model coefficients and caps, the active usage columns (in
        ledger order — order matters because the pressure reductions
        accumulate in it), the alive-pair mask against the newcomer's uid,
        and the newcomer's own usages.  Replicated mult=N fleets then
        share one kernel evaluation per structural pattern instead of one
        per device.  Returns ``(key, active_base)`` — pair indices are
        cached relative to the device's ledger segment and rebased on hit
        — or ``(None, 0)`` when the candidates span devices (the rare
        mixed case keeps the direct path)."""
        d0 = int(Dc[0])
        if not bool((Dc == d0).all()):
            return None, 0
        s = int(astart[d0])
        n_dev = int(na[d0])
        sel = slice(s, s + n_dev)
        L = np.concatenate([Pc, Pa[sel]])
        # equality pattern of L (np.unique(return_inverse) without its
        # dispatch overhead: these are ~a-device's-worth of ints)
        su = np.sort(L)
        uniq = su[np.concatenate(([True], su[1:] != su[:-1]))]
        inv = np.searchsorted(uniq, L)
        live = uid_a[sel] != task.uid
        _, mt_vec = self._tables(comp)
        key = (len(Pc), n_dev,
               task.usage.get("pu", 1.0), task.usage.get("mem", 1.0),
               inv.tobytes(),
               comp.ncr_rclass[L[:, None], L[None, :]].tobytes(),
               mt_vec[L].tobytes(), comp.mem_cap[L].tobytes(),
               Ua[sel].tobytes(), Ma[sel].tobytes(), live.tobytes())
        return key, s

    def _canon_cache_dict(self, comp) -> dict:
        cached = self._canon_cache
        if cached is not None and cached[0] is not comp \
                and self._factor_kin(cached[0], comp):
            # bandwidth-only delta clone: the canonical keys hash every
            # value the kernel math reads, none of which changed — keep
            # the warm factors instead of recomputing the whole fleet
            cached = (comp, cached[1])
            self._canon_cache = cached
        if cached is None or cached[0] is not comp:
            cached = (comp, {})
            self._canon_cache = cached
        return cached[1]

    def _canon_lookup(self, comp, key, base):
        hit = self._canon_cache_dict(comp).get(key)
        if hit is None:
            trace.count("cache.canon.miss")
            return None
        trace.count("cache.canon.hit")
        new_f, ci, rel_ai, act_pf = hit
        return new_f, ci, rel_ai + base, act_pf

    def _canon_store(self, key, base, result) -> None:
        # _canon_lookup always ran first, so the per-snapshot dict exists
        cache = self._canon_cache[1]
        if len(cache) > 100_000:            # runaway-key backstop
            cache.clear()
        new_f, ci, ai, act_pf = result
        cache[key] = (new_f, ci, ai - base, act_pf)

    def _same_device_rows(self, comp, task: Task, Pc, Dc, Pa, Ua, Ma,
                          uid_a, Da, astart, na):
        """Aggregation inputs of one newcomer's same-device constraint
        check: ``(X, mem, mt_term, ci, ai)`` with the candidate rows
        first and the (candidate, active) pair rows after, or ``None``
        when no active shares a device with any candidate."""
        C = len(Pc)
        A = len(Pa)
        _, mt_vec = self._tables(comp)
        kappa = self.params.superlinear
        R = len(comp.rclass_names)
        u_new = task.usage.get("pu", 1.0)
        mem_new = task.usage.get("mem", 1.0)
        Mc = np.minimum(mem_new, comp.mem_cap[Pc])
        empty = np.zeros(0, dtype=np.int64)

        def segment_pairs(left_ids, left_dev):
            """(li, ri): cross product of each left element with the active
            rows of its device (actives contiguous per device ordinal)."""
            rep = na[left_dev]
            K = int(rep.sum())
            if K == 0:
                return empty, empty
            li = np.repeat(left_ids, rep)
            within = np.arange(K) - np.repeat(np.cumsum(rep) - rep, rep)
            ri = np.repeat(astart[left_dev], rep) + within
            return li, ri

        # --- the new task's factor per candidate --------------------------
        ci, ai = segment_pairs(np.arange(C), Dc)
        if not len(ci):
            return None
        live = uid_a[ai] != task.uid
        Pci, Pai = Pc[ci], Pa[ai]
        same = (Pci == Pai) & live
        r_ca = np.asarray(comp.ncr_rclass[Pci, Pai], dtype=np.int64)
        validc = live & (Pci != Pai) & (r_ca >= 0)
        Xc = np.zeros((C, R))
        np.add.at(Xc, (ci[validc], r_ca[validc]), Ma[ai[validc]])
        mt_c = np.zeros(C)
        np.add.at(mt_c, ci[same], Ua[ai[same]])
        mt_term_c = _pterm_arr(mt_vec[Pc], mt_c, kappa) * u_new

        # --- each same-device active's factor if the task joins -----------
        # base pressures only for actives on candidate devices: the rest
        # never appear in a (candidate, active) pair
        d0 = int(Dc[0])
        if bool((Dc == d0).all()):           # single-device candidate set
            act_sel = np.arange(astart[d0], astart[d0] + na[d0])
        else:
            act_sel = np.nonzero(np.isin(Da, np.unique(Dc)))[0]
        a1, a2 = segment_pairs(act_sel, Da[act_sel])
        diff = uid_a[a1] != uid_a[a2]
        sameP = (Pa[a1] == Pa[a2]) & diff
        r_aa = np.asarray(comp.ncr_rclass[Pa[a1], Pa[a2]], dtype=np.int64)
        valida = diff & (Pa[a1] != Pa[a2]) & (r_aa >= 0)
        Xa = np.zeros((A, R))
        np.add.at(Xa, (a1[valida], r_aa[valida]), Ma[a2[valida]])
        mt_base = np.zeros(A)
        np.add.at(mt_base, a1[sameP], Ua[a2[sameP]])
        Xp = Xa[ai]                            # (K, R): base + join term
        r_ac = np.asarray(comp.ncr_rclass[Pai, Pci], dtype=np.int64)
        jc = live & (Pai != Pci) & (r_ac >= 0)
        kk = np.nonzero(jc)[0]
        Xp[kk, r_ac[kk]] += Mc[ci[kk]]
        mt_p = mt_base[ai] + np.where(same, u_new, 0.0)
        mt_term_p = _pterm_arr(mt_vec[Pai], mt_p, kappa) * Ua[ai]
        # stacked (candidate; pair) rows — the aggregation kernel is
        # elementwise per row, so callers split the result back exactly
        return (np.concatenate([Xc, Xp]),
                np.concatenate([Mc, Ma[ai]]),
                np.concatenate([mt_term_c, mt_term_p]), ci, ai)


class NoSlowdown:
    """Contention-blind model (what ACE-like baselines assume)."""

    def __init__(self, graph: HWGraph, *a, **k) -> None:
        self.graph = graph

    def factor(self, task: Task, pu_name: str,
               coruns: list[tuple[Task, str]]) -> float:
        return 1.0

    def factor_batch(self, pool) -> np.ndarray:
        return np.ones(len(pool))

    def factor_batch_idx(self, P, U, mem, uid) -> np.ndarray:
        return np.ones(len(P))

    def slowdown_matrix(self, pool) -> np.ndarray:
        return np.ones((len(pool), len(pool)))

    def factors_with_candidates(self, task, candidate_pus, active):
        return np.ones(len(candidate_pus)), np.ones((len(candidate_pus),
                                                     len(active)))

    def factors_with_candidates_idx(self, comp, task, Pc, Pa, Ua, Ma, uid_a):
        return np.ones(len(Pc)), np.ones((len(Pc), len(Pa)))

    def factors_same_device(self, comp, task, Pc, Dc, Pa, Ua, Ma, uid_a,
                            Da, astart, na):
        e = np.zeros(0, dtype=np.int64)
        return np.ones(len(Pc)), e, e, np.ones(0)

    def factors_same_device_multi(self, comp, items):
        return [self.factors_same_device(comp, *it) for it in items]

    def invalidate(self) -> None:
        pass
