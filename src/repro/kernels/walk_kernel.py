"""Kernel for the fused Alg. 1 subtree-scan accounting reduce.

The wave-batched orchestrator walk (core/orchestrator.py) lowers each
hierarchical frontier expansion to arrays over a *scan plan* — the
CSR-style preorder of one ORC subtree: per node its subtree PU range
``[pu_lo, pu_hi)``, own leaf count, child count, summed hop cost to its
children and depth below the scan root.  Given the fused constraint
check's ``ok``/``key`` vectors over the plan's PU order, the whole
recursive TraverseChildren replay collapses to one reduce:

    feas[n]  = any(ok[pu_lo[n]:pu_hi[n]])          (alive-subtree mask)
    winner   = argmin(key where ok)                 (first-wins, preorder)
    queries  = sum(leafcnt[feas])
    hops     = sum(nchild[feas])
    overhead = sum(hopsum[feas] + lqc*leafcnt[feas]*(depth[feas]+1))

The closed forms follow from Alg. 1's accounting recursion because a
feasible node's ancestors are feasible by construction (its witness PU
sits in every enclosing subtree range).  ``queries``/``hops`` are exact
integer sums; ``overhead`` may differ from the Python oracle's nested
accumulation order by float-associativity ulps (tests pin it at 1e-9,
and the pu/score decisions never read it).

Dispatch mirrors the other kernels: the numpy reference is the oracle
and the CPU path; ``REPRO_WALK_KERNEL`` selects ``ref`` | ``jax`` |
``auto`` (auto takes the jitted path whenever ``jax.default_backend()``
is not ``cpu`` — for a reduce this size, XLA on CPU would lose to numpy
on dispatch overhead alone).  The jax path is jitted over the static
plan shapes, so repeated scans of one plan reuse the compiled reduce.
It counts in int32 and computes keys and overheads in fp32.

A device call costs per value handed over and per blocking read, not
per operation, so each jax call hands over one array and reads one
back.  The plan's six columns and ``lqc`` are packed into one int32
array and kept on the device, keyed by their content (a bounded LRU,
shared by every plan with equal columns; a plan rebuilt with new values
misses, never reads stale constants); a call uploads only ``ok`` and
``key``, packed in fp32, and reads ``(winner, queries, hops, overhead's
fp32 bits)`` back as one int32 vector (``(rows, 4)`` batched).  All
device arithmetic is inside the two jitted programs, so a new plan of a
shape already seen compiles nothing.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Tuple

import numpy as np

from ..core import trace

__all__ = ["scan_reduce", "scan_reduce_batch", "scan_reduce_ref"]


def scan_reduce_ref(ok: np.ndarray, key: np.ndarray, pu_lo: np.ndarray,
                    pu_hi: np.ndarray, leafcnt: np.ndarray,
                    nchild: np.ndarray, hopsum: np.ndarray,
                    depth: np.ndarray, lqc: float,
                    ) -> Tuple[int, int, int, float]:
    """Numpy reference: (winner_pos, queries, hops, overhead).

    ``winner_pos`` is -1 when no PU in the scan is feasible (the scan
    root returns None); ties on ``key`` resolve to the first feasible
    position in plan (preorder) order, matching ``min()`` first-wins."""
    if len(ok) < 128:
        # scalar path: device-level scans are a handful of PUs, where
        # per-call numpy dispatch dwarfs the math.  Bit-identical to the
        # array path — numpy's pairwise summation is sequential below its
        # 128-element block size, so the Python running sums accumulate
        # in the same order
        okl = ok.tolist()
        keyl = key.tolist()
        if not any(okl[int(pu_lo[0]):int(pu_hi[0])]):
            return -1, 0, 0, 0.0
        w = -1
        best = 0.0
        for i, o in enumerate(okl):
            if o and (w < 0 or keyl[i] < best):
                w = i
                best = keyl[i]
        queries = 0
        hops = 0
        overhead = 0.0
        lol = pu_lo.tolist()
        hil = pu_hi.tolist()
        lcl = leafcnt.tolist()
        ncl = nchild.tolist()
        hsl = hopsum.tolist()
        dpl = depth.tolist()
        for nidx in range(len(lol)):
            lo, hi = lol[nidx], hil[nidx]
            if not any(okl[lo:hi]):
                continue
            queries += lcl[nidx]
            hops += ncl[nidx]
            overhead += hsl[nidx] + lqc * lcl[nidx] * (dpl[nidx] + 1.0)
        return w, queries, hops, overhead
    cs = np.zeros(len(ok) + 1, dtype=np.int64)
    np.cumsum(ok, out=cs[1:])
    feas = cs[pu_hi] > cs[pu_lo]
    if not feas[0]:
        return -1, 0, 0, 0.0
    # argmin over feasible rows only: with no deadline every feasible key
    # may be inf (unroutable comm), and the winner must still be feasible
    ok_idx = np.flatnonzero(ok)
    w = int(ok_idx[np.argmin(key[ok_idx])])
    queries = int(leafcnt[feas].sum())
    hops = int(nchild[feas].sum())
    overhead = float((hopsum[feas]
                      + lqc * leafcnt[feas] * (depth[feas] + 1.0)).sum())
    return w, queries, hops, overhead


def _scan_math():
    """The reduce over its nine operands in jnp; traced only inside the
    jitted programs."""
    import jax.numpy as jnp

    def scan(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth, lqc):
        cs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(ok.astype(jnp.int32))])
        feas = cs[pu_hi] > cs[pu_lo]
        # first feasible index attaining the feasible-row minimum (inf-safe)
        masked = jnp.where(ok, key, jnp.inf)
        kmin = jnp.min(masked)
        w = jnp.where(feas[0],
                      jnp.argmax(ok & ((masked == kmin) | ~jnp.isfinite(kmin))),
                      -1)
        queries = jnp.sum(jnp.where(feas, leafcnt, 0))
        hops = jnp.sum(jnp.where(feas, nchild, 0))
        overhead = jnp.sum(jnp.where(
            feas, hopsum + lqc * leafcnt * (depth + 1.0), 0.0))
        return w, queries, hops, overhead

    return scan


def _jax_reduce_raw():
    import jax.numpy as jnp
    from jax import lax

    scan = _scan_math()

    def reduce(data, plan):
        """``data`` is :func:`_pack_scans`'s ``(2, n)``, ``plan``
        :func:`_pack_plan`'s ``(6m + 1,)``; returns ``(winner, queries,
        hops, overhead's fp32 bits)`` as one int32 vector."""
        m = (plan.shape[-1] - 1) // 6
        f32 = lax.bitcast_convert_type(plan[4 * m:], jnp.float32)
        w, q, h, ov = scan(data[0] != 0, data[1], plan[:m], plan[m:2 * m],
                           plan[2 * m:3 * m], plan[3 * m:4 * m], f32[:m],
                           f32[m:2 * m], f32[2 * m])
        return jnp.stack([w.astype(jnp.int32), q.astype(jnp.int32),
                          h.astype(jnp.int32),
                          lax.bitcast_convert_type(ov.astype(jnp.float32),
                                                   jnp.int32)])

    return reduce


def _jax_reduce():
    import jax

    return jax.jit(_jax_reduce_raw())


def _jax_reduce_batch():
    import jax

    return jax.jit(jax.vmap(_jax_reduce_raw()))


def _pack_scans(ok, key) -> np.ndarray:
    """The per-call operands in one fp32 array, ``(..., 2, n)``: ``ok`` as
    0/1, then the keys rounded to fp32."""
    ok = np.asarray(ok)
    data = np.empty(ok.shape[:-1] + (2, ok.shape[-1]), dtype=np.float32)
    data[..., 0, :] = ok
    data[..., 1, :] = key
    return data


def _pack_plan(pu_lo, pu_hi, leafcnt, nchild, hopsum, depth,
               lqc: float) -> np.ndarray:
    """The plan constants in one int32 array, ``(..., 6m + 1)``:
    ``pu_lo``, ``pu_hi``, ``leafcnt``, ``nchild``, then the fp32 bits of
    ``hopsum``, ``depth`` and ``lqc``."""
    m = np.shape(pu_lo)[-1]
    out = np.empty(np.shape(pu_lo)[:-1] + (6 * m + 1,), dtype=np.int32)
    for i, c in enumerate((pu_lo, pu_hi, leafcnt, nchild)):
        out[..., i * m:(i + 1) * m] = c
    f32 = out[..., 4 * m:].view(np.float32)
    f32[..., :m] = hopsum
    f32[..., m:2 * m] = depth
    f32[..., 2 * m] = lqc
    return out


class _ResidentPlans:
    """Device-resident packed plan constants, keyed by their content (the
    bytes, dtype and shape of each column, and ``lqc``), least recently
    used first out.  A rebuilt plan with new values finds nothing stale,
    and plans with equal columns share one copy."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self._dev: OrderedDict = OrderedDict()
        self._lock = threading.Lock()     # the sharded walk's group threads

    def get(self, cols, lqc: float):
        cols = [np.asarray(c) for c in cols]
        key = (float(lqc),) + tuple((c.dtype.str, c.shape, c.tobytes())
                                    for c in cols)
        with self._lock:
            dev = self._dev.get(key)
            if dev is not None:
                self._dev.move_to_end(key)
        if dev is not None:
            trace.count("cache.plan_dev.hit")
            return dev
        import jax
        trace.count("cache.plan_dev.miss")
        trace.count("device.h2d")
        dev = jax.device_put(_pack_plan(*cols, lqc))
        with self._lock:
            self._dev[key] = dev
            while len(self._dev) > self.capacity:
                self._dev.popitem(last=False)
        return dev


_JAX_REDUCE = None
_JAX_REDUCE_BATCH = None
_AUTO_JAX = None                          # memoized auto-mode probe
# well above the distinct plans and batched stacks of a fleet's walk
_PLANS = _ResidentPlans(4096)


def _use_jax() -> bool:
    mode = os.environ.get("REPRO_WALK_KERNEL", "auto")
    if mode == "ref":
        return False
    if mode == "jax":
        return True
    # the backend cannot change mid-process: probe jax once, then the
    # auto path costs one env read per call
    global _AUTO_JAX
    if _AUTO_JAX is None:
        import jax
        _AUTO_JAX = jax.default_backend() != "cpu"
    return _AUTO_JAX


def scan_reduce(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth,
                lqc: float) -> Tuple[int, int, int, float]:
    """Dispatching entry: numpy ref on CPU, jitted reduce on accelerators
    (or when forced via ``REPRO_WALK_KERNEL=jax``)."""
    if _use_jax():
        global _JAX_REDUCE
        if _JAX_REDUCE is None:
            _JAX_REDUCE = _jax_reduce()
        with trace.span("device.walk_reduce"):
            with trace.span("device.walk_reduce.call"):
                plan = _PLANS.get((pu_lo, pu_hi, leafcnt, nchild, hopsum,
                                   depth), lqc)
                trace.count("device.h2d")
                out = _JAX_REDUCE(_pack_scans(ok, key), plan)
            with trace.span("device.walk_reduce.fetch"):
                trace.count("device.fetch")
                a = np.asarray(out)
                return (int(a[0]), int(a[1]), int(a[2]),
                        float(a.view(np.float32)[3]))
    return scan_reduce_ref(ok, key, pu_lo, pu_hi, leafcnt, nchild,
                           hopsum, depth, lqc)


def scan_reduce_batch(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum,
                      depth, lqc: float,
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Reduce a stack of same-shape scans in one call.

    All array inputs are 2-D with one scan per row (``ok``/``key`` over
    each row's plan PU order, the remaining five over its plan nodes);
    ``lqc`` is shared.  Returns per-row ``(winners, queries, hops,
    overheads)`` arrays; ``winners[i] == -1`` marks an infeasible row.

    The numpy path loops :func:`scan_reduce_ref` per row — bit-identical
    to the unbatched calls by construction.  The jax path vmaps the
    jitted reduce over the stack (one fused dispatch for the whole
    group of scans), used where the sharded walk driver stacks
    same-shape group slices."""
    if _use_jax():
        global _JAX_REDUCE_BATCH
        if _JAX_REDUCE_BATCH is None:
            _JAX_REDUCE_BATCH = _jax_reduce_batch()
        with trace.span("device.walk_reduce_batch"):
            with trace.span("device.walk_reduce_batch.call"):
                plan = _PLANS.get((pu_lo, pu_hi, leafcnt, nchild, hopsum,
                                   depth), lqc)
                trace.count("device.h2d")
                out = _JAX_REDUCE_BATCH(_pack_scans(ok, key), plan)
            with trace.span("device.walk_reduce_batch.fetch"):
                trace.count("device.fetch")
                a = np.asarray(out)
                return (a[:, 0].astype(np.int64), a[:, 1].astype(np.int64),
                        a[:, 2].astype(np.int64),
                        a.view(np.float32)[:, 3].astype(np.float64))
    n = len(ok)
    winners = np.empty(n, dtype=np.int64)
    queries = np.empty(n, dtype=np.int64)
    hops = np.empty(n, dtype=np.int64)
    overheads = np.empty(n, dtype=np.float64)
    for i in range(n):
        winners[i], queries[i], hops[i], overheads[i] = scan_reduce_ref(
            ok[i], key[i], pu_lo[i], pu_hi[i], leafcnt[i], nchild[i],
            hopsum[i], depth[i], lqc)
    return winners, queries, hops, overheads
