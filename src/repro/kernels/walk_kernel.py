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
It counts in int32 and, without ``jax_enable_x64``, keys and overheads
in fp32.
"""
from __future__ import annotations

import os
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


def _jax_reduce_raw():
    import jax.numpy as jnp

    def reduce(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth, lqc):
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

    return reduce


def _jax_reduce():
    import jax

    return jax.jit(_jax_reduce_raw())


def _jax_reduce_batch():
    import jax

    return jax.jit(jax.vmap(_jax_reduce_raw(),
                            in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None)))


_JAX_REDUCE = None
_JAX_REDUCE_BATCH = None
_AUTO_JAX = None                          # memoized auto-mode probe


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
                trace.count("device.h2d", 9)
                w, q, h, ov = _JAX_REDUCE(ok, key, pu_lo, pu_hi, leafcnt,
                                          nchild, hopsum, depth, lqc)
            with trace.span("device.walk_reduce.fetch"):
                trace.count("device.fetch", 4)
                return int(w), int(q), int(h), float(ov)
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
                trace.count("device.h2d", 9)
                w, q, h, ov = _JAX_REDUCE_BATCH(ok, key, pu_lo, pu_hi,
                                                leafcnt, nchild, hopsum,
                                                depth, lqc)
            with trace.span("device.walk_reduce_batch.fetch"):
                trace.count("device.fetch", 4)
                return (np.asarray(w, dtype=np.int64),
                        np.asarray(q, dtype=np.int64),
                        np.asarray(h, dtype=np.int64),
                        np.asarray(ov, dtype=np.float64))
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
