"""Plain float64 references of the two computations the scheduler sends to
the device, and their bfloat16 controls.

* The slowdown factor aggregation (``kernels/slowdown_kernel.py``): for each
  pool member ``i`` with per-resource-class pressures ``x[i, r]``,
  ``factor[i] = max(1, (1 + mt[i]) * prod_r(1 + beta[r] x[i,r] (1 + kappa
  x[i,r]) mem[i]))``, the term taken as 0 where ``x`` or ``beta`` is not
  positive (paper section 3.4).
* The scan reduce of the Alg. 1 walk (``kernels/walk_kernel.py``): over one
  orchestrator subtree laid out in preorder, a node is feasible when any PU
  in its range ``[pu_lo, pu_hi)`` is; the winner is the first feasible PU
  with the least key; queries, hops and overhead sum the accounting of the
  feasible nodes.

Written from those definitions, with no import of the program.  The
bfloat16 versions round every operand and intermediate to bfloat16: they
are the control that the comparison has to refuse.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def slowdown_factors(x, beta, mem, mt, kappa: float, dtype=np.float64):
    x = np.asarray(x, dtype=dtype)
    beta = np.asarray(beta, dtype=dtype)[None, :]
    mem = np.asarray(mem, dtype=dtype)
    mt = np.asarray(mt, dtype=dtype)
    one = dtype(1.0)
    k = dtype(kappa)
    term = np.where((x > 0) & (beta > 0), beta * x * (one + k * x),
                    dtype(0.0)).astype(dtype)
    g = (one + term * mem[:, None]).astype(dtype)
    prod = np.ones(len(x), dtype=dtype)
    for r in range(g.shape[1]):
        prod = (prod * g[:, r]).astype(dtype)
    return np.maximum(one, ((one + mt) * prod).astype(dtype))


def scan_reduce(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth,
                lqc: float, dtype=np.float64):
    """``(winner, queries, hops, overhead)`` of one scan; winner -1 when no
    PU is feasible."""
    ok = np.asarray(ok, dtype=bool)
    key = np.asarray(key, dtype=np.float64).astype(dtype)
    lo = np.asarray(pu_lo, dtype=np.int64)
    hi = np.asarray(pu_hi, dtype=np.int64)
    cs = np.concatenate([[0], np.cumsum(ok, dtype=np.int64)])
    feas = cs[hi] > cs[lo]
    if not len(feas) or not feas[0]:
        return -1, 0, 0, 0.0
    idx = np.flatnonzero(ok)
    w = int(idx[np.argmin(key[idx])])
    queries = int(np.asarray(leafcnt, dtype=np.int64)[feas].sum())
    hops = int(np.asarray(nchild, dtype=np.int64)[feas].sum())
    hs = np.asarray(hopsum, dtype=np.float64).astype(dtype)[feas]
    lc = np.asarray(leafcnt, dtype=np.float64).astype(dtype)[feas]
    dp = np.asarray(depth, dtype=np.float64).astype(dtype)[feas]
    terms = (hs + dtype(lqc) * lc * (dp + dtype(1.0))).astype(dtype)
    overhead = dtype(0.0)
    for v in terms:
        overhead = dtype(overhead + v)
    return w, queries, hops, float(overhead)


def scan_reduce_batch(ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum, depth,
                      lqc: float, dtype=np.float64):
    rows = [scan_reduce(*(a[i] for a in (ok, key, pu_lo, pu_hi, leafcnt,
                                         nchild, hopsum, depth)), lqc,
                        dtype=dtype) for i in range(len(ok))]
    return tuple(np.array([r[j] for r in rows],
                          dtype=np.float64 if j == 3 else np.int64)
                 for j in range(4))


# the controls, in the signature of the program's entries
def slowdown_factors_bf16(x, beta, mem, mt, kappa):
    return slowdown_factors(x, beta, mem, mt, kappa,
                            dtype=BF16).astype(np.float64)


def scan_reduce_bf16(*args):
    return scan_reduce(*args, dtype=BF16)


def scan_reduce_batch_bf16(*args):
    return scan_reduce_batch(*args, dtype=BF16)
