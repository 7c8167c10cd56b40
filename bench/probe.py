"""Counters and recorders at the program's two device entries.

The program selects each implementation once per process and keeps it in a
module-level slot: ``core.slowdown._AGGREGATE`` (the Pallas kernel on a
TPU) and ``core.orchestrator._SCAN_REDUCE`` / ``_SCAN_REDUCE_BATCH`` (the
walk's scan reduce, jitted on an accelerator).  :class:`Probe` puts a
wrapper in each slot that counts calls, counts the work of each call from
its inputs (``bench/roofline.py``), and, while the window is open, keeps a
copy of the inputs and answers of every ``SAMPLE``-th call of each entry
(from an offset the run's seed sets) for the comparison with
``bench/reference.py``.  ``impl`` holds what the wrapper calls underneath:
the program's own selection, or the stand-in that ``stand_ins[entry]``
makes of it (the bfloat16 control, a planted fault), which only the
control and the tests give.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from . import roofline

ENTRIES = ("slowdown_kernel", "walk_reduce", "walk_reduce_batch")
SAMPLE = 8


class Probe:
    def __init__(self, stand_ins: dict = None, offset: int = 0) -> None:
        self.stand_ins = dict(stand_ins or {})
        self.offset = abs(int(offset)) % SAMPLE
        self.recording = False
        self.calls: Counter = Counter()
        self.work: dict = {"slowdown_kernel": [0.0, 0.0],
                           "walk_reduce": [0.0, 0.0]}
        self.records: dict = {k: [] for k in ENTRIES}
        self.impl: dict = {}
        self._saved = None

    # -- installation ---------------------------------------------------
    def install(self) -> None:
        from repro.core import orchestrator, slowdown
        self._saved = (slowdown._AGGREGATE, orchestrator._SCAN_REDUCE,
                       orchestrator._SCAN_REDUCE_BATCH)
        selected = (slowdown._AGGREGATE or slowdown._select_aggregate(),
                    orchestrator._scan_reduce_kernel(),
                    orchestrator._scan_reduce_batch_kernel())
        for k, fn in zip(ENTRIES, selected):
            make = self.stand_ins.get(k)
            self.impl[k] = fn if make is None else make(fn)
        slowdown._AGGREGATE = self._aggregate
        orchestrator._SCAN_REDUCE = self._reduce
        orchestrator._SCAN_REDUCE_BATCH = self._reduce_batch

    def uninstall(self) -> None:
        from repro.core import orchestrator, slowdown
        if self._saved is not None:
            (slowdown._AGGREGATE, orchestrator._SCAN_REDUCE,
             orchestrator._SCAN_REDUCE_BATCH) = self._saved
            self._saved = None

    def start(self) -> None:
        self.recording = True
        self.calls.clear()
        for w in self.work.values():
            w[0] = w[1] = 0.0
        for v in self.records.values():
            v.clear()

    def stop(self) -> None:
        self.recording = False

    def _keep(self, entry: str) -> bool:
        """Count one call of ``entry``; True when it is one to copy."""
        n = self.calls[entry]
        self.calls[entry] = n + 1
        return n % SAMPLE == self.offset

    # -- the wrappers ----------------------------------------------------
    def _aggregate(self, x, beta, mem, mt_term, kappa):
        out = self.impl["slowdown_kernel"](x, beta, mem, mt_term, kappa)
        if self.recording:
            n, r = np.shape(x)
            ops, nb = roofline.slowdown_work(n, r)
            self.work["slowdown_kernel"][0] += ops
            self.work["slowdown_kernel"][1] += nb
            if self._keep("slowdown_kernel"):
                self.records["slowdown_kernel"].append(
                    (np.array(x), np.array(beta), np.array(mem),
                     np.array(mt_term), float(kappa), np.array(out)))
        return out

    def _reduce(self, ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum,
                depth, lqc):
        out = self.impl["walk_reduce"](ok, key, pu_lo, pu_hi, leafcnt,
                                       nchild, hopsum, depth, lqc)
        if self.recording:
            ops, nb = roofline.walk_work(len(ok), len(pu_lo))
            self.work["walk_reduce"][0] += ops
            self.work["walk_reduce"][1] += nb
            if self._keep("walk_reduce"):
                self.records["walk_reduce"].append(
                    (np.array(ok), np.array(key), pu_lo, pu_hi, leafcnt,
                     nchild, hopsum, depth, float(lqc), tuple(out)))
        return out

    def _reduce_batch(self, ok, key, pu_lo, pu_hi, leafcnt, nchild, hopsum,
                      depth, lqc):
        out = self.impl["walk_reduce_batch"](ok, key, pu_lo, pu_hi, leafcnt,
                                             nchild, hopsum, depth, lqc)
        if self.recording:
            rows, n = np.shape(ok)
            ops, nb = roofline.walk_work(n, np.shape(pu_lo)[1])
            self.work["walk_reduce"][0] += rows * ops
            self.work["walk_reduce"][1] += rows * nb
            if self._keep("walk_reduce_batch"):
                self.records["walk_reduce_batch"].append(
                    (np.array(ok), np.array(key), np.array(pu_lo),
                     np.array(pu_hi), np.array(leafcnt), np.array(nchild),
                     np.array(hopsum), np.array(depth), float(lqc),
                     tuple(np.array(o) for o in out)))
        return out
