"""The comparison that decides ``correct``.

Three witnesses judge what the run decided.

1. The device answers.  A sample of the calls the window made at the two
   device entries (every eighth, from an offset the seed sets) is
   recomputed by ``bench/reference.py`` in float64 from the inputs the call
   was given: the slowdown factors (``slowdown_rel``, the widest relative
   gap), each scan reduce's winner (``walk_gap``, the widest relative gap
   by which the chosen PU's key lies above the reference's best), its
   integer accounting (``walk_counts``, the number of queries/hops sums
   that differ) and its overhead (``walk_overhead_rel``).
2. The plain reference (``bench/loop_reference.py``), which imports
   nothing of the program, follows every wave of the run, warm-up
   included: the walk's choice (``choice_gap``), the predicted total of
   the chosen PU (``predict_rel``), the charged overhead
   (``overhead_rel``), the admission verdicts (``verdicts``) and the
   ground-truth finish times (``finish_rel``).
3. The host replay.  The same stream is played again, in this process and
   after the window, through the program with its float64 host paths
   selected, through the warm-up and the first half of the window's
   waves.  Every reading it decided must have the same verdict in the
   timed run (``replay_verdicts``) and its tasks the same PUs
   (``replay_placements``); finish times may differ by
   ``replay_finish_rel``.  This one holds the device paths to the
   program's own host paths.

Each number has its limit in ``bench/limits.json``, set from the readings
``PERF.md`` gives.
"""
from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from . import loop_reference, reference
from .cell import load_json

LIMITS = Path(__file__).resolve().parent / "limits.json"


def limits() -> dict:
    return load_json(LIMITS)["limits"]


def _rel(a: float, b: float) -> float:
    if a == b or (math.isnan(a) and math.isnan(b)):
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def _walk_row(ok, key, lo, hi, lc, nc, hs, dp, lqc, got):
    """(winner gap, integer sums that differ, overhead gap) of one scan."""
    w, q, h, ov = (int(got[0]), int(got[1]), int(got[2]), float(got[3]))
    rw, rq, rh, rov = reference.scan_reduce(ok, key, lo, hi, lc, nc, hs, dp,
                                            lqc)
    bad = int(q != rq) + int(h != rh)
    o_rel = _rel(ov, rov)
    if w == rw:
        return 0.0, bad, o_rel
    if w < 0 or rw < 0 or not ok[w]:
        return math.inf, bad, o_rel
    best = float(key[rw])
    return (float(key[w]) - best) / max(abs(best), 1e-300), bad, o_rel


def device_answers(records: dict) -> dict:
    """Widest gaps of the recorded device answers from the float64
    reference."""
    s_rel = 0.0
    for x, beta, mem, mt, kappa, out in records["slowdown_kernel"]:
        ref = reference.slowdown_factors(x, beta, mem, mt, kappa)
        got = np.asarray(out, dtype=np.float64)
        if got.shape != ref.shape or not np.isfinite(got).all():
            s_rel = math.inf
            continue
        s_rel = max(s_rel, float(np.max(np.abs(got - ref) / ref))
                    if len(ref) else 0.0)
    gap = o_rel = 0.0
    counts = 0
    for rec in records["walk_reduce"]:
        g, b, o = _walk_row(*rec[:9], rec[9])
        gap, counts, o_rel = max(gap, g), counts + b, max(o_rel, o)
    for rec in records["walk_reduce_batch"]:
        ok, key, lo, hi, lc, nc, hs, dp, lqc, out = rec
        for i in range(len(ok)):
            g, b, o = _walk_row(ok[i], key[i], lo[i], hi[i], lc[i], nc[i],
                                hs[i], dp[i], lqc,
                                tuple(o[i] for o in out))
            gap, counts, o_rel = max(gap, g), counts + b, max(o_rel, o)
    return {"slowdown_rel": s_rel, "walk_gap": gap, "walk_counts": counts,
            "walk_overhead_rel": o_rel}


def against_reference(cell, stream, loop) -> dict:
    """The run's every wave against the plain reference."""
    return loop_reference.compare(cell.config, stream.churn, loop.trail,
                                  loop.stop_at, loop.engine.finish_of)


def trajectory(timed, replay) -> dict:
    """Verdicts, placements and finish times of every reading the replay
    decided, in the timed loop against the replay."""
    a = {r.rid: r for r in timed.requests}
    verdicts = placements = 0
    f_rel = 0.0
    ea, eb = timed.engine, replay.engine
    for rb in replay.requests:
        if rb.verdict == "pending":
            continue                    # the replay stopped before its retry
        ra = a.get(rb.rid)
        if ra is None or (ra.verdict, ra.reject_reason) != \
                (rb.verdict, rb.reject_reason):
            verdicts += 1
            continue
        if ra.verdict != "accepted":
            continue
        for ta, tb in zip(ra.tasks, rb.tasks):
            if ta.assigned_pu != tb.assigned_pu:
                placements += 1
                continue
            fa, fb = ea.finish_of(ta.uid), eb.finish_of(tb.uid)
            if math.isnan(fa) != math.isnan(fb):
                # finished on one side only: it must lie at or past the
                # clock of the side that stopped first
                f = fb if math.isnan(fa) else fa
                other = ea.time if math.isnan(fa) else eb.time
                f_rel = max(f_rel, 0.0 if f >= other else _rel(f, other))
            elif not math.isnan(fa):
                f_rel = max(f_rel, _rel(fa, fb))
    return {"replay_verdicts": verdicts, "replay_placements": placements,
            "replay_finish_rel": f_rel}


def judge(numbers: dict, lim: dict) -> bool:
    """Every number within its limit (a missing or non-finite number
    fails)."""
    for name, limit in lim.items():
        v = numbers.get(name)
        if v is None or not (v <= limit):
            return False
    return True
