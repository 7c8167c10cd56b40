"""The runs that must come out not correct: the control and the planted
faults.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--what bf16|program|<fault>]

runs the cell once per seed in one process on the chip, with the float64
reference computed in bfloat16 put in the place of the program's two
device entries (``bf16``, the default), the program as it is
(``program``: the sound runs' readings), or the program broken as below
(at a device entry, or in the loop: ``stand_ins["loop"]`` is applied to
the built loop; ``DAG_FAULTS`` break what only a request with
dependencies and pinned stages has), and prints each run's compared
numbers.  The
benchmark's own runs never do this; ``bench/tests/test_control.py`` makes
the same runs on the CPU at a small fleet.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import reference

# the reference in bfloat16, in the program's place
CONTROL = {
    "slowdown_kernel": lambda prog: reference.slowdown_factors_bf16,
    "walk_reduce": lambda prog: reference.scan_reduce_bf16,
    "walk_reduce_batch": lambda prog: reference.scan_reduce_batch_bf16,
}


def _factor_altered(prog):
    """One answer altered where it is produced: the first member's slowdown
    factor of every aggregation, 0.1% high."""
    def call(*args):
        out = np.array(prog(*args), dtype=np.float64)
        if len(out):
            out[0] *= 1.001
        return out
    return call


def _pool_half(prog):
    """Half of the pool left out: only the first half of the members is
    aggregated, the rest reported as unslowed."""
    def call(x, beta, mem, mt, kappa):
        h = (len(x) + 1) // 2
        out = np.ones(len(x))
        out[:h] = prog(x[:h], beta, mem[:h], mt[:h], kappa)
        return out
    return call


def _winner_altered(prog):
    """One answer altered where it is produced: the scan reduce reports the
    feasible PU with the largest key in place of the least."""
    def call(ok, key, *rest):
        w, q, h, ov = prog(ok, key, *rest)
        idx = np.flatnonzero(ok)
        if w >= 0 and len(idx):
            w = int(idx[np.argmax(np.asarray(key)[idx])])
        return w, q, h, ov
    return call


def _batch_half(prog):
    """Half of the batch left out: the batched scan reduce computes its
    first half of the rows and reports the rest as having no feasible PU."""
    def call(ok, key, lo, hi, lc, nc, hs, dp, lqc):
        h = (len(ok) + 1) // 2
        part = prog(ok[:h], key[:h], lo[:h], hi[:h], lc[:h], nc[:h], hs[:h],
                    dp[:h], lqc)
        empty = (-1, 0, 0, 0.0)
        return tuple(np.concatenate([np.asarray(p),
                                     np.full(len(ok) - h, e,
                                             dtype=np.asarray(p).dtype)])
                     for p, e in zip(part, empty))
    return call


def _sync_skipped(loop):
    """A step that returns its state unchanged: the loop's reconciliation
    of the belief ledger with the timeline's completions does nothing, so
    finished tasks stay believed until their estimated finish."""
    loop._sync_completions = lambda: None


def _requests_rebuilt(loop, edit) -> None:
    """Each request the loop builds passes through ``edit`` first."""
    spec = loop.tenants[0]
    inner = spec.make_request
    spec.make_request = lambda k, t: edit(inner(k, t))


def _deps_stripped(loop):
    """A request's dependencies left out: its stages are mapped in one wave
    with their inputs taken at the origin, and run as soon as each is
    released, side by side."""
    from repro.core import TaskGraph

    def edit(g):
        flat = TaskGraph(g.name)
        for t in g:
            flat.add(t)
        return flat
    _requests_rebuilt(loop, edit)


def _return_leg_dropped(loop):
    """The pinned-return leg left out: no stage owes its output back to
    the origin, so a placement off the origin is priced without it."""
    def edit(g):
        for t in g:
            t.attrs.pop("succ_pinned_bytes", None)
        return g
    _requests_rebuilt(loop, edit)


def _fresh_signatures(loop):
    """Not a fault: the witness of one in the program.  Its resident walk
    context memoizes each task's signature, inbound legs included, by the
    task object, so a deferred stage that is retried with its predecessors
    on other devices is priced with its first attempt's legs.  Dropping
    those memos before every wave (pure memos to the program, which drops
    them itself past 8192 entries) prices the retry from its own."""
    session = loop.session
    inner = session.map_pending

    def map_pending(*args, **kw):
        for orc in session.policy.iter_tree():
            ctx = orc._resident_ctx
            if ctx is not None:
                ctx._sigs, ctx._cores, ctx._mkeys = {}, {}, {}
        return inner(*args, **kw)
    session.map_pending = map_pending


def fresh_signatures(stand_ins: dict) -> dict:
    """``stand_ins`` with the program's per-task memos dropped before every
    wave (:func:`_fresh_signatures`), ahead of any loop stand-in."""
    then = stand_ins.get("loop")

    def loop_fn(loop):
        _fresh_signatures(loop)
        if then is not None:
            then(loop)
    return {**stand_ins, "loop": loop_fn}


FAULTS = {
    "factor_altered": {"slowdown_kernel": _factor_altered},
    "pool_half": {"slowdown_kernel": _pool_half},
    "winner_altered": {"walk_reduce": _winner_altered},
    "batch_half": {"walk_reduce_batch": _batch_half},
    "sync_skipped": {"loop": _sync_skipped},
}
# faults of the DAG path, which only a request with dependencies and pinned
# stages can have (a mining reading has neither)
DAG_FAULTS = {
    "deps_stripped": {"loop": _deps_stripped},
    "return_leg_dropped": {"loop": _return_leg_dropped},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--what", default="bf16",
                    choices=["bf16", "program", *sorted(FAULTS),
                             *sorted(DAG_FAULTS)])
    ap.add_argument("--fresh-signatures", action="store_true",
                    help="drop the program's per-task memos before every "
                    "wave (the witness of its stale retry pricing)")
    args = ap.parse_args(argv)
    from . import run
    run.configure_environment()
    from .cell import find_cell
    cell = find_cell(args.workload)
    devices = run.accelerator(cell.chips)
    import repro.kernels  # noqa: F401
    stand_ins = {"bf16": CONTROL, "program": {}, **FAULTS,
                 **DAG_FAULTS}[args.what]
    if args.fresh_signatures:
        stand_ins = fresh_signatures(stand_ins)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, devices,
                           stand_ins=stand_ins)
        print(json.dumps({"what": args.what, "seed": seed,
                          "fresh_signatures": args.fresh_signatures,
                          "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
