"""The mining stream and the decisions the program makes on it, for one
seed at a small fleet (mult=2, seed 17), pinned: a change to how the
harness builds requests or draws traffic leaves both as they were."""
import hashlib
import json
from pathlib import Path

import numpy as np

from bench.cell import WindowPlan, build_loop, drive, make_stream

from .conftest import small_cell

GOLDEN = Path(__file__).with_name("golden_mining_x2_seed17.json")
WAVES = 48


def _sig(x: float) -> str:
    return f"{x:.12g}"


def record(seed: int = 17) -> dict:
    """Arrivals and origins of the stream, and the first ``WAVES`` waves
    the program decides on it (host paths; uids counted from the first)."""
    cell = small_cell("mining-x64.replay")
    stream = make_stream(cell, seed, 1.0)
    plan = WindowPlan(warm_until=float("inf"), seconds=1.0, paced_rate=None,
                      sim_rate=stream.sim_rate, stop_after=WAVES)
    loop = build_loop(cell, stream, plan)
    drive(loop)
    uid0 = loop.trail[0].readings[0].tasks[0][0]
    trail = [[_sig(w.now),
              [[r.rid, r.origin, r.defers, r.verdict,
                [[u - uid0, k, pu, _sig(tot), _sig(ov)]
                 for u, k, pu, tot, ov in r.tasks]]
               for r in w.readings]]
             for w in loop.trail]
    arr = np.ascontiguousarray(stream.arrivals, dtype=np.float64)
    return {"arrivals": len(arr),
            "arrivals_sha256": hashlib.sha256(arr.tobytes()).hexdigest(),
            "first_arrivals": [_sig(x) for x in arr[:8].tolist()],
            "origins_sha256": hashlib.sha256(
                "\n".join(stream.origins).encode()).hexdigest(),
            "first_origins": stream.origins[:8],
            "trail": trail}


def test_mining_stream_and_trail_match_the_golden():
    want = json.loads(GOLDEN.read_text())
    got = json.loads(json.dumps(record()))
    for key in ("arrivals", "arrivals_sha256", "first_arrivals",
                "origins_sha256", "first_origins"):
        assert got[key] == want[key], key
    assert len(got["trail"]) == len(want["trail"]) == WAVES
    for k, (a, b) in enumerate(zip(got["trail"], want["trail"])):
        assert a == b, k
