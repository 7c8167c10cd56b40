"""Operations and bytes of the two device computations, counted from the
real (unpadded) inputs at the program's entry, and the roofline share they
give against the chip's peaks (``bench/peaks.json``).

The counts are of the work the algorithm needs, whatever implements it:
padding, launch and transfer are not work, so a share below 100% is what
they and any idle time cost.
"""
from __future__ import annotations

from pathlib import Path

from .cell import load_json

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def slowdown_work(n_members: int, n_rclasses: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one factor aggregation over ``n_members``
    pool members and ``n_rclasses`` resource classes: per member and class
    the pressure term (two compares, a select, three multiplies, an add)
    and its factor (a multiply, an add, the running product's multiply);
    per member the tenancy term, the product with it and the floor.  Reads
    the pressures, the per-class betas, the memory and tenancy columns in
    fp32, writes one factor per member."""
    ops = n_members * (9.0 * n_rclasses + 3.0)
    nbytes = 4.0 * (n_members * n_rclasses + n_rclasses + 3 * n_members)
    return ops, nbytes


def walk_work(n_pus: int, n_nodes: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one scan reduce over ``n_pus`` PUs and
    ``n_nodes`` plan nodes: the prefix count, the masked minimum and the
    first-winner search over the PUs; per node the feasibility compare and
    the three masked sums with the overhead term.  Reads the mask (one byte
    per PU), the keys and six int32/fp32 node columns, writes four
    scalars."""
    ops = 5.0 * n_pus + 12.0 * n_nodes
    nbytes = 5.0 * n_pus + 24.0 * n_nodes + 16.0
    return ops, nbytes


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's row for ``device_kind``; a device the table does
    not hold is an error, never a default."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]


def share_pct(ops: float, nbytes: float, device_s: float,
              device_kind: str) -> tuple[float, str]:
    """Roofline share in percent: the least time the chip could take
    (operations over peak FLOP/s or bytes over peak bytes/s, whichever is
    larger) over the measured device time, and which of the two bounds
    it."""
    if device_s <= 0:
        raise ValueError("no device time to share against")
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / device_s, bound
