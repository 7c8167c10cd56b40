"""The comparison refuses the control (the reference in bfloat16 in the
program's place) and each fault planted under the timed path, in every
cell, at a small fleet on the CPU."""
import json
from pathlib import Path

import pytest

from bench.control import CONTROL, FAULTS

from .conftest import run_small

SPEC = json.loads(
    (Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, cpu_devices):
    out = run_small(name, cpu_devices, seconds=0.5, stand_ins=CONTROL)
    assert not out["correct"]
    c = out["checks"]["slowdown_rel"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, cpu_devices):
    out = run_small(name, cpu_devices, seconds=1.0, stand_ins=FAULTS[fault])
    assert not out["correct"], (fault, out["checks"])
