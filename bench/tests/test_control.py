"""The comparison refuses the control (the reference in bfloat16 in the
program's place) and each fault planted under the timed path, in every
cell and on the kept VR mix (whose frames alone can have the DAG path's
faults), at a small fleet on the CPU."""
import json
from pathlib import Path

import pytest

from bench.control import CONTROL, DAG_FAULTS, FAULTS, fresh_signatures
from bench.run import run_cell

from .conftest import kept_cell, run_small

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


# the number each stand-in on the VR mix must at least fail
DAG_CAUGHT_BY = {"bf16": ("slowdown_rel",),
                 "deps_stripped": ("finish_rel",),
                 "return_leg_dropped": ("predict_rel", "choice_gap")}


@pytest.mark.parametrize("what", sorted(DAG_CAUGHT_BY))
def test_control_and_dag_faults_are_not_correct_on_vr(what, cpu_devices):
    # over the program with its stale retry pricing bypassed, which alone
    # keeps the mix from reading correct (test_cells.py)
    stand_ins = fresh_signatures(CONTROL if what == "bf16"
                                 else DAG_FAULTS[what])
    out = run_cell(kept_cell("vr-x64", "headsets.replay"), 17, 1.0, False,
                   cpu_devices, stand_ins=stand_ins, on_event=lambda s: None)
    assert not out["correct"]
    c = out["checks"]
    assert any(c[k]["value"] > c[k]["limit"] for k in DAG_CAUGHT_BY[what]), c
