"""Every file the benchmark names loads, and each cell's driver runs in
process at a small fleet (mult=2) for about a second, correct."""
import json
from pathlib import Path

import pytest

from bench.cell import find_cell, load_json
from bench.control import fresh_signatures
from bench.run import cell_metrics, load_reader, run_cell

from .conftest import kept_cell, run_small, shrink

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_every_file_loads():
    for c in SPEC["configs"]:
        conf = load_json(ROOT / c["file"])
        assert conf["source"] == c["source"]
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        cell = find_cell(w["name"])
        assert cell.traffic["mode"] in ("replay", "paced")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(load_reader(m["name"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_small_fleet(name, cpu_devices):
    out = run_small(name, cpu_devices)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    want = {m["name"] for m in cell_metrics(SPEC, name, traced=False)}
    assert set(out["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    assert list(out)[-1] == "checks"


VR_CONFIG = {"name": "vr-x64", "source": "https://arxiv.org/abs/2402.04522",
             "file": "bench/configs/vr-x64.json", "reduced": [],
             "why": "cloud-rendered VR frames on the Fig. 11(a) testbed"}
VR_CELL = {"name": "vr-x64.headsets.replay", "config": "vr-x64",
           "traffic": "headsets.replay", "chips": 1,
           "why": "a cell added later"}


def _later_spec():
    # later cells bring their own configuration and workload entries and
    # edit none that exists
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "later-x64.replay",
                              "config": "mining-x64", "traffic": "replay",
                              "chips": 1, "why": "a cell added later"})
    spec["configs"].append(VR_CONFIG)
    spec["workloads"].append(VR_CELL)
    return spec


def _later_cell_metrics(name, traced):
    return {m["name"] for m in cell_metrics(_later_spec(), name,
                                            traced=traced)}


LATER = ("later-x64.replay", VR_CELL["name"])


def test_cell_added_by_entries_alone_reports_end_to_end():
    for name in LATER:
        assert _later_cell_metrics(name, False) == {"decisions_per_s",
                                                    "setup_s"}


def test_cell_added_by_entries_alone_reports_every_per_layer_metric():
    for name in LATER:
        got = _later_cell_metrics(name, True)
        assert got and got == {m["name"] for m in SPEC["per_layer"]}


def _vr_by_entries_alone(tmp_path):
    # a copy of BENCHMARK.json with the VR cell's two entries, over the
    # bench files as they are
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(_later_spec()))
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    cell = find_cell(VR_CELL["name"], root=tmp_path)
    shrink(cell.config)
    return cell


def test_cell_added_by_entries_alone_runs_correct(tmp_path, cpu_devices):
    """The VR cell by its two entries alone, correct once the program's
    stale per-task memos are dropped before every wave (the fault that
    keeps the cell out: next test)."""
    out = run_cell(_vr_by_entries_alone(tmp_path), 29, 1.0, False,
                   cpu_devices, stand_ins=fresh_signatures({}),
                   on_event=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == _later_cell_metrics(VR_CELL["name"],
                                                      False)


def test_vr_cell_is_refused_for_the_programs_stale_retry_pricing(
        tmp_path, cpu_devices):
    """As the program runs it, the VR cell is not correct: a deferred
    frame's retried stages are priced with their first attempt's inbound
    legs (``control._fresh_signatures``).  The reference's walk, verdicts
    and timeline agree; only the retries' predictions and choices differ."""
    out = run_cell(_vr_by_entries_alone(tmp_path), 29, 1.0, False,
                   cpu_devices, on_event=lambda s: None)
    c = {k: v["value"] for k, v in out["checks"].items()}
    lim = {k: v["limit"] for k, v in out["checks"].items()}
    assert not out["correct"]
    assert {k for k in c if c[k] > lim[k]} == {
        "choice_gap", "predict_rel", "overhead_rel"}, c


# mixes kept as data for later cells: each runs through the same generator
KEPT = [("mining-x64", "paced"), ("wireless-x64", "replay"),
        ("mining-x64", "zipf.replay")]
KEPT = [p for p in KEPT
        if p not in {(w["config"], w["traffic"]) for w in SPEC["workloads"]}]


@pytest.mark.parametrize("config,traffic", KEPT)
def test_kept_mix_runs_at_small_fleet(config, traffic, cpu_devices):
    cell = kept_cell(config, traffic)
    out = run_cell(cell, 23, 1.0, False, cpu_devices, on_event=lambda s: None)
    assert out["correct"], out["checks"]
    tr = cell.traffic
    if tr["mode"] == "paced":
        rate = tr["decisions_per_s"]
        assert 0.5 * rate <= out["attempted"] <= 1.5 * rate
