"""Every file the benchmark names loads, and each cell's driver runs in
process at a small fleet (mult=2) for about a second, correct."""
import json
from pathlib import Path

import pytest

from bench.cell import Cell, find_cell, load_json
from bench.run import cell_metrics, load_reader, run_cell

from .conftest import run_small, small_cell

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


def _later_cell_metrics(traced):
    # a later cell brings its own workload entry and edits none that exists
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "later-x64.replay",
                              "config": "mining-x64", "traffic": "replay",
                              "chips": 1, "why": "a cell added later"})
    return {m["name"] for m in cell_metrics(spec, "later-x64.replay",
                                            traced=traced)}


def test_cell_added_by_entries_alone_reports_end_to_end():
    assert _later_cell_metrics(False) == {"decisions_per_s", "setup_s"}


def test_cell_added_by_entries_alone_reports_every_per_layer_metric():
    got = _later_cell_metrics(True)
    assert got and got == {m["name"] for m in SPEC["per_layer"]}


# mixes kept as data for later cells: each runs through the same generator
KEPT = [("mining-x64", "paced"), ("wireless-x64", "replay"),
        ("mining-x64", "zipf.replay")]
KEPT = [p for p in KEPT
        if p not in {(w["config"], w["traffic"]) for w in SPEC["workloads"]}]


@pytest.mark.parametrize("config,traffic", KEPT)
def test_kept_mix_runs_at_small_fleet(config, traffic, cpu_devices):
    base = small_cell("mining-x64.replay")
    conf = load_json(ROOT / "bench" / "configs" / f"{config}.json")
    conf["fleet"] = base.config["fleet"]
    conf["sensors"] = base.config["sensors"]
    tr = load_json(ROOT / "bench" / "traffic" / f"{traffic}.json")
    cell = Cell(name=f"{config}.{traffic}", config_name=config,
                traffic_name=traffic, chips=1, config=conf, traffic=tr)
    out = run_cell(cell, 23, 1.0, False, cpu_devices, on_event=lambda s: None)
    assert out["correct"], out["checks"]
    if tr["mode"] == "paced":
        rate = tr["decisions_per_s"]
        assert 0.5 * rate <= out["attempted"] <= 1.5 * rate
