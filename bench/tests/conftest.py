"""A cell cut to a small fleet for in-process runs on the CPU."""
from pathlib import Path

import pytest

from bench.cell import Cell, find_cell, load_json

BENCH = Path(__file__).resolve().parents[1]


def shrink(conf: dict, mult: int = 2) -> dict:
    """The configuration's fleet at ``mult`` where it states ``mult=64``
    (every device count scaled by ``mult / conf["mult"]``: the mining
    fleet's ``mining_counts(mult)``, the VR field testbed times ``mult``),
    with 12 sensors per unit of ``mult`` where it has sensors."""
    scale = mult / conf["mult"]
    fl = conf["fleet"]
    for group in ("edges", "servers"):
        fl[group] = {k: int(n * scale) for k, n in fl[group].items()}
    if "sensors" in conf:
        conf["sensors"] = 12 * mult
    return conf


def small_cell(name: str, mult: int = 2):
    cell = find_cell(name)
    shrink(cell.config, mult)
    return cell


def kept_cell(config: str, traffic: str, mult: int = 2) -> Cell:
    """A configuration and a traffic mix that ``BENCHMARK.json`` need not
    name, as one cell at a small fleet."""
    conf = shrink(load_json(BENCH / "configs" / f"{config}.json"), mult)
    tr = load_json(BENCH / "traffic" / f"{traffic}.json")
    return Cell(name=f"{config}.{traffic}", config_name=config,
                traffic_name=traffic, chips=1, config=conf, traffic=tr)


@pytest.fixture(scope="module")
def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]


def run_small(name, devices, seed=17, seconds=1.0, stand_ins=None):
    from bench.run import run_cell
    return run_cell(small_cell(name), seed, seconds, False, devices,
                    stand_ins=stand_ins, on_event=lambda s: None)
