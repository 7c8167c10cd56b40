"""A cell cut to a small fleet for in-process runs on the CPU."""
import pytest

from bench import yardstick
from bench.cell import find_cell


def small_cell(name: str, mult: int = 2):
    cell = find_cell(name)
    ec, sc = yardstick.mining_counts(mult)
    cell.config["fleet"]["edges"] = ec
    cell.config["fleet"]["servers"] = sc
    cell.config["sensors"] = 12 * mult
    return cell


@pytest.fixture(scope="module")
def cpu_devices():
    import jax
    return jax.devices("cpu")[:1]


def run_small(name, devices, seed=17, seconds=1.0, stand_ins=None):
    from bench.run import run_cell
    return run_cell(small_cell(name), seed, seconds, False, devices,
                    stand_ins=stand_ins, on_event=lambda s: None)
