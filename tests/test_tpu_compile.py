"""Compile the main path's device kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip's compiler
would refuse (interpret mode cannot show this).  The topology is
described only inside a fixture, so collecting this file never loads the
TPU library; the persistent compile cache is off around these compiles,
whose entries could not be read back without a chip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import slowdown_kernel, timeline_kernel, walk_kernel

# scan-plan widths of the mult=128 mining fleet: a device ORC's scan
# (6 PUs, 1 node), the root's whole-fleet plan (8448 PUs, 1667 nodes),
# and the entry-scan stack the walk phase reduces in one batched call
_DEVICE_SCAN = (6, 1)
_FLEET_SCAN = (8448, 1667)
_BATCH_ROWS = 1152


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("rows", [8, 300, 2160])
def test_slowdown_kernel_compiles(one_chip, rows):
    nb = slowdown_kernel.bucket(rows)
    f32 = jnp.float32
    compiled = slowdown_kernel.factors_call.lower(
        _spec(one_chip, (8, nb), f32), _spec(one_chip, (8, 1), f32),
        _spec(one_chip, (1, nb), f32), _spec(one_chip, (1, nb), f32),
        kappa=0.12, n_r=6, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rate_advance_compiles(one_chip):
    # 4608 jobs: 36 rows of 128 lanes, padded to 40 rows of 8-row blocks
    call = functools.partial(timeline_kernel.rate_advance_call, now=0.5,
                             bn=8, interpret=False)
    spec = _spec(one_chip, (40, 128), jnp.float32)
    compiled = jax.jit(call).lower(spec, spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_min_compiles(one_chip):
    call = functools.partial(timeline_kernel.segment_min_call, bs=256,
                             interpret=False)
    compiled = jax.jit(call).lower(
        _spec(one_chip, (512, 128), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _reduce_specs(one_chip, n_pus, n_nodes, batch=()):
    # the packed operands: ok and keys in fp32, the plan constants in int32
    return (_spec(one_chip, batch + (2, n_pus), jnp.float32),
            _spec(one_chip, batch + (6 * n_nodes + 1,), jnp.int32))


@pytest.mark.parametrize("n_pus,n_nodes", [_DEVICE_SCAN, _FLEET_SCAN])
def test_walk_reduce_compiles(one_chip, n_pus, n_nodes):
    walk_kernel._jax_reduce().lower(
        *_reduce_specs(one_chip, n_pus, n_nodes)).compile()


def test_walk_reduce_batch_compiles(one_chip):
    walk_kernel._jax_reduce_batch().lower(
        *_reduce_specs(one_chip, *_DEVICE_SCAN, batch=(_BATCH_ROWS,))
    ).compile()
