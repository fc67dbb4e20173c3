"""The fused window kernel's Mosaic tier, compiled for a described TPU v5e.

No chip is attached: the TPU compiler compiles for the topology it is
given, and refuses here what the chip would refuse — primitives with no
Mosaic lowering, blocks that overflow VMEM — at no chip time. Nothing runs,
so these tests say nothing about results or times (``chip_smoke.py`` runs
the kernel on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.device_loop import TICK_BUDGET
from repro.engine.fleet_jax import _SHAPE_BUCKETS, lane_budget
from repro.kernels.fleet_tick import (CONSTS_ROWS, _TILE_VMEM_BYTES,
                                      fleet_tick_window, tick_tile)

N = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("T", [
    64,                   # a default-tick fused-loop window (S = 32)
    TICK_BUDGET,          # the fused loop with batch_interval_s live (S = 8)
    _SHAPE_BUCKETS[-1],   # the observe path's longest ladder window
])
def test_mosaic_window_compiles_for_v5e(T, one_chip, no_persistent_cache):
    S = lane_budget(T)
    p99_k = min(T * S, int(np.ceil(0.01 * (T * S - 1))) + 2)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = ((f32(2, N), f32(CONSTS_ROWS, N)) + (f32(T, N),) * 7
            + (f32(T, S, N),) * 2 + (f32(T, N), f32(T, N)))
    compiled = fleet_tick_window.lower(
        *args, noise=0.05, retention_s=60.0, straggler_prob=0.05, slo=1.5,
        shi=3.0, p99_k=p99_k, mode="mosaic").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tick_tile_fits_the_vmem_budget():
    """Long windows are tiled over ticks; each tile divides T, keeps the
    8-row sublane tiling, and its double-buffered blocks fit the budget."""
    for T in _SHAPE_BUCKETS + (1280, 2048):
        S = lane_budget(T)
        tT = tick_tile(T, S, 128)
        assert T % tT == 0 and tT % 8 == 0
        assert tT * 2 * 4 * 128 * (21 + 2 * S) <= _TILE_VMEM_BYTES
    assert tick_tile(_SHAPE_BUCKETS[-1], 8, 128) < _SHAPE_BUCKETS[-1]
    assert tick_tile(10, 16, 8) == 10     # short windows stay whole
