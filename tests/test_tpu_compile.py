"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jax, and it
compiles for a topology that is described rather than attached. What it
refuses here (misaligned blocks, scalar VMEM stores, too much VMEM or
device memory) it would refuse on the chip. Nothing runs, so these tests
say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and it keeps it until
it exits.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

SYSTEMS = 4096          # tempering chains in one program
SLOTS = 6               # DEFAULT_MAX_CHIPLETS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_prefix_select_kernel_compiles_at_stacked_size(one_chip):
    """The gather kernel over all six Table IV workloads' stacked tables
    (``[5, 288, 129]`` / ``[5, 288, 257]`` int64, packed) for 4096
    systems x 6 slots: the compiled Mosaic kernel, not interpret mode."""
    from repro.jaxenv import search_numerics
    from repro.kernels.prefix_gather import kernel as K

    with search_numerics():
        table, (R, L0, L1) = K.pack_tables(
            np.zeros((5, 288, 129), np.int64),
            np.zeros((5, 288, 257), np.int64))
        assert table.shape == (13896, 128) and (R, L0, L1) == (288, 129,
                                                              257)
        compiled = jax.jit(
            lambda t, ge, gs: K.select_groups(t, ge, gs, interpret=False)
        ).lower(_spec(table.shape, jnp.int32, one_chip),
                _spec((SYSTEMS, SLOTS), jnp.int32, one_chip),
                _spec((SYSTEMS, SLOTS), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == SYSTEMS * K.LANES * 4


def test_eval_cost_jnp_program_compiles_at_4096_rows(one_chip):
    """The fused float64 evaluate+cost program on the jnp gather path
    (WL1, 4096 rows) compiles for the chip and fits its memory."""
    from repro.core import workload
    from repro.jaxenv import search_numerics
    from repro.pathfinding.device import DeviceEvaluator

    dev = DeviceEvaluator(workload(1), use_pallas=False)
    width = dev.space.width
    f64 = jnp.float64
    with search_numerics():
        compiled = dev._eval_cost_jit.lower(
            _spec((SYSTEMS, width), jnp.int32, one_chip),
            _spec((6,), f64, one_chip), _spec((6,), f64, one_chip),
            _spec((6,), f64, one_chip), _spec((), f64, one_chip),
            _spec((), f64, one_chip), _spec((), f64, one_chip),
            _spec((24,), f64, one_chip), _spec((24,), f64, one_chip),
        ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30


def test_layer_blocked_kernel_compiles_at_network_size(one_chip):
    """The grouped gather kernel over a network's layer-blocked table:
    18 GEMM blocks of ``[5, 48, 65]`` / ``[5, 48, 513]`` int64 (packed,
    ~1.8 MB each; ~32 MB whole, past what a double-buffered VMEM copy
    allows), for 18 layers x 24 cells of 64 systems x 6 slots."""
    from repro.jaxenv import search_numerics
    from repro.kernels.prefix_gather import kernel as K

    with search_numerics():
        block, (R, L0, L1) = K.pack_tables(np.zeros((5, 48, 65), np.int64),
                                           np.zeros((5, 48, 513), np.int64))
        assert block.shape == (3472, 128) and (R, L0, L1) == (48, 65, 513)
        groups = 18 * 24
        compiled = jax.jit(
            lambda t, g, ge, gs: K.select_groups_grouped(
                t, g, ge, gs, interpret=False)
        ).lower(_spec((18,) + block.shape, jnp.int32, one_chip),
                _spec((groups,), jnp.int32, one_chip),
                _spec((groups, 64, SLOTS), jnp.int32, one_chip),
                _spec((groups, 64, SLOTS), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == groups * 64 * K.LANES * 4
