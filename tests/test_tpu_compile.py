"""Compile the chip's programs for a described TPU v5e, with no chip attached.

The TPU compiler ships with libtpu and compiles for a topology that is only
described (`jax.experimental.topologies`).  It refuses what interpret mode
accepts — unaligned slices, scalar reads from vector memory, programs that
do not fit — so these tests guard the Pallas kernels at the widths
`chip_smoke.py` runs them, and one family sweep program, without chip time.

Only one process at a time may load libtpu, and it keeps it until it exits:
the topology is described inside a module fixture, never at import, and
every compile runs in this process.  JAX's persistent compilation cache is
off around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_spray_select_compiles_for_v5e(one_chip):
    from repro.kernels.spray_select import spray_select_pallas

    compiled = spray_select_pallas.lower(
        _shape(one_chip, (1 << 16,), jnp.uint32),
        _shape(one_chip, (8,), jnp.int32),
        _shape(one_chip, (), jnp.uint32),
        _shape(one_chip, (), jnp.uint32),
        ell=10, method=1,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lt_encode_compiles_for_v5e(one_chip):
    from repro.kernels.lt_encode import lt_encode_pallas

    compiled = lt_encode_pallas.lower(
        _shape(one_chip, (1024, 1024), jnp.uint32),
        _shape(one_chip, (1024, 16), jnp.int32),
        _shape(one_chip, (1024, 16), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fat_tree_family_sweep_compiles_for_v5e(one_chip):
    from repro.net.scenarios import fat_tree_scenarios, stack_scenarios
    from repro.net.sender import (
        SenderSpec, policy_sweep_params, sweep_flows_scenarios,
    )
    from repro.net.transport import Policy

    scens = fat_tree_scenarios(flows=16, n_pods=2, horizon=256)
    topos, scheds = stack_scenarios(list(scens.values()))
    sp = policy_sweep_params((Policy.ECMP, Policy.WAM), rate=16)
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    shapes = jax.tree.map(
        lambda x: _shape(one_chip, np.shape(x), x.dtype),
        (topos, scheds, sp, keys),
    )
    t, c, p, k = shapes
    compiled = sweep_flows_scenarios.lower(
        t, c, SenderSpec(rate_cap=16, early_exit=True), p, 4, k, horizon=256,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
