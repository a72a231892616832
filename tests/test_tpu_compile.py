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
import re

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


def _computation(hlo: str, name: str) -> list:
    """The instruction names of computation `name` in HLO text."""
    out, inside = [], False
    for line in hlo.splitlines():
        if not inside:
            inside = line.startswith(f"%{name} ") or line.startswith(f"{name} ")
            continue
        if line.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
        if m:
            out.append(m.group(1))
    return out


def test_fat_tree_sweep_names_the_tick_components_for_v5e(one_chip):
    """The engine's named scopes reach the chip's optimized HLO, where the
    benchmark's trace reduction (`bench.scopes.op_scopes`) reads them: the
    ops of the chunk scan's body fall under one tick component each, WAM's
    `searchsorted` gathers under its policy's branch, the fabric's
    scatter-adds under `tick/fabric` though the TPU compiler strips their
    roots' metadata, and the chunk loop states its trip count."""
    from bench.scopes import ScopedTrace, op_scopes, scope_path
    from bench.trace import CONTAINERS, op_kinds
    from repro.net.scenarios import fat_tree_scenarios, stack_scenarios
    from repro.net.sender import (
        SenderSpec, policy_sweep_params, sweep_flows_scenarios,
    )
    from repro.net.transport import Policy

    scens = fat_tree_scenarios(flows=16, n_pods=2, horizon=256)
    topos, scheds = stack_scenarios(list(scens.values()))
    sp = policy_sweep_params((Policy.ECMP, Policy.WAM), rate=16)
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    t, c, p, k = jax.tree.map(
        lambda x: _shape(one_chip, np.shape(x), x.dtype),
        (topos, scheds, sp, keys),
    )
    spec = SenderSpec(rate_cap=16, early_exit=True)
    hlo = sweep_flows_scenarios.lower(
        t, c, spec, p, 4, k, horizon=256,
    ).compile().as_text()
    tr = ScopedTrace({}, [], op_kinds([hlo]), scopes=op_scopes([hlo]))
    parts = ("tick/assign", "tick/fabric", "tick/sender")

    # the chunk scan: the one loop under engine/chunk, outside the tick,
    # whose trip count the HLO states
    chunks = [n for n in tr.scopes["trips"]
              if tr.under(n, "engine/chunk") and not tr.under(n, "tick")]
    assert len(chunks) == 1
    assert tr.scopes["trips"][chunks[0]] == spec.exit_chunk
    body = re.search(rf"%{re.escape(chunks[0])} = .*?body=%?([\w.\-]+)", hlo).group(1)

    # every op of its body that does work lies under exactly one tick
    # component; the rest are the scan's own counter and key slice, and
    # what the compiler made with no scope of its own (copies, buffers,
    # constant tables), which carry the loop's
    free = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")
    seen = {part: 0 for part in parts}
    for name in _computation(hlo, body):
        kind = tr.kind(name)
        if kind in CONTAINERS or kind in free:
            continue
        under = [part for part in parts if tr.under(name, part)]
        if tr.under(name, "tick"):
            assert len(under) == 1, (name, tr.scopes["ops"][name])
            seen[under[0]] += 1
        else:
            path = scope_path(tr.scopes["ops"][name])
            assert path[-1] in ("while", "closed_call") or path[-2:] in (
                ["body", "add"], ["body", "dynamic_slice"]), (name, path)
    assert all(seen.values()), seen

    # WAM's (and RAND_ADAPTIVE's) `spray.select_path` searchsorted gathers
    gathers = [n for n, kind in tr.kinds.items() if kind == "gather"
               and "searchsorted" in (tr.scopes["ops"][n] or "")]
    assert gathers
    assert all(tr.under(n, "tick/assign/wam") or tr.under(n, "tick/assign/rand_adaptive")
               for n in gathers)
    assert any(tr.under(n, "tick/assign/wam") for n in gathers)

    # `_link_sum`'s and `scatter_delivery`'s scatters, whatever the
    # compiler left at their roots
    scatters = [n for n in _computation(hlo, body) if tr.kind(n) == "scatter"]
    fabric = [n for n in scatters if tr.under(n, "tick/fabric")]
    assert all(tr.under(n, "tick/fabric") or tr.under(n, "tick/sender") for n in scatters)
    assert any(tr.under(n, "tick/fabric/link_sums") for n in fabric)
    assert any(tr.under(n, "tick/fabric/delivery") for n in fabric)
