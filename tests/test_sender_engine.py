"""Unified sender engine: golden bit-identity, traced-policy dispatch, sweeps.

The acceptance contract of the engine refactor: `simulate_message` on the
independent-bundle seed fabric is BIT-identical to the pre-refactor traces
pinned in tests/golden/transport_seed.npz (regenerate deliberately via
tests/golden/gen_golden_transport.py — never to make a red test green), the
traced-policy `lax.switch` engine matches the per-policy static compiles
element-wise for all five policies on both fabrics and both reliability
modes, and the shared completion threshold guards tiny messages.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.net.sender import (
    BASELINE_POLICIES,
    Policy,
    SenderSpec,
    completion_need,
    policy_sweep_params,
    sender_params,
    sweep_flows,
    sweep_message,
)
from repro.net.topology import leaf_spine, null_schedule
from repro.net.transport import TransportConfig, simulate_flows, simulate_message

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
FIELDS = ("cct", "sent_total", "dropped_total", "final_b", "received")


def _load_gen():
    spec = importlib.util.spec_from_file_location(
        "gen_golden_transport",
        os.path.join(GOLDEN_DIR, "gen_golden_transport.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = _load_gen()
GOLDEN = np.load(os.path.join(GOLDEN_DIR, "transport_seed.npz"))


def mkparams(n=4):
    return GEN.golden_params(n)


@pytest.mark.parametrize(
    "case", GEN.golden_cases(), ids=lambda c: c[0].replace("/", "-")
)
def test_simulate_message_matches_golden_trace(case):
    name, params, cfg, n_packets, seed, horizon = case
    r = simulate_message(params, cfg, n_packets, jax.random.PRNGKey(seed), horizon)
    for field in FIELDS:
        got = np.asarray(getattr(r, field))
        want = GOLDEN[f"{name}/{field}"]
        assert np.array_equal(got, want), (name, field, got, want)


@pytest.mark.parametrize("fname", ["transport_seed.npz", "transport_policies.npz"])
def test_golden_pinned_under_running_jax(fname):
    """The traces pin one JAX version's random bits: a different JAX or PRNG
    mode must be reported as such, not as a semantic change."""
    pins = np.load(os.path.join(GOLDEN_DIR, fname))
    assert str(pins["meta/jax_version"]) == jax.__version__
    assert bool(pins["meta/threefry_partitionable"]) == bool(
        jax.config.jax_threefry_partitionable
    )


def test_simulate_flows_matches_golden_trace():
    topo, sched, cfg, n_packets, seed, horizon = GEN.golden_flows_case()
    r = simulate_flows(topo, sched, cfg, n_packets, jax.random.PRNGKey(seed), horizon)
    for field in FIELDS:
        got = np.asarray(getattr(r, field))
        want = GOLDEN[f"FLOWS/WAM/{field}"]
        assert np.array_equal(got, want), field


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_traced_policy_matches_static_compiles_bundle_fabric(coded):
    """lax.switch dispatch (one compile, policy a vmap axis) is element-wise
    identical to the per-policy static-cfg compiles on the seed fabric."""
    params = mkparams()
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    spec = SenderSpec(coded=coded, rate_cap=16)
    sp = policy_sweep_params(rate=16)
    r = sweep_message(params, spec, sp, 128, keys, horizon=256)
    # the default sweep axis is the five baselines; the eight-policy set is
    # covered by tests/test_policy_contract.py with state blocks enabled
    for pi, pol in enumerate(BASELINE_POLICIES):
        cfg = TransportConfig(policy=pol, coded=coded, rate=16)
        for di, k in enumerate(keys):
            ref = simulate_message(params, cfg, 128, k, 256)
            for field in FIELDS:
                got = np.asarray(getattr(r, field))[pi, di]
                want = np.asarray(getattr(ref, field))
                assert np.array_equal(got, want), (pol.name, field)


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_traced_policy_matches_static_compiles_shared_fabric(coded):
    topo = leaf_spine(4, 4, [(0, 1), (2, 3)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    spec = SenderSpec(coded=coded, rate_cap=16)
    sp = policy_sweep_params(rate=16)
    r = sweep_flows(topo, sched, spec, sp, 96, keys, horizon=256)
    for pi, pol in enumerate(BASELINE_POLICIES):
        cfg = TransportConfig(policy=pol, coded=coded, rate=16)
        for di, k in enumerate(keys):
            ref = simulate_flows(topo, sched, cfg, 96, k, 256)
            for field in FIELDS:
                got = np.asarray(getattr(r, field))[pi, di]
                want = np.asarray(getattr(ref, field))
                assert np.array_equal(got, want), (pol.name, field, coded)


def test_completion_need_matches_seed_formula():
    """For non-tiny messages the shared helper reproduces the historical
    threshold exactly: int(K * (1 + eps)) + 1 - 0.25 (coded), K - 0.25 (arq).

    The range deliberately includes every K in [5, 5000): K * (1 + eps)
    landing exactly on an integer (every K divisible by 20 at eps=0.05) is
    where a float32 `1 + eps` formulation flips the floor and silently
    breaks bit-identity with the seed."""
    for n_packets in range(5, 5000):
        want = float(int(n_packets * 1.05) + 1) - 0.25
        got = float(completion_need(n_packets, True, 0.05))
        assert got == np.float32(want), n_packets
    for n_packets in (5, 17, 100, 256, 1024, 4096):
        for eps in (0.0, 0.05, 0.25):
            want = float(int(n_packets * (1.0 + eps)) + 1) - 0.25
            got = float(completion_need(n_packets, True, eps))
            assert got == np.float32(want), (n_packets, eps)
        assert float(completion_need(n_packets, False, 0.05)) == n_packets - 0.25


def test_completion_need_tiny_message_guard():
    # n <= 4: the coded overhead is waived — a 1-packet message needs 1 packet
    for n_packets in (1, 2, 3, 4):
        assert float(completion_need(n_packets, True, 0.05)) == n_packets - 0.25
        assert float(completion_need(n_packets, False, 0.05)) == n_packets - 0.25
    # n == 0: non-positive threshold -> completes at tick 0
    assert float(completion_need(0, True, 0.05)) <= 0.0
    assert float(completion_need(0, False, 0.05)) <= 0.0


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_empty_message_completes_at_tick_zero(coded):
    params = mkparams()
    cfg = TransportConfig(policy=Policy.WAM, coded=coded, rate=16)
    r = simulate_message(params, cfg, 0, jax.random.PRNGKey(0), 64)
    assert float(r.cct) == 0.0
    assert float(r.sent_total.sum()) == 0.0

    topo = leaf_spine(2, 4, [(0, 1)], uplink_capacity=8.0)
    rf = simulate_flows(
        topo, null_schedule(topo.links), cfg, 0, jax.random.PRNGKey(0), 64
    )
    assert np.all(np.asarray(rf.cct) == 0.0)


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_tiny_messages_complete_without_overhead(coded):
    params = mkparams()
    for n_packets in (1, 2, 4):
        cfg = TransportConfig(policy=Policy.WAM, coded=coded, rate=16)
        r = simulate_message(params, cfg, n_packets, jax.random.PRNGKey(1), 256)
        assert float(r.cct) < 256, (coded, n_packets)  # completed, not sentinel
        assert float(r.received) >= n_packets - 0.25


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_finished_mask_tracks_horizon_sentinel(coded):
    """finished is True exactly when cct < horizon; a too-short horizon
    yields the sentinel AND finished == False (no silent flattening)."""
    params = mkparams()
    cfg = TransportConfig(policy=Policy.WAM, coded=coded, rate=16)
    ok = simulate_message(params, cfg, 64, jax.random.PRNGKey(0), 512)
    assert bool(ok.finished) and float(ok.cct) < 512
    short = simulate_message(params, cfg, 4096, jax.random.PRNGKey(0), 8)
    assert not bool(short.finished)
    assert float(short.cct) == 8.0  # the sentinel, flagged as such

    topo = leaf_spine(2, 4, [(0, 1)], uplink_capacity=8.0)
    rf = simulate_flows(
        topo, null_schedule(topo.links), cfg, 4096, jax.random.PRNGKey(0), 8
    )
    assert not np.any(np.asarray(rf.finished))
    assert np.all(np.asarray(rf.cct) == 8.0)


def test_transport_config_seed_validation():
    """Concrete configs keep the historical host-side seed guard (the
    engine's traced seeds are normalized instead — flow-0 semantics)."""
    with pytest.raises(ValueError):
        TransportConfig(policy=Policy.WAM, seed=(333, 734))  # even sb
    with pytest.raises(ValueError):
        TransportConfig(policy=Policy.WAM, seed=(4096, 735))  # sa >= m
    # traced path: an even sb is forced odd, matching run_flows' flow 0
    from repro.net.sender import run_message

    params = mkparams()
    sp_even = sender_params(Policy.WAM, rate=16, seed=(333, 734))
    sp_odd = sender_params(Policy.WAM, rate=16, seed=(333, 735))
    spec = SenderSpec(rate_cap=16)
    key = jax.random.PRNGKey(0)
    r_even = run_message(params, spec, sp_even, 64, key, 256)
    r_odd = run_message(params, spec, sp_odd, 64, key, 256)
    assert np.array_equal(np.asarray(r_even.cct), np.asarray(r_odd.cct))
    assert np.array_equal(
        np.asarray(r_even.sent_total), np.asarray(r_odd.sent_total)
    )


def test_sweep_shapes_and_rate_axis():
    """The sweep axis is any SenderParams field, not just policy: a rate
    sweep shares one program sized by rate_cap."""
    from repro.net.sender import stack_params

    params = mkparams()
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    spec = SenderSpec(rate_cap=16)
    sp = stack_params(
        [sender_params(Policy.WAM, rate=r) for r in (4, 8, 16)]
    )
    r = sweep_message(params, spec, sp, 64, keys, horizon=512)
    assert r.cct.shape == (3, 3)
    ccts = np.asarray(r.cct)
    # higher rate never completes later (healthy-ish fabric, averaged draws)
    assert ccts[0].mean() >= ccts[1].mean() >= ccts[2].mean()
    # rate swept within one program matches the static rate_cap==rate compile
    ref = simulate_message(
        params, TransportConfig(policy=Policy.WAM, rate=16), 64,
        keys[0], 512,
    )
    assert np.array_equal(np.asarray(r.cct)[2, 0], np.asarray(ref.cct))


def test_ring_steps_shared_single_compile_matches_loop():
    """collectives' vmapped ring steps == a Python loop of per-step calls."""
    from repro.net.collectives import ring_steps_cct_shared
    from repro.net.topology import null_schedule as null
    from repro.net import ring_topology

    topo = ring_topology(4, n_spines=4, uplink_capacity=8.0)
    sched = null(topo.links)
    tcfg = TransportConfig(policy=Policy.WAM, rate=16)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    per_step, finished = ring_steps_cct_shared(
        topo, sched, tcfg.spec(), tcfg.params(), 64, keys, 256
    )
    want = [
        float(
            jnp.max(simulate_flows(topo, sched, tcfg, 64, k, 256).cct)
        )
        for k in keys
    ]
    assert np.allclose(np.asarray(per_step), np.asarray(want), atol=0)
    assert bool(np.asarray(finished).all())
