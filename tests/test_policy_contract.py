"""Sender-engine policy contract suite: one invariant battery, all 8 policies.

Every registered policy (the five baselines + PRIME / STRACK / CC_COUPLED)
goes through the same checks:

  * allocation conservation — sum(b) == m under arbitrary whack / restore /
    controller-step sequences (hypothesis when installed, auto-skip
    otherwise, with a fixed-seed fallback battery that always runs), and at
    the end of every engine run;
  * per-flow emission conservation — on a clean (non-degrading, unbounded-
    queue) fabric an ARQ sender emits exactly n_packets and delivers all of
    them, under every policy;
  * finished-mask consistency — `finished` implies cct <= horizon,
    ~finished implies the cct == horizon sentinel, on both a sufficient and
    an insufficient horizon;
  * traced-`lax.switch` dispatch == per-policy static compile — the
    eight-policy sweep (union state blocks) is bit-identical to each
    policy's own static compile (its own blocks only) on BOTH the
    independent-bundle seed fabric and the shared leaf-spine fabric.  This
    simultaneously pins the dispatch path and the "extra enabled blocks are
    observation-only" property of the per-policy state refactor;
  * golden traces — the new policies match tests/golden/
    transport_policies.npz, and tests/golden/transport_seed.npz still
    contains EXACTLY the pre-refactor five-policy key set (the extension
    never rewrites it; byte-for-byte content identity is pinned by
    tests/test_sender_engine.py).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.feedback import (
    PathStats,
    controller_step,
    make_controller,
    restore_path,
    whack_down,
)
from repro.core.profile import uniform_profile
from repro.core.spray import SprayMethod, SprayState
from repro.net.fabric import FabricParams
from repro.net.policies import (
    ALL_POLICIES,
    BASELINE_POLICIES,
    POLICY_DEFS,
    Policy,
    blocks_for,
    strack_scores,
)
from repro.net.policy_state import (
    BLOCKS,
    CCW_MAX,
    CCW_MIN,
    PEN_DECAY,
    init_policy_state,
    update_policy_state,
)
from repro.net.sender import (
    SenderSpec,
    assign_paths,
    policy_sweep_params,
    spec_for_policies,
    sweep_flows,
    sweep_message,
)
from repro.net.topology import leaf_spine, null_schedule
from repro.net.transport import TransportConfig, simulate_flows, simulate_message

try:
    import hypothesis  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed (auto-skip)"
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
FIELDS = ("cct", "sent_total", "dropped_total", "final_b", "received")
NEW_POLICIES = (Policy.PRIME, Policy.STRACK, Policy.CC_COUPLED)


def _load_gen():
    spec = importlib.util.spec_from_file_location(
        "gen_golden_transport_contract",
        os.path.join(GOLDEN_DIR, "gen_golden_transport.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = _load_gen()
GOLDEN_POLICIES = np.load(os.path.join(GOLDEN_DIR, "transport_policies.npz"))


def clean_params(n=4):
    """Non-degrading fabric with unbounded queues: nothing is ever dropped,
    so emission accounting must balance exactly."""
    return FabricParams(
        capacity=jnp.full((n,), 8.0),
        latency=jnp.full((n,), 4, jnp.int32),
        queue_limit=jnp.full((n,), 1e6),
        ecn_threshold=jnp.full((n,), 6.0),
        degrade_p=jnp.full((n,), 0.0),
        recover_p=jnp.full((n,), 1.0),
        degrade_factor=jnp.full((n,), 1.0),
        fb_delay=8,
        ring_len=64,
    )


def bakeoff_sweep(coded=True, rate=16):
    spec = spec_for_policies(SenderSpec(coded=coded, rate_cap=rate), ALL_POLICIES)
    sp = policy_sweep_params(ALL_POLICIES, rate=rate)
    return spec, sp


# --- registry sanity -------------------------------------------------------


def test_registry_covers_every_policy():
    assert tuple(d.policy for d in POLICY_DEFS) == ALL_POLICIES
    assert len(ALL_POLICIES) == 8
    assert ALL_POLICIES[:5] == BASELINE_POLICIES
    for d in POLICY_DEFS:
        assert set(d.blocks) <= set(BLOCKS), d
        if d.policy in BASELINE_POLICIES:
            assert d.blocks == (), "baselines must stay stateless"


def test_blocks_for_is_canonical_union():
    assert blocks_for(BASELINE_POLICIES) == ()
    assert blocks_for((Policy.STRACK,)) == ("rtt", "penalty")
    assert blocks_for((Policy.PRIME,)) == ("entropy",)
    assert blocks_for((Policy.CC_COUPLED,)) == ("ccw",)
    # union is in BLOCKS order regardless of input order
    assert blocks_for(reversed(ALL_POLICIES)) == BLOCKS


def test_zero_width_state_is_structural_noop():
    off = init_policy_state((), (3,), 4, latency=jnp.zeros((4,)), sa=jnp.zeros((3,), jnp.uint32))
    on = init_policy_state(BLOCKS, (3,), 4, latency=jnp.zeros((4,)), sa=jnp.zeros((3,), jnp.uint32))
    for leaf in (off.rtt, off.penalty, off.entropy, off.ccw):
        assert leaf.shape == (3, 0)
    for leaf in (on.rtt, on.penalty, on.entropy, on.ccw):
        assert leaf.shape == (3, 4)
    # updating a zero-width state is a no-op with the same structure
    fb = jnp.zeros((3, 4))
    off2 = update_policy_state(
        off, ecn_rate=fb, loss_rate=fb, rtt_sample=fb, seen=fb > 0
    )
    assert jax.tree.structure(off2) == jax.tree.structure(off)


# --- allocation conservation ----------------------------------------------


def _check_controller_sequence(n, ops):
    """sum(b) == m and b >= 0 after every whack / restore / step."""
    ell = 6
    m = 1 << ell
    ctrl = make_controller(uniform_profile(n, ell))
    for kind, payload in ops:
        if kind == "step":
            ecn, loss, rtt = payload
            stats = PathStats(
                ecn_rate=jnp.asarray(ecn, jnp.float32),
                loss_rate=jnp.asarray(loss, jnp.float32),
                rtt=jnp.asarray(rtt, jnp.float32),
            )
            ctrl, _ = controller_step(ctrl, stats)
        elif kind == "whack":
            ctrl = whack_down(ctrl, jnp.asarray(payload, jnp.float32))
        else:
            ctrl = restore_path(ctrl, int(payload))
        b = np.asarray(ctrl.profile.b)
        assert int(b.sum()) == m, (kind, b)
        assert (b >= 0).all(), (kind, b)


def _random_ops(rng, n, k):
    ops = []
    for _ in range(k):
        kind = rng.choice(["step", "whack", "restore"])
        if kind == "step":
            ops.append(
                ("step", (rng.random(n), rng.random(n) * 0.5,
                          1.0 + rng.random(n) * 50.0))
            )
        elif kind == "whack":
            ops.append(("whack", rng.random(n)))
        else:
            ops.append(("restore", rng.integers(n)))
    return ops


@pytest.mark.parametrize("n", [2, 3, 8])
def test_alloc_conservation_fixed_sequences(n):
    """Always-on fallback for the hypothesis battery: 64 random whack /
    restore / step ops from a fixed seed keep sum(b) == m."""
    rng = np.random.default_rng(100 + n)
    _check_controller_sequence(n, _random_ops(rng, n, 64))


@needs_hypothesis
def test_alloc_conservation_hypothesis():
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 8), st.integers(0, 2**32 - 1), st.integers(1, 40))
    def run(n, seed, k):
        _check_controller_sequence(
            n, _random_ops(np.random.default_rng(seed), n, k)
        )

    run()


def test_alloc_conservation_end_of_run_all_policies():
    """Every policy's final profile still sums to m after a full engine run
    on the degrading golden fabric (one compiled 8-policy sweep)."""
    spec, sp = bakeoff_sweep(coded=True)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    r = sweep_message(GEN.golden_params(4), spec, sp, 128, keys, horizon=512)
    b = np.asarray(r.final_b)  # [8, D, n]
    m = 1 << spec.ell
    assert (b.sum(axis=-1) == m).all()
    assert (b >= 0).all()


# --- per-policy state dynamics --------------------------------------------


def _check_state_dynamics(feedback_seq, n=4):
    state = init_policy_state(
        BLOCKS, (), n, latency=jnp.full((n,), 4.0), sa=jnp.uint32(5)
    )
    for ecn, loss, rtt in feedback_seq:
        prev_ent = np.asarray(state.entropy)
        state = update_policy_state(
            state,
            ecn_rate=jnp.asarray(ecn, jnp.float32),
            loss_rate=jnp.asarray(loss, jnp.float32),
            rtt_sample=jnp.asarray(rtt, jnp.float32),
            seen=jnp.asarray(rtt, jnp.float32) > 0,
        )
        assert (np.asarray(state.penalty) >= 0).all()
        assert (np.asarray(state.ccw) >= CCW_MIN).all()
        assert (np.asarray(state.ccw) <= CCW_MAX).all()
        assert np.isfinite(np.asarray(state.rtt)).all()
        assert state.entropy.dtype == jnp.uint32
        if not (np.any(np.asarray(ecn) > 0) or np.any(np.asarray(loss) > 0)):
            # clean feedback never rerolls entropy slots
            assert (np.asarray(state.entropy) == prev_ent).all()
        # STrack eligibility never empties
        _, good = strack_scores(state)
        assert bool(np.asarray(good).any())


def test_state_dynamics_fixed_sequences():
    rng = np.random.default_rng(7)
    seq = [
        (rng.random(4) * (rng.random() < 0.5), rng.random(4) * 0.3,
         1.0 + rng.random(4) * 20.0)
        for _ in range(50)
    ]
    seq.append((np.zeros(4), np.zeros(4), np.full(4, 5.0)))  # clean tick
    _check_state_dynamics(seq)


@needs_hypothesis
def test_state_dynamics_hypothesis():
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.integers(1, 30))
    def run(seed, k):
        rng = np.random.default_rng(seed)
        seq = [
            (rng.random(4), rng.random(4), rng.random(4) * 100.0)
            for _ in range(k)
        ]
        _check_state_dynamics(seq)

    run()


def test_strack_eligible_set_tracks_penalty_decay():
    state = init_policy_state(
        ("rtt", "penalty"), (), 2, latency=jnp.full((2,), 4.0), sa=jnp.uint32(0)
    )
    state = dataclasses_replace_penalty(state, jnp.asarray([2.0, 0.0]))
    _, good = strack_scores(state)
    assert list(np.asarray(good)) == [False, True]
    # pure decay (clean feedback) re-admits the penalized path
    for _ in range(64):
        state = update_policy_state(
            state,
            ecn_rate=jnp.zeros((2,)), loss_rate=jnp.zeros((2,)),
            rtt_sample=jnp.full((2,), 4.0), seen=jnp.ones((2,), bool),
        )
    _, good = strack_scores(state)
    assert list(np.asarray(good)) == [True, True]
    assert float(state.penalty[0]) == pytest.approx(2.0 * PEN_DECAY**64)


def dataclasses_replace_penalty(state, pen):
    import dataclasses

    return dataclasses.replace(state, penalty=jnp.asarray(pen, jnp.float32))


# --- emission conservation + finished mask --------------------------------


def test_emission_conservation_arq_clean_fabric():
    """No drops -> an ARQ sender emits EXACTLY n_packets and delivers all of
    them, whatever the policy sprays."""
    spec, sp = bakeoff_sweep(coded=False)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    r = sweep_message(clean_params(4), spec, sp, 64, keys, horizon=512)
    assert np.asarray(r.finished).all()
    np.testing.assert_array_equal(np.asarray(r.sent_total).sum(axis=-1), 64.0)
    np.testing.assert_array_equal(np.asarray(r.dropped_total), 0.0)
    np.testing.assert_array_equal(np.asarray(r.received), 64.0)


def test_coded_clean_fabric_meets_need():
    spec, sp = bakeoff_sweep(coded=True)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    r = sweep_message(clean_params(4), spec, sp, 64, keys, horizon=512)
    assert np.asarray(r.finished).all()
    # need = floor(64 + 64*0.05) + 1 - 0.25 = 67.75
    assert (np.asarray(r.received) >= 67.75).all()
    assert (np.asarray(r.sent_total).sum(axis=-1) >= np.asarray(r.received)).all()


@pytest.mark.parametrize("horizon", [8, 512], ids=["insufficient", "ample"])
def test_finished_mask_consistency(horizon):
    spec, sp = bakeoff_sweep(coded=True)
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    r = sweep_message(clean_params(4), spec, sp, 64, keys, horizon=horizon)
    cct = np.asarray(r.cct)
    fin = np.asarray(r.finished)
    assert (cct[~fin] == horizon).all()
    assert (cct[fin] <= horizon).all()
    if horizon == 8:
        assert not fin.any(), "8 ticks cannot complete 64 packets"
    else:
        assert fin.all()


# --- traced switch == per-policy static compiles, all 8 policies ----------


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_traced_dispatch_matches_static_all_policies_bundle(coded):
    """The 8-policy sweep (UNION state blocks) is bit-identical to each
    policy's own static compile (its OWN blocks only) on the seed fabric:
    pins both the lax.switch dispatch and blocks-are-observation-only."""
    params = GEN.golden_params(4)
    keys = jax.random.split(jax.random.PRNGKey(11), 1)
    spec, sp = bakeoff_sweep(coded=coded)
    r = sweep_message(params, spec, sp, 128, keys, horizon=256)
    for pi, pol in enumerate(ALL_POLICIES):
        cfg = TransportConfig(policy=pol, coded=coded, rate=16)
        assert cfg.spec().state_blocks == blocks_for((pol,))
        ref = simulate_message(params, cfg, 128, keys[0], 256)
        for field in FIELDS:
            got = np.asarray(getattr(r, field))[pi, 0]
            want = np.asarray(getattr(ref, field))
            assert np.array_equal(got, want), (pol.name, field)


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_traced_dispatch_matches_static_all_policies_shared(coded):
    topo = leaf_spine(4, 4, [(0, 1), (2, 3)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    keys = jax.random.split(jax.random.PRNGKey(13), 1)
    spec, sp = bakeoff_sweep(coded=coded)
    r = sweep_flows(topo, sched, spec, sp, 96, keys, horizon=256)
    for pi, pol in enumerate(ALL_POLICIES):
        cfg = TransportConfig(policy=pol, coded=coded, rate=16)
        ref = simulate_flows(topo, sched, cfg, 96, keys[0], 256)
        for field in FIELDS:
            got = np.asarray(getattr(r, field))[pi, 0]
            want = np.asarray(getattr(ref, field))
            assert np.array_equal(got, want), (pol.name, field, coded)


def test_baselines_bit_identical_with_blocks_enabled():
    """Enabling every state block changes NOTHING for the stateless five —
    the zero-cost-extension property the golden traces rely on."""
    params = GEN.golden_params(4)
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    sp = policy_sweep_params(rate=16)
    spec_off = SenderSpec(rate_cap=16)
    spec_on = spec_for_policies(spec_off, ALL_POLICIES)
    r0 = sweep_message(params, spec_off, sp, 128, keys, horizon=256)
    r1 = sweep_message(params, spec_on, sp, 128, keys, horizon=256)
    for field in FIELDS:
        assert np.array_equal(
            np.asarray(getattr(r0, field)), np.asarray(getattr(r1, field))
        ), field


def test_stateless_fallback_is_rand_static():
    """Without its state block a state-bearing policy's branch IS the
    rand_static branch (the documented degradation), packet for packet."""
    n, rate_cap = 4, 8
    profile = uniform_profile(n, 6)
    spray = SprayState(
        j=jnp.uint32(0), sa=jnp.uint32(5), sb=jnp.uint32(7),
        path_seq=jnp.zeros((n,), jnp.int32), ell=6,
        method=int(SprayMethod.SHUFFLE_1),
    )
    key = jax.random.PRNGKey(23)
    k_emit = jnp.int32(rate_cap)
    ecmp = jnp.int32(0)
    out = {}
    for pol in (Policy.RAND_STATIC,) + NEW_POLICIES:
        arrivals, _ = assign_paths(
            rate_cap, n, jnp.int32(int(pol)), spray, profile, k_emit, key, ecmp
        )
        out[pol] = np.asarray(arrivals)
    for pol in NEW_POLICIES:
        np.testing.assert_array_equal(out[pol], out[Policy.RAND_STATIC])


# --- golden traces ---------------------------------------------------------


@pytest.mark.parametrize(
    "case", GEN.golden_policy_cases(), ids=lambda c: c[0].replace("/", "-")
)
def test_new_policy_matches_golden_trace(case):
    name, params, cfg, n_packets, seed, horizon = case
    r = simulate_message(params, cfg, n_packets, jax.random.PRNGKey(seed), horizon)
    for field in FIELDS:
        got = np.asarray(getattr(r, field))
        want = GOLDEN_POLICIES[f"{name}/{field}"]
        assert np.array_equal(got, want), (name, field, got, want)


@pytest.mark.parametrize(
    "case", GEN.golden_policy_flows_cases(), ids=lambda c: c[0].replace("/", "-")
)
def test_new_policy_flows_match_golden_trace(case):
    name, topo, sched, cfg, n_packets, seed, horizon = case
    r = simulate_flows(topo, sched, cfg, n_packets, jax.random.PRNGKey(seed), horizon)
    for field in FIELDS:
        got = np.asarray(getattr(r, field))
        want = GOLDEN_POLICIES[f"{name}/{field}"]
        assert np.array_equal(got, want), (name, field)


def test_seed_golden_file_keys_frozen():
    """transport_seed.npz contains EXACTLY the pre-refactor five-policy key
    set: the new-policy traces live in transport_policies.npz, and the gen
    script never rewrites the seed file by default (content identity is
    pinned byte-for-byte by tests/test_sender_engine.py)."""
    seed_keys = set(np.load(os.path.join(GOLDEN_DIR, "transport_seed.npz")).keys())
    assert set(GEN.META_KEYS) <= seed_keys
    seed_keys -= set(GEN.META_KEYS)
    expected = {
        f"{pol.name}/{rel}/{field}"
        for pol in BASELINE_POLICIES
        for rel in ("coded", "arq")
        for field in FIELDS
    }
    expected |= {f"WAM/default8/{field}" for field in FIELDS}
    expected |= {f"FLOWS/WAM/{field}" for field in FIELDS}
    assert seed_keys == expected
    assert not any(p.name in k for k in seed_keys for p in NEW_POLICIES)
    # and the gen script's seed-case list stays the frozen baseline set
    assert {c[0].split("/")[0] for c in GEN.golden_cases()} == {
        p.name for p in BASELINE_POLICIES
    }
