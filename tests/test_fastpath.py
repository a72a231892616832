"""Fast-path equivalence suite: every hot-loop transform == its reference.

The perf pass (scatter delivery ring, compare-count path assignment,
hoisted pre-split RNG, early-exit horizons, scenario-axis batching, padded
spray_select blocks) must be REFACTORS, not semantic changes: each test
here pins one transform against the formulation it replaced.  Golden
traces (tests/test_sender_engine.py) additionally pin the composed engine
bit-for-bit; this file isolates the individual claims so a regression
points at the guilty transform.

Property tests use hypothesis where available and fall back to a fixed
seed sweep otherwise (the seed image ships without hypothesis).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.profile import quantize_profile
from repro.kernels import ops, ref
from repro.net.sender import (
    Policy,
    SenderSpec,
    fabric_quiescent,
    policy_sweep_params,
    run_flows_sized,
    sender_params,
    sweep_flows,
    sweep_flows_scenarios,
    sweep_message,
    tick_keys,
)
from repro.net.fabric import FabricParams
from repro.net.scenarios import pair_scenarios, stack_scenarios
from repro.net.topology import (
    EventSchedule,
    leaf_spine,
    null_schedule,
    scatter_delivery,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

# the fields the early-exit mode promises bit-identical (final_b and the
# link counters are exempt: controller/background keep evolving over the
# dead ticks a full-horizon scan still executes)
COMPLETION_FIELDS = ("cct", "sent_total", "dropped_total", "received", "finished")

RNG = np.random.default_rng(0)


def _params(n=4):
    return FabricParams(
        capacity=jnp.full((n,), 4.0),
        latency=jnp.full((n,), 2, jnp.int32),
        queue_limit=jnp.full((n,), 24.0),
        ecn_threshold=jnp.full((n,), 6.0),
        degrade_p=jnp.full((n,), 0.02),
        recover_p=jnp.full((n,), 0.1),
        degrade_factor=jnp.full((n,), 0.05),
        fb_delay=4,
        ring_len=64,
    )


def _assert_completion_equal(a, b, ctx=""):
    for field in COMPLETION_FIELDS:
        x = np.asarray(getattr(a, field))
        y = np.asarray(getattr(b, field))
        assert np.array_equal(x, y), (ctx, field)


# ---------------------------------------------------------------------------
# scatter delivery ring == one-hot/einsum reference
# ---------------------------------------------------------------------------
def _check_scatter_ring(seed: int) -> None:
    rng = np.random.default_rng(seed)
    F, n, R = int(rng.integers(1, 7)), int(rng.integers(1, 9)), 32
    ring = jnp.asarray(rng.random((F, R)).astype(np.float32) * 8)
    slot = jnp.asarray(rng.integers(0, R, (F, n)), jnp.int32)
    exiting = jnp.asarray(rng.random((F, n)).astype(np.float32) * 3)
    got = jax.jit(scatter_delivery)(ring, slot, exiting)
    onehot = jax.nn.one_hot(slot, R, dtype=exiting.dtype)
    want = ring + jnp.einsum("fn,fnr->fr", exiting, onehot)
    assert np.array_equal(np.asarray(got), np.asarray(want)), seed


if HAVE_HYPOTHESIS:

    @given(st.integers(0, 2**20))
    @settings(max_examples=30, deadline=None)
    def test_scatter_ring_matches_onehot_einsum(seed):
        _check_scatter_ring(seed)

else:

    @pytest.mark.parametrize("seed", list(range(30)))
    def test_scatter_ring_matches_onehot_einsum(seed):
        _check_scatter_ring(seed)


def test_scatter_ring_colliding_slots():
    """All paths landing in one slot (the zero-delay common case) must sum
    exactly like the einsum reduction."""
    ring = jnp.asarray(RNG.random((3, 16)).astype(np.float32))
    slot = jnp.full((3, 5), 7, jnp.int32)
    exiting = jnp.asarray(RNG.random((3, 5)).astype(np.float32))
    got = scatter_delivery(ring, slot, exiting)
    onehot = jax.nn.one_hot(slot, 16, dtype=exiting.dtype)
    want = ring + jnp.einsum("fn,fnr->fr", exiting, onehot)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# hoisted RNG == per-tick fold_in + split
# ---------------------------------------------------------------------------
def test_tick_keys_match_per_tick_fold_in():
    for seed in (0, 7, 123):
        k_loop = jax.random.PRNGKey(seed)
        keys = np.asarray(tick_keys(k_loop, 19))
        for t in range(19):
            want = np.asarray(
                jax.random.split(jax.random.fold_in(k_loop, t))
            )
            assert np.array_equal(keys[t], want), (seed, t)


# ---------------------------------------------------------------------------
# early-exit mode == full-horizon mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_early_exit_matches_full_horizon_shared_fabric(coded):
    """All five policies x draws on the shared fabric: the chunked
    while_loop engine reports identical completion fields, including when
    it genuinely exits early (horizon far beyond the last completion)."""
    topo = leaf_spine(4, 4, [(0, 1), (2, 3)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    spec = SenderSpec(coded=coded, rate_cap=16)
    spec_ee = dataclasses.replace(spec, early_exit=True, exit_chunk=32)
    sp = policy_sweep_params(rate=16)
    full = sweep_flows(topo, sched, spec, sp, 96, keys, horizon=512)
    fast = sweep_flows(topo, sched, spec_ee, sp, 96, keys, horizon=512)
    _assert_completion_equal(full, fast, ("shared", coded))
    # the early exit actually had dead ticks to skip
    assert float(np.asarray(full.cct).max()) < 512


@pytest.mark.parametrize("coded", [True, False], ids=["coded", "arq"])
def test_early_exit_matches_full_horizon_bundle_fabric(coded):
    params = _params()
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    spec = SenderSpec(coded=coded, rate_cap=16)
    spec_ee = dataclasses.replace(spec, early_exit=True, exit_chunk=32)
    sp = policy_sweep_params(rate=16)
    full = sweep_message(params, spec, sp, 64, keys, horizon=512)
    fast = sweep_message(params, spec_ee, sp, 64, keys, horizon=512)
    _assert_completion_equal(full, fast, ("bundle", coded))


def test_early_exit_unfinished_flows_keep_sentinel():
    """A horizon too short to finish must report the identical sentinel —
    the while_loop may not run past the horizon's tick budget."""
    topo = leaf_spine(2, 4, [(0, 1)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    key = jax.random.PRNGKey(0)
    sp = policy_sweep_params((Policy.WAM,), rate=16)
    spec = SenderSpec(rate_cap=16)
    # horizon 40 with exit_chunk 32 exercises the tail scan (40 = 32 + 8)
    spec_ee = dataclasses.replace(spec, early_exit=True, exit_chunk=32)
    keys = key[None] if key.ndim == 1 else key
    full = sweep_flows(topo, sched, spec, sp, 4096, keys, horizon=40)
    fast = sweep_flows(topo, sched, spec_ee, sp, 4096, keys, horizon=40)
    _assert_completion_equal(full, fast, "sentinel")
    assert not np.asarray(full.finished).any()
    assert np.all(np.asarray(full.cct) == 40.0)


def test_early_exit_per_flow_sizes_with_silent_flows():
    """The cluster layer's regime: size-0 flows complete at tick 0 and the
    whole coupled simulation settles once the one live flow drains."""
    topo = leaf_spine(4, 4, [(0, 1), (2, 3)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    sizes = jnp.asarray([64, 0], jnp.int32)
    sp = sender_params(Policy.WAM, rate=16)
    key = jax.random.PRNGKey(1)
    spec = SenderSpec(rate_cap=16)
    spec_ee = dataclasses.replace(spec, early_exit=True)
    full = run_flows_sized(topo, sched, spec, sp, sizes, key, 384)
    fast = run_flows_sized(topo, sched, spec_ee, sp, sizes, key, 384)
    _assert_completion_equal(full, fast, "per-flow sizes")
    assert float(np.asarray(full.cct)[1]) == 0.0


def test_fabric_quiescent_flags_inflight_traffic():
    from repro.net.topology import init_shared_fabric, shared_fabric_tick

    topo = leaf_spine(2, 2, [(0, 1)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    state = init_shared_fabric(topo)
    assert bool(fabric_quiescent(state))
    arrivals = jnp.ones((1, topo.n), jnp.float32)
    state, _ = shared_fabric_tick(
        topo, sched, state, arrivals, jax.random.PRNGKey(0)
    )
    assert not bool(fabric_quiescent(state))


def test_sub_nanopacket_backlog_drains_whole():
    """A link that can serve its whole backlog serves it whole, however
    small: the queue drains to an exact zero, the link counter books what
    the flows were served, and the fabric goes quiescent."""
    from repro.net.topology import init_shared_fabric, shared_fabric_tick

    topo = leaf_spine(2, 2, [(0, 1)], uplink_capacity=8.0)
    sched = null_schedule(topo.links)
    state = init_shared_fabric(topo)
    state = dataclasses.replace(
        state, queue=state.queue.at[0, 0, 1].set(3.8e-11)
    )
    link = int(topo.route[0, 0, 1])
    zero = jnp.zeros((1, topo.n), jnp.float32)
    nxt, _ = shared_fabric_tick(topo, sched, state, zero, jax.random.PRNGKey(0))
    assert float(nxt.queue[0, 0, 1]) == 0.0
    served = float(state.queue[0, 0, 1]) - float(nxt.queue[0, 0, 1])
    assert float(nxt.forward[0, 0, 1]) == served
    assert float(nxt.link_served[link] - state.link_served[link]) == served

    hops = topo.route.shape[0]
    state = nxt
    for t in range(hops + int(topo.latency.max()) + topo.ring_len):
        if bool(fabric_quiescent(state)):
            break
        state, _ = shared_fabric_tick(
            topo, sched, state, zero, jax.random.PRNGKey(t + 1)
        )
    assert bool(fabric_quiescent(state))


def _k8_permutation_topology(seed: int):
    """A k=8 fat-tree (128 hosts, 16 paths) under a uniform host
    permutation with no host sending inside its own edge leaf."""
    from repro.net.topology import fat_tree

    hosts, per_leaf = 128, 4
    leaf = np.arange(hosts) // per_leaf
    rng = np.random.default_rng(seed)
    dst = rng.permutation(hosts)
    while np.any(dst // per_leaf == leaf):
        dst = rng.permutation(hosts)
    pairs = np.stack([leaf, dst // per_leaf], axis=1)
    return fat_tree(8, 4, 4, 4, pairs, uplink_capacity=32.0)


def test_fat_tree_permutation_early_exit_matches_full_horizon():
    """On a k=8 fat-tree permutation the ECMP point's hot links drain to
    exact zeros, so early exit stops within the horizon and every SimResult
    field, link counters included, equals the full-horizon run; no link
    stays busy long after the last flow completed.  Permutation seed 0 with
    draw 0: the former 1e-9-guarded service fraction left ~1e-11-packet
    residues there that kept the ECMP point's links busy to the horizon
    (255 of 256 ticks)."""
    horizon = 256
    topo = _k8_permutation_topology(0)
    sched = null_schedule(topo.links)
    topos, scheds = jax.tree.map(lambda x: x[None], (topo, sched))
    sp = policy_sweep_params((Policy.ECMP, Policy.WAM), rate=32)
    keys = jax.random.split(jax.random.PRNGKey(0), 1)
    runs = [
        sweep_flows_scenarios(
            topos, scheds, SenderSpec(rate_cap=32, early_exit=ee), sp, 256,
            keys, horizon=horizon,
        )
        for ee in (True, False)
    ]
    for field in dataclasses.fields(runs[0]):
        got, want = (np.asarray(getattr(r, field.name)) for r in runs)
        assert np.array_equal(got, want), field.name
    res = runs[0]
    assert bool(np.all(res.finished))
    # a link serves only packets emitted before their flow completed, which
    # cross the fabric within its hops and propagation delay
    slack = topo.route.shape[0] + int(topo.latency.max())
    assert float(np.max(res.link_busy)) <= float(np.max(res.cct)) + slack


# ---------------------------------------------------------------------------
# scenario-axis batching == per-scenario sweeps
# ---------------------------------------------------------------------------
def test_stacked_scenarios_match_per_scenario_sweeps():
    scens = pair_scenarios(flows=2, n_spines=2, horizon=192)
    topos, scheds = stack_scenarios(list(scens.values()))
    spec = SenderSpec(rate_cap=16, early_exit=True)
    sp = policy_sweep_params((Policy.ECMP, Policy.WAM), rate=16)
    keys = jax.random.split(jax.random.PRNGKey(2), 1)
    fam = sweep_flows_scenarios(topos, scheds, spec, sp, 48, keys, horizon=192)
    for i, (name, (topo, sched)) in enumerate(scens.items()):
        one = sweep_flows(topo, sched, spec, sp, 48, keys, horizon=192)
        for field in COMPLETION_FIELDS:
            got = np.asarray(getattr(fam, field))[i]
            want = np.asarray(getattr(one, field))
            assert np.array_equal(got, want), (name, field)


def test_stack_scenarios_extends_schedules_by_last_row():
    scens = pair_scenarios(flows=2, n_spines=2, horizon=32)
    _, scheds = stack_scenarios(list(scens.values()))
    T = scheds.cap_scale.shape[1]
    assert T == 32
    # the null-schedule entries were extended by repeating their only row
    incast_cap = np.asarray(scheds.cap_scale)[0]
    assert np.array_equal(incast_cap, np.ones_like(incast_cap))


def test_stack_scenarios_rejects_mismatched_shapes():
    a = pair_scenarios(flows=2, n_spines=2, horizon=32)["incast"]
    b = pair_scenarios(flows=4, n_spines=2, horizon=32)["incast"]
    with pytest.raises(ValueError, match="not stackable"):
        stack_scenarios([a, b])


def test_stack_scenarios_rejects_mismatched_statics():
    topo, sched = pair_scenarios(flows=2, n_spines=2, horizon=32)["incast"]
    other = dataclasses.replace(topo, fb_delay=topo.fb_delay + 1)
    with pytest.raises(ValueError, match="statics differ"):
        stack_scenarios([(topo, sched), (other, sched)])


def _check_stack_last_row_persistence(seed: int) -> None:
    """Schedule extension is invisible to the fabric: for every tick t the
    extended schedule's read row min(t, T-1) is bit-identical to the
    original's read row min(t, T_i - 1) — the exact invariant that lets
    `stack_scenarios` batch unequal-horizon failure scenarios into one
    compiled family."""
    rng = np.random.default_rng(seed)
    topo = leaf_spine(2, 2, [(0, 1)])
    L = int(topo.capacity.shape[0])
    horizons = [int(h) for h in rng.integers(1, 24, size=3)]
    scens = []
    for T in horizons:
        scens.append((topo, EventSchedule(
            cap_scale=jnp.asarray(
                rng.uniform(0.1, 1.0, (T, L)).astype(np.float32)
            ),
            bg_arrivals=jnp.asarray(
                rng.uniform(0.0, 2.0, (T, L)).astype(np.float32)
            ),
        )))
    _, stacked = stack_scenarios(scens)
    Tmax = max(horizons)
    assert stacked.cap_scale.shape[:2] == (len(scens), Tmax)
    for i, (_, orig) in enumerate(scens):
        for field in ("cap_scale", "bg_arrivals"):
            ext = np.asarray(getattr(stacked, field))[i]
            src = np.asarray(getattr(orig, field))
            Ti = src.shape[0]
            for t in range(Tmax + 4):  # overrun past Tmax: both clamp
                got = ext[min(t, Tmax - 1)]
                want = src[min(t, Ti - 1)]
                assert np.array_equal(got, want), (seed, i, field, t)


if HAVE_HYPOTHESIS:

    @given(st.integers(0, 2**20))
    @settings(max_examples=20, deadline=None)
    def test_stack_scenarios_read_equivalence(seed):
        _check_stack_last_row_persistence(seed)

else:

    @pytest.mark.parametrize("seed", list(range(20)))
    def test_stack_scenarios_read_equivalence(seed):
        _check_stack_last_row_persistence(seed)


# ---------------------------------------------------------------------------
# spray_select: padded final block + small and empty batches
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", [0, 1, 2])
@pytest.mark.parametrize("B", [1, 5, 1000, 1537, 2051])
def test_spray_select_non_multiple_batches(method, B):
    """Any batch size works: the final block is zero-padded and the
    padding lanes' throwaway selections sliced off."""
    ell, n = 10, 7
    prof = quantize_profile(RNG.random(n) + 0.01, ell)
    counters = jnp.asarray(RNG.integers(0, 2**31, B, dtype=np.uint32))
    got = ops.spray_select(
        counters, prof.c, 17, 9, ell=ell, method=method, backend="pallas",
        interpret=True,
    )
    want = ref.spray_select_ref(
        counters, prof.c, 17, 9, ell=ell, method=method
    )
    assert got.shape == (B,)
    assert np.array_equal(np.asarray(got), np.asarray(want)), (method, B)


def test_spray_select_batch_smaller_than_block():
    from repro.kernels.spray_select import spray_select_pallas

    ell, n = 8, 3
    prof = quantize_profile(np.arange(1, n + 1, dtype=float), ell)
    counters = jnp.arange(37, dtype=jnp.uint32)
    got = spray_select_pallas(
        counters, prof.c, 5, 3, ell=ell, method=1, block=256, interpret=True
    )
    want = ref.spray_select_ref(counters, prof.c, 5, 3, ell=ell, method=1)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_spray_select_rejects_empty_batch():
    from repro.kernels.spray_select import spray_select_pallas

    with pytest.raises(ValueError, match="empty"):
        spray_select_pallas(
            jnp.zeros((0,), jnp.uint32), jnp.asarray([1, 2], jnp.int32),
            0, 1, ell=4, method=0, interpret=True,
        )


# ---------------------------------------------------------------------------
# compile-count gate (benchmarks.common)
# ---------------------------------------------------------------------------
def test_compile_gate_trips_on_extra_compiles():
    common = pytest.importorskip("benchmarks.common")

    f = jax.jit(lambda x: x + 1)
    x = jnp.ones((4,))
    with common.compile_gate("one allowed", max_compiles=1):
        common.aot_compile(f, x)
    with pytest.raises(RuntimeError, match="per-scenario compiles"):
        with common.compile_gate("one allowed", max_compiles=1):
            common.aot_compile(f, x)
            common.aot_compile(f, jnp.ones((8,)))


# ---------------------------------------------------------------------------
# graceful-degradation escape (benchmarks.common)
# ---------------------------------------------------------------------------
def test_check_finished_allow_unfinished_records_degraded_rows():
    common = pytest.importorskip("benchmarks.common")

    fin = np.ones((2, 2, 3), bool)
    fin[1, 0, 2] = False
    fin[0, 1, 1] = False
    before = len(common.DEGRADED_STATS)
    try:
        mask = common.check_finished(
            "degradation test", fin,
            axes=("scenario", "policy", "flow"),
            labels={"policy": ["ECMP", "WAM"]},
            allow_unfinished=True,
        )
        np.testing.assert_array_equal(mask, fin)
        rows = common.DEGRADED_STATS[before:]
        assert {tuple(sorted(r["index"].items())) for r in rows} == {
            (("flow", "1"), ("policy", "WAM"), ("scenario", "0")),
            (("flow", "2"), ("policy", "ECMP"), ("scenario", "1")),
        }
        assert all(r["name"] == "degradation test" for r in rows)
    finally:
        del common.DEGRADED_STATS[before:]

    # without the escape the same mask raises, naming the stranded index
    with pytest.raises(RuntimeError, match="policy=WAM"):
        common.check_finished(
            "degradation test", fin,
            axes=("scenario", "policy", "flow"),
            labels={"policy": ["ECMP", "WAM"]},
        )

    # an all-finished mask is returned unchanged and records nothing
    n0 = len(common.DEGRADED_STATS)
    mask = common.check_finished(
        "clean", np.ones((4,), bool), allow_unfinished=True
    )
    assert mask.all() and len(common.DEGRADED_STATS) == n0


def test_sentinel_free_p99_contract():
    common = pytest.importorskip("benchmarks.common")

    horizon = 100
    cct = np.asarray([10.0, 20.0, 100.0, 100.0])
    fin = np.asarray([True, True, False, True])
    # the finished flow at cct == horizon (completed on the last tick) is a
    # legitimate sample; the unfinished sentinel is excluded
    got = common.sentinel_free_p99(cct, fin, horizon, q=50.0)
    assert got == pytest.approx(20.0)

    # nothing finished (all sentinels) -> the metric does not exist
    sentinels = np.full(4, float(horizon))
    assert common.sentinel_free_p99(sentinels, np.zeros(4, bool), horizon) is None

    # an unfinished flow with a sub-horizon cct means mask and ccts came
    # from different runs: hard error, not silent admission
    with pytest.raises(RuntimeError, match="outside the finished mask"):
        common.sentinel_free_p99(
            np.asarray([10.0, 50.0]), np.asarray([True, False]), horizon
        )
    with pytest.raises(ValueError, match="shape"):
        common.sentinel_free_p99(cct, fin[:2], horizon)
