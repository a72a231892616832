"""Unified flow-batched sender engine: ONE tick core, traced policy dispatch.

This module is the single home of the paper's sender semantics (§2, §4-6):
emit budget, spray/path assignment, retransmission debt, the delayed-feedback
profile controller, and completion detection all live in exactly one scan
body (`run_sender`'s `sender_tick`).  Every transport entry point —
`transport.simulate_message`, `transport.simulate_message_on`,
`transport.simulate_flows`, and the swept engines below — is a thin
specialization of that core, so a fix lands everywhere at once.

Configuration splits along the trace boundary:

  * `SenderSpec`   — static, hashable, shape-affecting: reliability mode
                     (coded vs ARQ changes the emit-budget dataflow), spray
                     precision `ell`, spray method, and `rate_cap` (the width
                     of the per-tick emission lanes).  A jit cache key.
  * `SenderParams` — a TRACED pytree: policy (int32 -> `jax.lax.switch`),
                     rate, cwnd, code_overhead, ctrl_interval, spray seeds.
                     Anything here can be swept by `jax.vmap` WITHOUT
                     recompiling — policies x config points x PRNG draws all
                     ride one XLA program.

The one-compile sweep idiom::

    spec = SenderSpec(rate_cap=32)
    sp = policy_sweep_params(rate=32)            # all 5 policies, stacked
    keys = jax.random.split(key, draws)
    r = sweep_flows(topo, sched, spec, sp, n_packets, keys, horizon=2048)
    r.cct                                        # [policies, draws, flows]

Hot-loop fast paths (all bit-identical to the formulations they replaced;
pinned by the golden traces and tests/test_fastpath.py):

  * per-tick PRNG is pre-split into a [horizon] key array (`tick_keys`)
    instead of fold_in+split inside the scan body;
  * path assignment segment-sums the emission lanes onto their paths via
    a branchless compare-count (no float [rate_cap, n] one-hot per tick;
    a literal scatter-add was measured and rejected — XLA:CPU lowers it
    to a serial per-lane loop inside the scan);
  * `SenderSpec(early_exit=True)` scans the horizon in `exit_chunk`-tick
    chunks inside a while_loop that stops once every flow completed, ARQ
    debt drained and the fabric drained (`fabric_quiescent`) — identical
    `cct`/`sent_total`/`dropped_total`/`received`/`finished`, dead ticks
    skipped;
  * `sweep_flows_scenarios` adds a stacked scenario axis on top of the
    policy/draw sweep: a whole scenario library in ONE compiled program
    (see `scenarios.stack_scenarios`).

Policies (§2, §4 + the baselines the paper positions against; the enum and
branch bodies live in `repro.net.policies`, re-exported here):

  * ECMP          — flow-hash: every packet of the flow on one fixed path.
  * RR            — round-robin across all paths, health-blind.
  * RAND_STATIC   — uniform random path per packet (stochastic spraying).
  * RAND_ADAPTIVE — random per the *adaptive* profile (same feedback
                    controller as WaM; isolates determinism from adaptivity).
  * WAM           — Whack-a-Mole: bit-reversal deterministic spray over the
                    adaptive profile (the paper's algorithm).
  * PRIME / STRACK / CC_COUPLED — the literature's adaptive-spraying
                    competitors (arXiv:2507.23012 / 2407.15266 /
                    2509.07907), reading per-path sender state
                    (`repro.net.policy_state`) threaded through the scan
                    carry as zero-width-when-disabled blocks
                    (`SenderSpec.state_blocks`) — the bake-off set.

Reliability modes:
  * coded   — fountain/LT transport: the flow completes when ANY
              need ~= K * (1+overhead) distinct packets arrive (§1-2);
              losses are never retransmitted.
  * arq     — uncoded: drops become retransmission debt after the feedback
              delay (selective-repeat accounting), windowed at `cwnd`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.feedback import (
    ControllerState,
    PathStats,
    controller_step,
    make_controller,
)
from repro.core.profile import PathProfile, uniform_profile
from repro.core.spray import SprayMethod, SprayState
from repro.net.fabric import FabricParams, fabric_tick, init_fabric
from repro.net.policies import (
    ALL_POLICIES,
    BASELINE_POLICIES,
    Policy,
    blocks_for,
    policy_branches,
    profile_adaptive,
)
from repro.net.policy_state import (
    PolicyState,
    init_policy_state,
    update_policy_state,
)
from repro.net.telemetry import TelemetrySpec, init_frame, record
from repro.net.topology import (
    EventSchedule,
    TopologyParams,
    init_shared_fabric,
    link_telemetry,
    shared_fabric_tick,
)

__all__ = [
    "Policy",
    "BASELINE_POLICIES",
    "ALL_POLICIES",
    "SenderSpec",
    "SenderParams",
    "SimResult",
    "spec_for_policies",
    "sender_params",
    "stack_params",
    "policy_sweep_params",
    "completion_need",
    "assign_paths",
    "tick_keys",
    "fabric_quiescent",
    "run_sender",
    "run_message_on",
    "run_message",
    "run_flows",
    "run_flows_sized",
    "sweep_message",
    "sweep_flows",
    "sweep_flows_scenarios",
    "FLOW_AXIS",
    "flow_mesh",
    "shard_run_flows",
    "shard_sweep_flows",
    "shard_sweep_flows_scenarios",
]


@dataclasses.dataclass(frozen=True)
class SenderSpec:
    """Static, shape-affecting sender description (a hashable jit cache key).

    `rate_cap` sizes the per-tick emission lanes: each tick assigns paths to
    up to `rate_cap` packets and masks the first `k_emit` live.  A traced
    `SenderParams.rate <= rate_cap` throttles within those lanes, so sweeps
    over rate share one program sized by the cap.
    """

    coded: bool = True
    ell: int = 10                          # profile precision (m = 2**ell)
    method: SprayMethod = SprayMethod.SHUFFLE_1
    rate_cap: int = 32                     # emission lane width (packets/tick)
    # Early-exit execution mode: scan the horizon in `exit_chunk`-tick
    # chunks inside a while_loop that stops once every flow completed, ARQ
    # debt is drained and the fabric is quiescent (`fabric_quiescent`).
    # Bit-identical to the full-horizon scan on cct / sent_total /
    # dropped_total / received / finished (the stop condition freezes all of
    # them); final_b and the link counters may differ (the controller and
    # background traffic would keep evolving over the skipped dead ticks).
    early_exit: bool = False
    exit_chunk: int = 64                   # ticks per early-exit scan chunk
    # In-scan telemetry: when set, a `TelemetryFrame` rides the sender_tick
    # carry and every engine entry point returns (SimResult, frame) instead
    # of a bare SimResult — decimated per-tick time series captured inside
    # the one compiled program (see repro.net.telemetry).  Capture is
    # observation-only (the SimResult is bit-identical either way) and
    # freezes once the run settles, so early-exit and full-horizon runs
    # record identical series.  None (the default) leaves the engine's
    # code path, carry and outputs untouched.
    telemetry: TelemetrySpec | None = None
    # Per-policy sender state blocks (repro.net.policy_state) enabled for
    # this run: a STATIC canonical tuple (subset of policy_state.BLOCKS),
    # usually `policies.blocks_for(<the policies swept>)` — see
    # `spec_for_policies`.  Disabled blocks are zero-width leaves in the
    # carried PolicyState, and the default () makes the whole state a
    # structural no-op: carry shapes, PRNG streams and outputs are
    # bit-identical to the pre-policy-state engine (golden traces hold).
    # A state-bearing policy (PRIME / STRACK / CC_COUPLED) swept WITHOUT
    # its blocks statically degrades to RAND_STATIC (see
    # policies.policy_branches) — enable the blocks for real comparisons.
    state_blocks: Tuple[str, ...] = ()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SenderParams:
    """Traced sender knobs — a pytree of scalars, `jax.vmap`-able over any
    leading axis (policies, config grid points, PRNG-decorrelated repeats)."""

    policy: jax.Array         # int32 Policy value -> lax.switch branch index
    rate: jax.Array           # int32 emit budget per tick (<= spec.rate_cap)
    cwnd: jax.Array           # float32 ARQ in-flight window
    code_overhead: jax.Array  # float32 fountain reception overhead epsilon
    ctrl_interval: jax.Array  # int32 controller cadence (ticks)
    sa: jax.Array             # uint32 spray seed a
    sb: jax.Array             # uint32 spray seed b (odd)


def sender_params(
    policy: Policy | int,
    *,
    rate: int = 32,
    cwnd: float = 256.0,
    code_overhead: float = 0.05,
    ctrl_interval: int = 4,
    seed: Tuple[int, int] = (333, 735),
) -> SenderParams:
    """Scalar `SenderParams` with the seed transport's defaults."""
    return SenderParams(
        policy=jnp.int32(int(policy)),
        rate=jnp.int32(rate),
        cwnd=jnp.float32(cwnd),
        code_overhead=jnp.float32(code_overhead),
        ctrl_interval=jnp.int32(ctrl_interval),
        sa=jnp.uint32(seed[0]),
        sb=jnp.uint32(seed[1]),
    )


def stack_params(params: Sequence[SenderParams]) -> SenderParams:
    """Stack scalar param pytrees along a new leading sweep axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params)


def policy_sweep_params(
    policies: Sequence[Policy] = BASELINE_POLICIES, **kw
) -> SenderParams:
    """`SenderParams` with a leading policy axis.  Defaults to the five
    baseline policies (the historical all-policies sweep — BENCH history
    and the golden traces pin that axis); pass `ALL_POLICIES` for the
    eight-way bake-off set, pairing it with `spec_for_policies` so the
    state-bearing policies get their blocks."""
    return stack_params([sender_params(p, **kw) for p in policies])


def spec_for_policies(
    spec: SenderSpec, policies: Sequence[Policy | int]
) -> SenderSpec:
    """`spec` with `state_blocks` set to exactly the blocks the given
    policy set reads — the one-liner for wiring a bake-off sweep."""
    return dataclasses.replace(spec, state_blocks=blocks_for(policies))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimResult:
    cct: jax.Array            # float32 — completion tick (or horizon sentinel)
    sent_total: jax.Array     # float32[n]
    dropped_total: jax.Array  # float32[n]
    final_b: jax.Array        # int32[n] final profile allocation
    received: jax.Array       # float32
    # True iff the flow completed within the horizon.  cct == horizon is the
    # sentinel for "did not finish" — without this mask a too-short horizon
    # silently flattens every tail-latency statistic, so gated benchmarks
    # must check it (benchmarks.common.check_finished) and fail loudly.
    finished: jax.Array       # bool
    # cumulative per-link served packets / busy ticks (shared leaf-spine
    # fabric only; empty [0] on the independent-bundle fabric, which has no
    # link concept).  Feed the cluster layer's per-link utilization metric:
    # served / (nominal capacity x busy ticks) is exact and <= 1.
    link_served: jax.Array    # float32[L] or float32[0]
    link_busy: jax.Array      # float32[L] or float32[0]


def completion_need(n_packets, coded: bool, code_overhead) -> jax.Array:
    """Completion threshold shared by every sender entry point.

    Coded flows need ~ceil(K * (1+overhead)) distinct arrivals (§1-2); ARQ
    flows need all K.  The -0.25 is the fluid-model float-residue guard: the
    fabric serves fractional packets during degradation, so an exact integer
    threshold could strand a completion on accumulated float error.

    Tiny messages are guarded: for n_packets <= 4 the coded overhead is
    waived (a 1-packet message must not require 2 arrivals), and n_packets
    == 0 yields a non-positive threshold so the flow completes at tick 0
    rather than running to the horizon sentinel.
    """
    npk = jnp.asarray(n_packets, jnp.float32)
    if coded:
        # floor(K + K*eps), NOT floor(K * (1+eps)): adding eps to 1 in
        # float32 discards eps's low mantissa bits, which biases the product
        # low and flips the floor whenever K*(1+eps) lands on an integer
        # (every K divisible by 20 at the default eps=0.05).  The split form
        # keeps K exact and rounds only the small overhead term, matching
        # the historical float64 int(K * (1+eps)) threshold.
        overhead = npk * jnp.asarray(code_overhead, jnp.float32)
        need = jnp.floor(npk + overhead) + 1.0
    else:
        need = npk
    need = jnp.where(npk <= 4.0, npk, need)
    return need - 0.25


def assign_paths(
    rate_cap: int,
    n: int,
    policy: jax.Array,
    spray: SprayState,
    profile: PathProfile,
    k_emit: jax.Array,
    key: jax.Array,
    ecmp_path: jax.Array,
    pstate: PolicyState | None = None,
):
    """Choose a path for each of up to rate_cap packets (first k_emit valid).

    `policy` is TRACED: dispatch is a `jax.lax.switch` over the branch list
    built by `policies.policy_branches`, so one compiled program serves all
    eight policies and vmaps over a policy axis.  `pstate` carries the
    per-policy state blocks the PRIME/STRACK/CC_COUPLED branches read; None
    (the stateless callers' default) builds an all-disabled state, under
    which those branches statically degrade to RAND_STATIC.  Returns
    (arrivals[n] float32, spray') — the spray counter advances by k_emit so
    the WaM sequence is exactly the paper's (no holes).
    """
    if pstate is None:
        pstate = init_policy_state(
            (), (), n, latency=jnp.zeros((n,), jnp.float32), sa=spray.sa
        )
    live = jnp.arange(rate_cap) < k_emit  # [rate_cap]

    paths = jax.lax.switch(policy, policy_branches(
        rate_cap, n, spray, profile, key, ecmp_path, pstate
    ))
    # segment-sum of the live lanes onto their paths as a branchless
    # compare-count (the spray_select kernel's sum-of-comparisons idiom):
    # bit-identical to the historical one_hot(paths, n) float reduction
    # (0/1 contributions sum exactly in any order).  Measured on XLA:CPU
    # this beats both that float einsum and a `.at[paths].add` scatter —
    # scatter lowers to a serial per-lane loop inside the hot scan body.
    hits = (paths[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None])
    arrivals = jnp.sum(hits & live[None, :], axis=1).astype(jnp.float32)
    spray = dataclasses.replace(spray, j=spray.j + k_emit.astype(jnp.uint32))
    return arrivals, spray


def tick_keys(k_loop: jax.Array, horizon: int) -> jax.Array:
    """Pre-split the per-tick PRNG keys, hoisted out of the scan body.

    Bit-identical to the historical in-loop ``split(fold_in(k_loop, t))``:
    fold_in and split are deterministic functions of (key, tick), so
    vmapping them over the tick index yields exactly the key stream the
    per-tick derivation produced — the scan body then just reads its slice
    instead of re-hashing the loop key every tick.  Returns the stacked
    split outputs with a leading [horizon] axis (row t = (ka_t, kb_t)).
    """
    return jax.vmap(
        lambda t: jax.random.split(jax.random.fold_in(k_loop, t))
    )(jnp.arange(horizon))


def fabric_quiescent(state) -> jax.Array:
    """True when no flow traffic is left anywhere in the fabric state.

    Checks the queue backlog, the delivery ring, and the pending-drop
    feedback ring (plus the store-and-forward pipeline on fabrics that have
    one) — the pieces that could still emit, drop, or deliver a flow packet
    on a later tick.  Combined with "every flow done" (and "ARQ debt
    drained"), this is the early-exit stop condition: once it holds, no
    completion-relevant SimResult field can change again.
    """
    parts = [state.queue, state.arrive_ring, state.drop_ring]
    forward = getattr(state, "forward", None)
    if forward is not None:
        parts.append(forward)
    quiet = jnp.all(parts[0] == 0)
    for p in parts[1:]:
        quiet = quiet & jnp.all(p == 0)
    return quiet


def _settled(spec, carry) -> jax.Array:
    """The early-exit stop condition on a bare sender carry: every flow
    completed, ARQ debt drained (uncoded only), fabric quiescent.  Once it
    holds it holds forever (completed flows stop emitting, nothing is left
    to drop or deliver), which is what makes both early exit and the
    telemetry capture freeze sound.  (The policy-state blocks keep evolving
    from the feedback stream after settle, like the controller profile —
    neither participates in the stop condition nor in any completion-
    relevant output.)"""
    fabric, _ctrl, _spray, _sched, debt, done_at, _sent, _known, _ps = carry
    done = jnp.all(done_at >= 0) & fabric_quiescent(fabric)
    if not spec.coded:
        done = done & jnp.all(debt == 0)
    return done


def _scan_early_exit(spec, sender_tick, carry0, tkeys, horizon: int,
                     settled: Callable):
    """Run `sender_tick` over the horizon with early termination.

    Chunked `lax.scan` inside a `lax.while_loop`: after each `exit_chunk`
    ticks the loop re-checks the stop condition `settled(carry)` (see
    `_settled` — every flow completed (`done_at >= 0`), retransmission
    debt drained (ARQ only), and the fabric quiescent
    (`fabric_quiescent`)).  Once that holds, no further tick can emit,
    drop or deliver a flow packet, so skipping the remaining ticks is
    bit-identical on every completion-relevant field; a carry that never
    settles runs all ceil-chunks and matches the full scan exactly.  The
    tail ticks (horizon % exit_chunk) always run: on a settled carry they
    are no-ops on those fields, on an unsettled one they are the last
    ticks of the horizon.  Under vmap the while_loop runs until every batch
    element settles, with settled elements' carries frozen by the batching
    rule's select — the invariant above keeps those extra body applications
    observation-free.  (Telemetry-wrapped carries gate capture on the same
    predicate, so their frames also stop changing at settle — the invariant
    extends to the whole carry.)
    """
    chunk = max(1, min(spec.exit_chunk, horizon))
    n_full, rem = divmod(horizon, chunk)

    def cond(loop):
        i, carry = loop
        return (i < n_full) & ~settled(carry)

    def body(loop):
        i, carry = loop
        ks = jax.lax.dynamic_slice_in_dim(tkeys, i * chunk, chunk)
        carry, _ = jax.lax.scan(sender_tick, carry, ks)
        return (i + 1, carry)

    with jax.named_scope("engine/chunk"):
        _, carry = jax.lax.while_loop(cond, body, (jnp.int32(0), carry0))
        if rem:
            carry, _ = jax.lax.scan(
                sender_tick, carry, tkeys[n_full * chunk:]
            )
    return carry


def run_sender(
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    horizon: int,
    *,
    lead: Tuple[int, ...],
    n: int,
    fabric0,
    stepper: Callable,
    latency_f: jax.Array,
    spray0: SprayState,
    ctrl0: ControllerState,
    ecmp_path: jax.Array,
    assign_fn: Callable,
    ctrl_update: Callable,
    received_fn: Callable,
    dropped_fn: Callable,
    k_loop: jax.Array,
    link_fn: Callable | None = None,
    tel_link_fn: Callable | None = None,
    settle_reduce: Callable | None = None,
) -> SimResult:
    """THE sender tick core, generic over a leading flow axis `lead`.

    Per-flow scalars have shape `lead` (() for one flow, (F,) for coupled
    flows); per-path arrays have shape `lead + (n,)`.  `n_packets` may be a
    Python int, a traced scalar, or a traced array of shape `lead` (per-flow
    message sizes — the cluster layer's heterogeneous-job plumbing); it only
    feeds arithmetic, nothing shape-depends on it.  The specializations
    differ only in their initial states and in the injected callables:

      * stepper(fabric, arrivals, key) -> (fabric', fb) — the fabric, any
        model honouring the `fabric_tick` feedback contract.
      * assign_fn(spray, pstate, profile, k_emit, key, ecmp_path) — path
        assignment (the F-flow engine vmaps `assign_paths` and splits the
        tick key per flow; the single-flow engine binds it directly).
        `pstate` is the carried per-policy state (`spec.state_blocks`
        sizes its blocks; zero-width when disabled).
      * ctrl_update(ctrl, stats) -> ctrl — profile controller step (vmapped
        over flows where applicable).
      * received_fn / dropped_fn — read completion/drop totals out of the
        (otherwise opaque) fabric state.
      * link_fn — read cumulative per-link (served packets, busy ticks) out
        of the fabric state (None: no link concept, report empty [0] arrays).
      * tel_link_fn — telemetry reader of per-link (queue, served, dropped,
        ecn) out of the fabric state (None: no link concept, the telemetry
        frame's link channels stay zero-width).
      * settle_reduce — applied to `_settled`'s local predicate before the
        early-exit while_loop tests it.  The flow-sharded engine passes a
        `lax.psum`-based all-shards reduction here so every device agrees on
        the trip count (a per-device predicate would desynchronize the
        all_gather collectives inside the loop body) — and because the
        global stop condition is simply the AND of the local ones, the
        sharded run executes exactly the chunk count of the unsharded run.

    With `spec.telemetry` set, a `TelemetryFrame` rides the scan carry and
    the return value is ``(SimResult, frame)``; capture happens after each
    tick, gated on ``(~settled_before_the_tick) & (t % stride == 0)`` — the
    settle gate makes the recorded series independent of whether the engine
    early-exits the dead ticks.

    Everything in `sp` is traced: the policy runs through `lax.switch`
    inside `assign_fn`, and non-adaptive policies simply never take the
    controller branch, leaving the profile at its uniform initial value —
    identical to the historical static dispatch, but sweepable.
    """
    need = completion_need(n_packets, spec.coded, sp.code_overhead)
    rate = jnp.minimum(sp.rate, spec.rate_cap)  # lanes are rate_cap wide
    adaptive = profile_adaptive(sp.policy)
    with jax.named_scope("engine/keys"):
        tkeys = tick_keys(k_loop, horizon)
    pstate0 = init_policy_state(
        spec.state_blocks, lead, n, latency=latency_f, sa=spray0.sa
    )

    def sender_tick(carry, kt):
        # Named scopes label the tick's ops in the compiled HLO's metadata
        # (`tick/assign`, `tick/fabric`, `tick/sender`), which is how the
        # benchmark's trace reduction attributes device time; they change
        # no op.
        with jax.named_scope("tick"):
            with jax.named_scope("sender"):
                (
                    fabric, ctrl, spray, sent_sched, debt, done_at, sent_pp,
                    known, pstate,
                ) = carry
                t = fabric.t
                ka, kb = kt[0], kt[1]

                # --- emit budget ---
                if spec.coded:
                    # keep the pipe full until completion
                    k_emit = jnp.where(done_at >= 0, 0, rate).astype(
                        jnp.int32
                    )
                else:
                    outstanding = (
                        jnp.maximum(n_packets - sent_sched, 0.0) + debt
                    )
                    known_delivered, known_dropped = known
                    in_flight = (
                        jnp.sum(sent_pp, axis=-1) - known_delivered
                        - known_dropped
                    )
                    room = jnp.maximum(sp.cwnd - in_flight, 0.0)
                    # ceil: the fabric is a fluid model (fractional service
                    # during degradation), but the sender emits whole
                    # packets — rounding debt down would strand a
                    # fractional residue short of completion.
                    k_emit = jnp.ceil(
                        jnp.minimum(
                            jnp.minimum(outstanding, room),
                            rate.astype(jnp.float32),
                        )
                    ).astype(jnp.int32)

            # --- spray / path assignment (traced-policy lax.switch) ---
            with jax.named_scope("assign"):
                arrivals, spray = assign_fn(
                    spray, pstate, ctrl.profile, k_emit, ka, ecmp_path
                )
            with jax.named_scope("sender"):
                sent_pp = sent_pp + arrivals
            with jax.named_scope("fabric"):
                fabric, fb = stepper(fabric, arrivals, kb)

            with jax.named_scope("sender"):
                # --- per-policy state blocks <- delayed per-path feedback
                # Statically skipped when no block is enabled (the
                # default), which is what keeps the stateless engine — and
                # the goldens — untouched.  The update runs every tick
                # (unlike the profile controller's cadence) and consumes NO
                # PRNG; tick t's assignment above read the state as of tick
                # t-1's feedback.
                if spec.state_blocks:
                    sent_m = jnp.maximum(fb["sent"], 1e-6)
                    seen1 = jnp.minimum(fb["sent"], 1.0)
                    pstate = update_policy_state(
                        pstate,
                        ecn_rate=fb["marked"] / sent_m * seen1,
                        loss_rate=fb["dropped"] / sent_m * seen1,
                        rtt_sample=latency_f + fb["qdelay"],
                        seen=fb["sent"] > 0,
                    )

                # --- retransmission debt (uncoded): NACKed drops re-enter
                # the stream
                new_debt = debt + jnp.sum(fb["dropped"], axis=-1) - (
                    jnp.maximum(
                        k_emit - jnp.maximum(n_packets - sent_sched, 0.0),
                        0.0,
                    )
                )
                new_debt = jnp.maximum(new_debt, 0.0)
                sent_sched = sent_sched + k_emit

                # --- delayed feedback -> profile controller (adaptive
                # policies)
                def do_ctrl(c):
                    sent = jnp.maximum(fb["sent"], 1e-6)
                    stats = PathStats(
                        ecn_rate=fb["marked"] / sent
                        * jnp.minimum(fb["sent"], 1.0),
                        loss_rate=fb["dropped"] / sent
                        * jnp.minimum(fb["sent"], 1.0),
                        rtt=latency_f + fb["qdelay"],
                    )
                    return ctrl_update(c, stats)

                ctrl = jax.lax.cond(
                    adaptive & ((t % sp.ctrl_interval) == 0),
                    do_ctrl, lambda c: c, ctrl,
                )

                # --- completion detection ---
                known = (
                    known[0] + fb["landed"],
                    known[1] + jnp.sum(fb["dropped"], axis=-1),
                )
                done_now = (received_fn(fabric) >= need) & (done_at < 0)
                done_at = jnp.where(
                    done_now, t.astype(jnp.int32) + 1, done_at
                )
                return (
                    fabric, ctrl, spray, sent_sched, new_debt, done_at,
                    sent_pp, known, pstate,
                ), None

    zeros = jnp.zeros(lead, jnp.float32)
    # empty messages (need <= 0) complete at tick 0, not the horizon sentinel
    done_at0 = jnp.broadcast_to(
        jnp.where(need <= 0.0, 0, -1).astype(jnp.int32), lead
    )
    carry0 = (
        fabric0,
        ctrl0,
        spray0,
        zeros,
        zeros,
        done_at0,
        jnp.zeros(lead + (n,), jnp.float32),
        (zeros, zeros),
        pstate0,
    )
    def settled_fn(c):
        with jax.named_scope("engine/settle"):
            done = _settled(spec, c)
            return done if settle_reduce is None else settle_reduce(done)

    tspec = spec.telemetry
    if tspec is None:
        if spec.early_exit:
            carry = _scan_early_exit(
                spec, sender_tick, carry0, tkeys, horizon, settled_fn
            )
        else:
            carry, _ = jax.lax.scan(sender_tick, carry0, tkeys)
        frame = None
    else:
        links = 0
        if tspec.links and tel_link_fn is not None:
            links = int(tel_link_fn(fabric0)[0].shape[-1])
        tel0 = init_frame(
            tspec, lead, n, links,
            pen_width=pstate0.penalty.shape[-1],
            ccw_width=pstate0.ccw.shape[-1],
        )
        m = 1 << spec.ell

        def tel_tick(wcarry, kt):
            base, tel = wcarry
            # settle is ABSORBING (see _settled), so gating capture on the
            # pre-tick predicate suppresses exactly the dead ticks an
            # early-exit run would skip: the recorded series is identical
            # in both execution modes, and a denser stride's samples are a
            # superset of a coarser one's.
            settled_pre = _settled(spec, base)
            t_pre = base[0].t
            base, _ = sender_tick(base, kt)
            (
                fabric, ctrl, spray, sent_sched, debt, done_at, sent_pp, _,
                pstate,
            ) = base
            capture = (~settled_pre) & ((t_pre % tspec.stride) == 0)
            link = None
            if tspec.links and tel_link_fn is not None:
                link = tel_link_fn(fabric)
            tel = record(
                tspec, tel, capture,
                tick=t_pre, m=m,
                alloc=ctrl.profile.b,
                sent_pp=sent_pp,
                dropped_pp=dropped_fn(fabric),
                debt=debt,
                emitted=sent_sched,
                received=received_fn(fabric),
                j=spray.j,
                link=link,
                pen=pstate.penalty,
                ccw=pstate.ccw,
            )
            return (base, tel), None

        if spec.early_exit:
            carry, frame = _scan_early_exit(
                spec, tel_tick, (carry0, tel0), tkeys, horizon,
                lambda wc: settled_fn(wc[0]),
            )
        else:
            (carry, frame), _ = jax.lax.scan(tel_tick, (carry0, tel0), tkeys)
    with jax.named_scope("engine/result"):
        (fabric, ctrl, _, _, _, done_at, sent_pp, _, _) = carry
        cct = jnp.where(
            done_at >= 0, done_at.astype(jnp.float32), float(horizon)
        )
        if link_fn is not None:
            link_served, link_busy = link_fn(fabric)
        else:
            link_served = link_busy = jnp.zeros((0,), jnp.float32)
        result = SimResult(
            cct=cct,
            sent_total=sent_pp,
            dropped_total=dropped_fn(fabric),
            final_b=ctrl.profile.b,
            received=received_fn(fabric),
            finished=done_at >= 0,
            link_served=link_served,
            link_busy=link_busy,
        )
    return result if frame is None else (result, frame)


def run_message_on(
    fabric0,
    stepper,
    latency: jax.Array,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    key: jax.Array,
    horizon: int = 4096,
    *,
    received_fn=None,
    dropped_fn=None,
) -> SimResult:
    """Single-flow (lead=()) specialization over an arbitrary fabric stepper.

    `stepper(state, arrivals[n], key) -> (state', fb)` must honour the
    `fabric_tick` feedback contract; `fabric0` is its initial state.
    `received_fn` / `dropped_fn` read the cumulative delivered scalar and
    per-path drop vector out of the (otherwise opaque) fabric state —
    defaults match `FabricState`; shared-fabric adapters override them.
    Not jitted itself: call from a jitted wrapper with static spec/sizes.
    """
    n = int(latency.shape[-1])
    if received_fn is None:
        received_fn = lambda s: s.received  # noqa: E731
    if dropped_fn is None:
        dropped_fn = lambda s: s.dropped  # noqa: E731
    ctrl0 = make_controller(uniform_profile(n, spec.ell))
    # normalize the traced seed exactly like flow 0 of `run_flows`: sa into
    # [0, m), sb odd — seeds are traced so a host-side ValueError can't
    # guard them here (concrete configs validate in TransportConfig).
    mask = jnp.uint32((1 << spec.ell) - 1)
    spray0 = SprayState(
        j=jnp.uint32(0),
        sa=sp.sa & mask,
        sb=(sp.sb & mask) | jnp.uint32(1),
        path_seq=jnp.zeros((n,), jnp.int32),
        ell=spec.ell,
        method=int(spec.method),
    )
    k_hash, k_loop = jax.random.split(key)
    ecmp_path = jax.random.randint(k_hash, (), 0, n, jnp.int32)

    def assign_fn(spray, pstate, profile, k_emit, ka, ecmp):
        return assign_paths(
            spec.rate_cap, n, sp.policy, spray, profile, k_emit, ka, ecmp,
            pstate,
        )

    def ctrl_update(c, stats):
        c2, _ = controller_step(c, stats)
        return c2

    return run_sender(
        spec, sp, n_packets, horizon,
        lead=(), n=n,
        fabric0=fabric0, stepper=stepper,
        latency_f=latency.astype(jnp.float32),
        spray0=spray0, ctrl0=ctrl0, ecmp_path=ecmp_path,
        assign_fn=assign_fn, ctrl_update=ctrl_update,
        received_fn=received_fn, dropped_fn=dropped_fn,
        k_loop=k_loop,
    )


@functools.partial(jax.jit, static_argnames=("spec", "n_packets", "horizon"))
def run_message(
    params: FabricParams,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    key: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """Single-flow message transfer on the independent-bundle fabric, with
    every `SenderParams` field traced (vmap-able; see `sweep_message`)."""
    return run_message_on(
        init_fabric(params),
        functools.partial(fabric_tick, params),
        params.latency,
        spec, sp, n_packets, key, horizon,
    )


def _run_flows(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets,
    key: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """Shared body of `run_flows` / `run_flows_sized` — see `run_flows`.

    `n_packets` may be a Python int (the static-size jit below) or a traced
    int32 scalar (`run_flows_sized`): the sender core only does arithmetic
    with it, nothing shape-depends on the message size.
    """
    F, n = topo.flows, topo.n
    m = 1 << spec.ell
    mask = jnp.uint32(m - 1)
    fidx = jnp.arange(F, dtype=jnp.uint32)
    ctrl0 = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (F,) + x.shape),
        make_controller(uniform_profile(n, spec.ell)),
    )
    spray0 = SprayState(
        j=jnp.zeros((F,), jnp.uint32),
        sa=(sp.sa + fidx * jnp.uint32(0x9E3779B9)) & mask,
        sb=((sp.sb + 2 * fidx) & mask) | jnp.uint32(1),
        path_seq=jnp.zeros((F, n), jnp.int32),
        ell=spec.ell,
        method=int(spec.method),
    )
    k_hash, k_loop = jax.random.split(key)
    ecmp_path = jax.random.randint(k_hash, (F,), 0, n, jnp.int32)

    vassign = jax.vmap(
        functools.partial(assign_paths, spec.rate_cap, n, sp.policy)
    )

    def assign_fn(spray, pstate, profile, k_emit, ka, ecmp):
        return vassign(
            spray, profile, k_emit, jax.random.split(ka, F), ecmp, pstate
        )

    def ctrl_update(c, stats):
        def one(ci, si):
            c2, _ = controller_step(ci, si)
            return c2

        return jax.vmap(one)(c, stats)

    return run_sender(
        spec, sp, n_packets, horizon,
        lead=(F,), n=n,
        fabric0=init_shared_fabric(topo),
        stepper=functools.partial(shared_fabric_tick, topo, sched),
        latency_f=topo.latency.astype(jnp.float32),
        spray0=spray0, ctrl0=ctrl0, ecmp_path=ecmp_path,
        assign_fn=assign_fn, ctrl_update=ctrl_update,
        received_fn=lambda s: s.received, dropped_fn=lambda s: s.dropped,
        k_loop=k_loop, link_fn=lambda s: (s.link_served, s.link_busy),
        tel_link_fn=lambda s: link_telemetry(topo, s),
    )


@functools.partial(jax.jit, static_argnames=("spec", "n_packets", "horizon"))
def run_flows(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    key: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """F coupled flows (lead=(F,)), one `n_packets` message each, on one
    shared fabric — the same `sender_tick` core vmapped per flow for path
    assignment and control, with ALL arrivals feeding `shared_fabric_tick`
    so one flow's burst raises the queues every other flow sees.

    Flows decorrelate their spray seeds (paper §4: per-source (sa, sb));
    flow 0 keeps `sp`'s seed.  Returns a SimResult with a leading F axis on
    every field (`cct[F]`, `sent_total[F, n]`, ...).
    """
    return _run_flows(topo, sched, spec, sp, n_packets, key, horizon)


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def run_flows_sized(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: jax.Array,
    key: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """`run_flows` with the message size TRACED (int32 scalar or [F] vector).

    Nothing in the sender core shape-depends on `n_packets` — it only feeds
    the completion threshold and the ARQ emit budget — so the payload can be
    a `jax.vmap` axis like any `SenderParams` field.  This is what lets the
    job layer (`repro.net.jobs`) run several model configs' collective
    schedules (different shard sizes per model and per phase) as ONE
    compiled program per scenario instead of one per distinct size.

    A PER-FLOW `n_packets[F]` gives each coupled flow its own message size:
    flows with size 0 complete at tick 0 and emit nothing, which is how the
    cluster layer (`repro.net.cluster`) runs several co-scheduled jobs'
    concurrently-active ring steps — each flow tagged with its owning job —
    as one coupled simulation where idle/not-yet-started jobs are silent.
    """
    return _run_flows(topo, sched, spec, sp, n_packets, key, horizon)


@functools.partial(jax.jit, static_argnames=("spec", "n_packets", "horizon"))
def sweep_message(
    params: FabricParams,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    keys: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """ONE compiled sweep on the independent-bundle fabric: `sp` carries a
    leading sweep axis P (policies / config points), `keys` is [D, 2] PRNG
    draws — SimResult fields gain leading [P, D] axes."""
    return jax.vmap(
        lambda s: jax.vmap(
            lambda k: run_message(params, spec, s, n_packets, k, horizon)
        )(keys)
    )(sp)


@functools.partial(jax.jit, static_argnames=("spec", "n_packets", "horizon"))
def sweep_flows(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    keys: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """ONE compiled sweep on the shared fabric: P sweep points x D draws x F
    coupled flows without a Python loop or a recompile — `cct[P, D, F]`."""
    return jax.vmap(
        lambda s: jax.vmap(
            lambda k: run_flows(topo, sched, spec, s, n_packets, k, horizon)
        )(keys)
    )(sp)


@functools.partial(jax.jit, static_argnames=("spec", "n_packets", "horizon"))
def sweep_flows_scenarios(
    topos: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets: int,
    keys: jax.Array,
    horizon: int = 4096,
) -> SimResult:
    """`sweep_flows` with a leading SCENARIO axis on the topology/schedule.

    `topos` / `scheds` carry stacked per-scenario arrays (uniform shapes —
    see `scenarios.stack_scenarios`), so the whole scenario library x P
    sweep points x D draws x F flows compiles into ONE XLA program instead
    of one per scenario: `cct[C, P, D, F]`.  Scenario c runs exactly the
    computation `sweep_flows(topos[c], scheds[c], ...)` would — the
    scenario axis is an outer vmap, not a semantic change.
    """
    return jax.vmap(
        lambda tp, sc: _sweep_flows_traced(
            tp, sc, spec, sp, n_packets, keys, horizon
        )
    )(topos, scheds)


def _sweep_flows_traced(
    topo, sched, spec, sp, n_packets, keys, horizon
) -> SimResult:
    """Unjitted `sweep_flows` body (vmap-able over topology pytrees)."""
    return jax.vmap(
        lambda s: jax.vmap(
            lambda k: _run_flows(topo, sched, spec, s, n_packets, k, horizon)
        )(keys)
    )(sp)


# --------------------------------------------------------------------------
# Flow-sharded execution: shard_map over multiple devices.
#
# The flow axis is split into contiguous blocks, one per device; every
# INPUT is replicated (the topology, schedule, params and keys are small —
# the win is splitting the per-flow scan work F/N ways, not the memory).
# Bit-identity with the unsharded engine is BY CONSTRUCTION:
#
#   * every per-flow PRNG stream (the per-tick `split(ka, F)` fan-out, the
#     ECMP hash draw, the fidx-derived spray seeds) is derived at the REAL
#     flow count F and then padded/sliced — only the partitionable
#     threefry (JAX 0.9's default) is split-count-prefix-stable; under the
#     other mode `split(k, F_pad)[:F] != split(k, F)`, so deriving at the
#     padded count would silently change every flow's randomness;
#   * the two per-link segment-sums inside `shared_fabric_tick` all_gather
#     the flow axis first (`axis_name=`/`route_global=`), reproducing the
#     unsharded scatter-add in the exact same float order — so the global
#     drop/serve fractions, and through them every local per-flow value,
#     match the unsharded run bit for bit;
#   * padding flows (F not divisible by the device count) carry n_packets
#     0: `completion_need` goes non-positive, they complete at tick 0, emit
#     nothing, and contribute exact +0.0 to every link sum;
#   * the early-exit stop predicate is psum-reduced across shards
#     (`settle_reduce`), so every device runs the unsharded chunk count.
#
# `telemetry` is not supported on this path (frames would need their own
# gather plumbing); the unsharded engine remains the observability path.
# --------------------------------------------------------------------------

FLOW_AXIS = "flows"


def flow_mesh(n_devices: int | None = None):
    """A 1-D device mesh over the `FLOW_AXIS` used by the shard_* engines:
    the first `n_devices` devices of the platform JAX runs on (default:
    every device).

    On a TPU host these are the chips present, and asking for more is an
    error.  On the CPU they are host devices, which exist only if
    ``--xla_force_host_platform_device_count=N`` was in ``XLA_FLAGS`` BEFORE
    jax initialized — the entry points' ``--devices N`` arranges that under
    ``JAX_PLATFORMS=cpu`` (see `repro.launch.devices`).
    """
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            platform = devs[0].platform
            hint = (
                f" — on the CPU, pass --devices {n_devices} to the entry "
                "point under JAX_PLATFORMS=cpu, which forces that many host "
                "devices before jax initializes"
                if platform == "cpu" else ""
            )
            raise ValueError(
                f"flow_mesh: {n_devices} devices requested but the "
                f"{platform} platform has only {len(devs)}{hint}"
            )
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.asarray(devs), (FLOW_AXIS,))


def _pad_flow_axis(x: jax.Array, F_pad: int, axis: int, fill=None):
    """Pad `axis` (the flow axis) of `x` up to F_pad — edge-repeat by
    default (valid link ids / keys / paths), constant `fill` on request."""
    pad = F_pad - x.shape[axis]
    if pad == 0:
        return x
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    if fill is None:
        return jnp.pad(x, width, mode="edge")
    return jnp.pad(x, width, constant_values=fill)


def _pad_topology(topo: TopologyParams, F_pad: int) -> TopologyParams:
    """Pad the per-flow leaves (route [..., F, n], latency [..., F, n]) up
    to F_pad flows.  Edge-repeat keeps the padded routes valid link ids;
    padded flows never emit, so their +0.0 link contributions are exact."""
    return dataclasses.replace(
        topo,
        route=_pad_flow_axis(topo.route, F_pad, topo.route.ndim - 2),
        latency=_pad_flow_axis(topo.latency, F_pad, topo.latency.ndim - 2),
    )


def _local_flow_run(spec: SenderSpec, horizon: int, F: int, n_shards: int):
    """Build the per-shard sender body (the `_run_flows` of one flow block).

    The returned ``run(topo_g, sched, sp, npk_g, key)`` expects fully
    REPLICATED, flow-padded global inputs and computes the SimResult of its
    own contiguous flow block (`lax.axis_index(FLOW_AXIS)`), coupling with
    the other shards only through the all_gathered link sums and the
    psum-reduced settle predicate.  It runs identically under
    `shard_map(..., mesh=flow_mesh(N))` and under the device-free test
    emulation ``jax.vmap(run, in_axes=None, axis_name=FLOW_AXIS,
    axis_size=N)`` — vmap implements the same collectives, which is what
    lets tier-1 pin sharded-vs-unsharded bit-identity on a 1-device host.
    """
    if spec.telemetry is not None:
        raise NotImplementedError(
            "telemetry capture is not supported on the flow-sharded path; "
            "use the unsharded engine for observability runs"
        )

    def run(topo_g, sched, sp, npk_g, key):
        F_pad = topo_g.route.shape[1]
        F_loc = F_pad // n_shards
        n = topo_g.n
        lo = jax.lax.axis_index(FLOW_AXIS) * F_loc

        def local(x, axis=0):
            return jax.lax.dynamic_slice_in_dim(x, lo, F_loc, axis=axis)

        topo_l = dataclasses.replace(
            topo_g,
            route=local(topo_g.route, 1),
            latency=local(topo_g.latency, 0),
        )
        npk_l = local(npk_g)
        mask = jnp.uint32((1 << spec.ell) - 1)
        fidx = local(jnp.arange(F_pad, dtype=jnp.uint32))
        ctrl0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (F_loc,) + x.shape),
            make_controller(uniform_profile(n, spec.ell)),
        )
        spray0 = SprayState(
            j=jnp.zeros((F_loc,), jnp.uint32),
            sa=(sp.sa + fidx * jnp.uint32(0x9E3779B9)) & mask,
            sb=((sp.sb + 2 * fidx) & mask) | jnp.uint32(1),
            path_seq=jnp.zeros((F_loc, n), jnp.int32),
            ell=spec.ell,
            method=int(spec.method),
        )
        k_hash, k_loop = jax.random.split(key)
        ecmp_path = local(_pad_flow_axis(
            jax.random.randint(k_hash, (F,), 0, n, jnp.int32), F_pad, 0
        ))

        vassign = jax.vmap(
            functools.partial(assign_paths, spec.rate_cap, n, sp.policy)
        )

        def assign_fn(spray, pstate, profile, k_emit, ka, ecmp):
            # split at the REAL flow count (see the module-section comment),
            # pad, then take this shard's block
            kf = _pad_flow_axis(jax.random.split(ka, F), F_pad, 0)
            return vassign(spray, profile, k_emit, local(kf), ecmp, pstate)

        def ctrl_update(c, stats):
            def one(ci, si):
                c2, _ = controller_step(ci, si)
                return c2

            return jax.vmap(one)(c, stats)

        def stepper(state, arrivals, kb):
            return shared_fabric_tick(
                topo_l, sched, state, arrivals, kb,
                axis_name=FLOW_AXIS, route_global=topo_g.route,
            )

        def settle_reduce(p):
            return jax.lax.psum(p.astype(jnp.int32), FLOW_AXIS) == n_shards

        return run_sender(
            spec, sp, npk_l, horizon,
            lead=(F_loc,), n=n,
            fabric0=init_shared_fabric(topo_l),
            stepper=stepper,
            latency_f=topo_l.latency.astype(jnp.float32),
            spray0=spray0, ctrl0=ctrl0, ecmp_path=ecmp_path,
            assign_fn=assign_fn, ctrl_update=ctrl_update,
            received_fn=lambda s: s.received, dropped_fn=lambda s: s.dropped,
            k_loop=k_loop, link_fn=lambda s: (s.link_served, s.link_busy),
            settle_reduce=settle_reduce,
        )

    return run


def _flow_out_specs(n_lead: int) -> SimResult:
    """SimResult of PartitionSpecs: flow-axis fields sharded at position
    `n_lead` (after the sweep axes), link counters replicated (every shard
    computes the identical global values from the gathered sums)."""
    P = jax.sharding.PartitionSpec
    f = P(*([None] * n_lead + [FLOW_AXIS]))
    r = P()
    return SimResult(
        cct=f, sent_total=f, dropped_total=f, final_b=f,
        received=f, finished=f, link_served=r, link_busy=r,
    )


def _strip_flow_pad(r: SimResult, F: int, axis: int) -> SimResult:
    def cut(x):
        return jax.lax.slice_in_dim(x, 0, F, axis=axis)

    return SimResult(
        cct=cut(r.cct), sent_total=cut(r.sent_total),
        dropped_total=cut(r.dropped_total), final_b=cut(r.final_b),
        received=cut(r.received), finished=cut(r.finished),
        link_served=r.link_served, link_busy=r.link_busy,
    )


def _shard_call(topo, sched, spec, sp, n_packets, key_or_keys, horizon,
                mesh, inner, n_lead: int) -> SimResult:
    """Common shard_map plumbing: pad the flow axis to a device multiple,
    run `inner(local_run, topo_g, sched, sp, npk_g, keys)` — which wraps the
    per-shard body in the wrapper's sweep vmaps — under a fully-replicated
    shard_map, then slice the padding back off."""
    from jax.experimental.shard_map import shard_map

    n_shards = int(mesh.shape[FLOW_AXIS])
    F = int(topo.route.shape[-2])
    F_pad = -(-F // n_shards) * n_shards
    topo_g = _pad_topology(topo, F_pad)
    npk_g = _pad_flow_axis(
        jnp.broadcast_to(jnp.asarray(n_packets), (F,)), F_pad, 0, fill=0
    )
    local_run = _local_flow_run(spec, horizon, F, n_shards)
    P = jax.sharding.PartitionSpec
    body = shard_map(
        functools.partial(inner, local_run),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=_flow_out_specs(n_lead),
        check_rep=False,
    )
    return _strip_flow_pad(
        body(topo_g, sched, sp, npk_g, key_or_keys), F, n_lead
    )


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_run_flows(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets,
    key: jax.Array,
    horizon: int = 4096,
    *,
    mesh,
) -> SimResult:
    """`run_flows` sharded over the flow axis on `mesh` (see `flow_mesh`).

    Bit-identical to the unsharded `run_flows` / `run_flows_sized` for any
    flow count (non-divisible counts are padded with silent flows and
    sliced back off).  `n_packets` may be a scalar or a per-flow [F] vector.
    """
    def inner(local_run, topo_g, sched_g, sp_g, npk_g, k):
        return local_run(topo_g, sched_g, sp_g, npk_g, k)

    return _shard_call(
        topo, sched, spec, sp, n_packets, key, horizon, mesh, inner, 0
    )


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_sweep_flows(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets,
    keys: jax.Array,
    horizon: int = 4096,
    *,
    mesh,
) -> SimResult:
    """`sweep_flows` sharded over the flow axis: `cct[P, D, F]`, the sweep
    axes riding vmaps INSIDE the shard body (shards stay in lockstep; the
    collectives commute with vmap)."""
    def inner(local_run, topo_g, sched_g, sp_g, npk_g, ks):
        return jax.vmap(
            lambda s: jax.vmap(
                lambda k: local_run(topo_g, sched_g, s, npk_g, k)
            )(ks)
        )(sp_g)

    return _shard_call(
        topo, sched, spec, sp, n_packets, keys, horizon, mesh, inner, 2
    )


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_sweep_flows_scenarios(
    topos: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    n_packets,
    keys: jax.Array,
    horizon: int = 4096,
    *,
    mesh,
) -> SimResult:
    """`sweep_flows_scenarios` sharded over the flow axis: ONE compiled
    program for scenarios x policies x draws x flows/devices —
    `cct[C, P, D, F]`, bit-identical to the unsharded family sweep."""
    def inner(local_run, topos_g, scheds_g, sp_g, npk_g, ks):
        return jax.vmap(
            lambda tp, sc: jax.vmap(
                lambda s: jax.vmap(
                    lambda k: local_run(tp, sc, s, npk_g, k)
                )(ks)
            )(sp_g)
        )(topos_g, scheds_g)

    return _shard_call(
        topos, scheds, spec, sp, n_packets, keys, horizon, mesh, inner, 3
    )
