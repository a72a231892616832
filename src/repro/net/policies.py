"""Path-selection policy library: the paper's baselines + the literature.

`Policy` (formerly defined in `repro.net.sender`, re-exported there) now
spans eight members: the five originals — ECMP / RR / RAND_STATIC /
RAND_ADAPTIVE / WAM — plus the three adaptive-spraying competitors the
ROADMAP names as the real comparison set for the bake-off:

  * PRIME       — PRIME-style adaptive multi-part-entropy spraying
                  (arXiv:2507.23012, Sobhani et al.).  Each sender keeps n
                  per-slot entropy values; packet j uses slot ``j % n`` and
                  goes to path ``entropy[slot] % n``.  A slot whose current
                  path shows congestion (ECN above `ENT_ECN_THRESH` or loss
                  above `ENT_LOSS_THRESH` in the delayed feedback) REROLLS
                  its entropy through a deterministic avalanche hash
                  (`policy_state.entropy_mix`) — spraying stays
                  deterministic-per-state like real multi-part-entropy
                  rewriting, only the entropy mutates.
  * STRACK      — STrack-style per-path penalization with penalty-decay
                  recovery (arXiv:2407.15266, Le et al.).  Per-path score =
                  penalty + normalized EWMA-RTT excess; spraying
                  round-robins over the ELIGIBLE set {score <= min_score +
                  `STRACK_SLACK`}.  Penalties accumulate from ECN/loss and
                  decay by `policy_state.PEN_DECAY` per tick, so a whacked
                  path re-enters the eligible set on a closed-form tick
                  bound (the recovery-dynamics oracle in
                  tests/test_telemetry.py).
  * CC_COUPLED  — Gerstein-style congestion-control-coupled spraying
                  (arXiv:2509.07907, Gerstein/Silberstein/Keslassy): one
                  AIMD window per path driven by the fabric's ECN signal;
                  the spray WEIGHTS are the windows, while the spray
                  SEQUENCE stays WaM's deterministic low-discrepancy key
                  stream — the key is mapped through the cumulative-window
                  CDF instead of the controller profile's.

The three newcomers read per-path sender state (`repro.net.policy_state`)
that the five originals do not carry; a state-bearing policy whose block is
statically disabled (zero-width leaf, e.g. the spray-throughput microbench
sweeping all eight policies stateless) degrades to the RAND_STATIC branch
rather than tracing an invalid gather — loudly documented here because it
is a fallback, not an implementation of the policy.

None of the new policies drives the WaM profile controller
(`profile_adaptive` stays RAND_ADAPTIVE | WAM): their adaptivity lives
entirely in their own state blocks, so `final_b` remains uniform for them
and the controller cadence cost is not charged to their score.

Dispatch stays a single traced `jax.lax.switch` (`policy_branches` builds
the ordered branch list consumed by `sender.assign_paths`), so one
compiled program still serves all eight policies with the policy id a
plain vmap axis.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.profile import PathProfile
from repro.core.spray import SprayState, select_path, spray_key
from repro.net.policy_state import PolicyState, canon_blocks

__all__ = [
    "Policy",
    "BASELINE_POLICIES",
    "ALL_POLICIES",
    "PolicyDef",
    "POLICY_DEFS",
    "blocks_for",
    "profile_adaptive",
    "STRACK_SLACK",
    "strack_scores",
    "policy_branches",
]


class Policy(enum.IntEnum):
    """Path-selection policy ids (the `lax.switch` branch indices).

    The first five are the original baselines and their ids are FROZEN —
    golden traces, BENCH history and the transport configs encode them.
    """

    ECMP = 0
    RR = 1
    RAND_STATIC = 2
    RAND_ADAPTIVE = 3
    WAM = 4
    PRIME = 5
    STRACK = 6
    CC_COUPLED = 7


BASELINE_POLICIES: Tuple[Policy, ...] = tuple(Policy)[:5]
ALL_POLICIES: Tuple[Policy, ...] = tuple(Policy)


@dataclasses.dataclass(frozen=True)
class PolicyDef:
    """Registry row: which state blocks a policy reads, and whether it
    drives the WaM profile controller."""

    policy: Policy
    blocks: Tuple[str, ...] = ()
    profile_adaptive: bool = False


POLICY_DEFS: Tuple[PolicyDef, ...] = (
    PolicyDef(Policy.ECMP),
    PolicyDef(Policy.RR),
    PolicyDef(Policy.RAND_STATIC),
    PolicyDef(Policy.RAND_ADAPTIVE, profile_adaptive=True),
    PolicyDef(Policy.WAM, profile_adaptive=True),
    PolicyDef(Policy.PRIME, blocks=("entropy",)),
    PolicyDef(Policy.STRACK, blocks=("rtt", "penalty")),
    PolicyDef(Policy.CC_COUPLED, blocks=("ccw",)),
)
_DEF_BY_POLICY = {d.policy: d for d in POLICY_DEFS}


def blocks_for(policies: Sequence[Policy | int]) -> Tuple[str, ...]:
    """Union of the state blocks the given policies read, canonically
    ordered — the value for `SenderSpec.state_blocks` of a sweep over
    exactly those policies."""
    want = set()
    for p in policies:
        want.update(_DEF_BY_POLICY[Policy(int(p))].blocks)
    return canon_blocks(want)


def profile_adaptive(policy: jax.Array) -> jax.Array:
    """Traced: does `policy` drive the WaM delayed-feedback profile
    controller?  Only RAND_ADAPTIVE and WAM do (see module docstring)."""
    return (policy == Policy.RAND_ADAPTIVE) | (policy == Policy.WAM)


# STrack eligibility slack: a path is sprayable while its score is within
# this of the best path's.  With PEN_DECAY=1-1/16 a penalty of P re-enters
# the eligible set after ceil(ln(SLACK/P)/ln(PEN_DECAY)) clean ticks — the
# closed form the recovery oracle pins.
STRACK_SLACK = 0.5


def strack_scores(state: PolicyState):
    """STrack per-path (score, eligible) from the rtt/penalty blocks.

    score = penalty + (rtt - min rtt) / max(min rtt, 1) — penalty timers
    plus normalized excess delay; eligible = score <= min score +
    `STRACK_SLACK` (the argmin path is always eligible, so the eligible
    set is never empty).  Broadcasts over leading flow axes; shared by the
    dispatch branch and the recovery-dynamics oracle test.
    """
    rtt, pen = state.rtt, state.penalty
    base = jnp.min(rtt, axis=-1, keepdims=True)
    score = pen + (rtt - base) / jnp.maximum(base, 1.0)
    good = score <= jnp.min(score, axis=-1, keepdims=True) + STRACK_SLACK
    return score, good


def policy_branches(
    rate_cap: int,
    n: int,
    spray: SprayState,
    profile: PathProfile,
    key: jax.Array,
    ecmp_path: jax.Array,
    pstate: PolicyState,
):
    """The ordered `lax.switch` branch list: index == Policy value.

    Each branch maps the tick's `rate_cap` emission lanes to path ids
    int32[rate_cap].  The five baseline bodies are the exact code that
    lived in `sender.assign_paths` before the policy-state refactor
    (bit-identity there is pinned by the golden traces); the three
    state-bearing branches read `pstate` blocks and statically fall back
    to `rand_static` when their block is disabled (zero-width).
    """
    lanes = jnp.arange(rate_cap, dtype=jnp.uint32)

    def ecmp():
        return jnp.full((rate_cap,), ecmp_path, jnp.int32)

    def rr():
        return ((spray.j + lanes) % n).astype(jnp.int32)

    def rand_static():
        return jax.random.randint(key, (rate_cap,), 0, n, jnp.int32)

    def rand_adaptive():
        u = jax.random.randint(key, (rate_cap,), 0, profile.m, jnp.int32)
        return select_path(profile.c, u)

    def wam():
        keys = spray_key(
            spray.j + lanes, spray.sa, spray.sb, spray.ell, spray.method
        )
        return select_path(profile.c, keys)

    def prime():
        # slot j%n carries entropy e; the packet goes to path e%n.  The
        # entropy only changes via the feedback-driven reroll in
        # policy_state.update_policy_state — selection itself is
        # deterministic given the state, like WAM given the profile.
        slot = ((spray.j + lanes) % jnp.uint32(n)).astype(jnp.int32)
        ent = pstate.entropy[slot]
        return (ent % jnp.uint32(n)).astype(jnp.int32)

    def strack():
        _, good = strack_scores(pstate)
        # round-robin over the eligible set: cumsum ranks the good paths
        # 1..n_good; lane slot s (mod n_good) picks the (s+1)-th good path
        # via searchsorted — branchless, n_good >= 1 by construction.
        k = jnp.cumsum(good.astype(jnp.int32))
        n_good = k[-1].astype(jnp.uint32)
        slot = ((spray.j + lanes) % n_good).astype(jnp.int32)
        return jnp.searchsorted(k, slot + 1, side="left").astype(jnp.int32)

    def cc_coupled():
        # WaM's deterministic low-discrepancy key sequence, mapped through
        # the AIMD windows' CDF instead of the controller profile's: the
        # congestion-control coupling of arXiv:2509.07907 grafted onto the
        # paper's spray sequence.
        keys = spray_key(
            spray.j + lanes, spray.sa, spray.sb, spray.ell, spray.method
        )
        cum = jnp.cumsum(pstate.ccw)
        unit = (keys.astype(jnp.float32) + 0.5) / jnp.float32(profile.m)
        path = jnp.searchsorted(cum, unit * cum[-1], side="left")
        return jnp.clip(path.astype(jnp.int32), 0, n - 1)

    def gated(fn, block_width: int):
        # STATIC fallback (shapes are static under trace): a state-bearing
        # policy without its block degrades to stochastic spraying rather
        # than gathering from a zero-width leaf.  Runs that sweep these
        # policies for real must enable the blocks (sender.spec_for_policies).
        return fn if block_width else rand_static

    def scoped(policy: Policy, fn):
        # a branch's ops carry its policy's name in their HLO metadata
        # (`tick/assign/wam`), which the benchmark's trace reduction reads
        def branch():
            with jax.named_scope(policy.name.lower()):
                return fn()

        return branch

    return [scoped(p, fn) for p, fn in zip(Policy, (
        ecmp,
        rr,
        rand_static,
        rand_adaptive,
        wam,
        gated(prime, pstate.entropy.shape[-1]),
        gated(strack, pstate.rtt.shape[-1] and pstate.penalty.shape[-1]),
        gated(cc_coupled, pstate.ccw.shape[-1]),
    ))]
