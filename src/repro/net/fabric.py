"""Multipath fabric model: the dynamic network the paper sprays over (§2).

Discrete-time, fully vectorized (jax.lax.scan over ticks).  Each source-
destination flow sees n paths with per-path service capacity (packets/tick),
base latency (ticks), a FIFO queue with tail-drop and an ECN marking
threshold.  Transient congestion ("moles") is a per-path Markov on/off
degradation process that multiplies capacity while active — concurrent flows,
link faults and PFC-style stalls are all expressible as degradations.

The fabric is deliberately flow-centric (queues per path of one flow's
bundle) rather than a full packet-level topology simulator: the paper's
claims are about the *source's* per-packet path decisions under imperfect,
delayed feedback, which this captures exactly — including the feedback loop:
per-path ECN/loss/RTT statistics are echoed to the source after `fb_delay`
ticks, matching §5's per-path sequence-number feedback design.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["FabricParams", "FabricState", "init_fabric", "fabric_tick"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FabricParams:
    """Static fabric description (float32/int32 arrays of shape [n])."""

    capacity: jax.Array        # packets served per tick, per path
    latency: jax.Array         # int32 propagation delay in ticks
    queue_limit: jax.Array     # tail-drop threshold (packets)
    ecn_threshold: jax.Array   # mark served packets when queue exceeds this
    degrade_p: jax.Array       # P[healthy -> degraded] per tick
    recover_p: jax.Array       # P[degraded -> healthy] per tick
    degrade_factor: jax.Array  # capacity multiplier while degraded (0..1)
    fb_delay: int = dataclasses.field(metadata=dict(static=True))
    ring_len: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n(self) -> int:
        return int(self.capacity.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FabricState:
    """Per-flow dynamic state (leading dims broadcast over flows/workers)."""

    queue: jax.Array          # float32[..., n] backlog
    degraded: jax.Array       # bool[..., n]
    arrive_ring: jax.Array    # float32[..., ring_len] deliveries landing at t+d
    # per-path stats rings for delayed feedback (sent/marked/dropped per tick)
    sent_ring: jax.Array      # float32[..., fbwin, n]
    mark_ring: jax.Array      # float32[..., fbwin, n]
    drop_ring: jax.Array      # float32[..., fbwin, n]
    qdelay_ring: jax.Array    # float32[..., fbwin, n] queueing delay sample
    received: jax.Array       # float32[...] cumulative delivered packets
    dropped: jax.Array        # float32[..., n] cumulative drops (ARQ debt)
    t: jax.Array              # int32 tick counter


def init_fabric(params: FabricParams, lead_shape: Tuple[int, ...] = ()) -> FabricState:
    n = params.n
    fbwin = params.fb_delay
    f32 = jnp.float32
    return FabricState(
        queue=jnp.zeros(lead_shape + (n,), f32),
        degraded=jnp.zeros(lead_shape + (n,), bool),
        arrive_ring=jnp.zeros(lead_shape + (params.ring_len,), f32),
        sent_ring=jnp.zeros(lead_shape + (fbwin, n), f32),
        mark_ring=jnp.zeros(lead_shape + (fbwin, n), f32),
        drop_ring=jnp.zeros(lead_shape + (fbwin, n), f32),
        qdelay_ring=jnp.zeros(lead_shape + (fbwin, n), f32),
        received=jnp.zeros(lead_shape, f32),
        dropped=jnp.zeros(lead_shape + (n,), f32),
        t=jnp.zeros((), jnp.int32),
    )


def fabric_tick(
    params: FabricParams,
    state: FabricState,
    arrivals: jax.Array,  # float32[..., n] packets injected on each path
    key: jax.Array,
) -> Tuple[FabricState, dict]:
    """Advance one tick.  Returns (state', feedback) where feedback carries the
    per-path statistics the source saw `fb_delay` ticks ago (§5 semantics)."""
    n = params.n
    t = state.t
    kd = key

    # --- degradation process (the moles) ---
    u = jax.random.uniform(kd, state.degraded.shape)
    go_down = (~state.degraded) & (u < params.degrade_p)
    go_up = state.degraded & (u < params.recover_p)
    degraded = (state.degraded | go_down) & ~go_up
    cap = params.capacity * jnp.where(degraded, params.degrade_factor, 1.0)

    # --- enqueue with tail drop ---
    q_in = state.queue + arrivals
    drops = jnp.maximum(q_in - params.queue_limit, 0.0)
    q_in = jnp.minimum(q_in, params.queue_limit)

    # --- serve up to capacity; schedule arrival after latency + queue delay ---
    served = jnp.minimum(q_in, cap)
    queue = q_in - served
    qdelay = jnp.where(cap > 0, queue / jnp.maximum(cap, 1e-6), 0.0)
    # round, don't floor: truncation would report zero delay for any sub-tick
    # backlog, hiding early congestion from the delayed-feedback RTT signal
    delay = params.latency + jnp.round(qdelay).astype(jnp.int32)
    delay = jnp.minimum(delay, params.ring_len - 1)
    slot = (t + 1 + delay) % params.ring_len  # [..., n]
    arrive_ring = state.arrive_ring
    # scatter-add each path's served packets into its landing slot; HIGHEST
    # keeps the f32 operands exact on the TPU, whose default matmul
    # precision would round `served` to bf16
    ring_idx = jax.nn.one_hot(slot, params.ring_len, dtype=served.dtype)
    arrive_ring = arrive_ring + jnp.einsum(
        "...n,...nr->...r", served, ring_idx,
        precision=jax.lax.Precision.HIGHEST,
    )

    # --- deliveries landing this tick ---
    cur = t % params.ring_len
    landed = arrive_ring[..., cur]
    arrive_ring = arrive_ring.at[..., cur].set(0.0)
    received = state.received + landed

    # --- ECN marking on served packets ---
    marked = jnp.where(queue > params.ecn_threshold, served, 0.0)

    # --- delayed feedback rings ---
    fbwin = params.fb_delay
    w = t % fbwin
    fb = dict(
        sent=state.sent_ring[..., w, :],
        marked=state.mark_ring[..., w, :],
        dropped=state.drop_ring[..., w, :],
        qdelay=state.qdelay_ring[..., w, :],
        landed=landed,
    )
    new_state = FabricState(
        queue=queue,
        degraded=degraded,
        arrive_ring=arrive_ring,
        sent_ring=state.sent_ring.at[..., w, :].set(arrivals),
        mark_ring=state.mark_ring.at[..., w, :].set(marked),
        drop_ring=state.drop_ring.at[..., w, :].set(drops),
        qdelay_ring=state.qdelay_ring.at[..., w, :].set(qdelay),
        received=received,
        dropped=state.dropped + drops,
        t=t + 1,
    )
    return new_state, fb
