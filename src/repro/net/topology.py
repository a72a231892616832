"""Shared-fabric leaf–spine topology engine: flows that contend (§2 at scale).

The seed fabric (`repro.net.fabric`) gives every flow an *independent* bundle
of n paths — a worker's burst can never degrade another worker's paths, so
incast, oversubscription and cross-job interference are inexpressible.  This
module models the coupling directly: a 2-tier leaf–spine topology where F
concurrent flows map their n logical paths onto shared physical links via a
static routing matrix ``route[hop, flow, path] -> link``, and every link runs
ONE fluid FIFO/ECN/tail-drop queue fed by the *sum* of arrivals from all
flows (and background traffic) crossing it.  One flow's burst now raises the
queue every other flow sharing the link sees — the real "mole" the paper's
Markov degradations stand in for.

Mechanics per tick (fully vectorized, scan/vmap friendly):

  * Store-and-forward pipeline: packets served at hop h enter hop h+1 on the
    next tick, so all hops advance in parallel with one segment-sum over the
    routing matrix per quantity (no sequential per-hop loop).
  * Tail drop charges *incoming* traffic proportionally (backlog that already
    won a queue slot is never dropped), service shares the link capacity in
    proportion to per-(flow, path) backlog — the standard fluid FIFO
    approximation.
  * ECN marks a path's exiting packets when ANY link on the path is over its
    threshold; queueing delay is summed along the path and *rounded* to
    ticks (consistent with `fabric.fabric_tick`).
  * Optional per-link Markov degradations (same on/off moles as the seed
    fabric) compose multiplicatively with a deterministic per-tick
    `EventSchedule` of capacity scales + background arrivals — scenario
    constructors in `repro.net.scenarios` are just builders of these.

`shared_fabric_tick` honours the `fabric_tick` feedback contract per flow
(sent/marked/dropped/qdelay per path after `fb_delay` ticks, plus landed),
so the transports in `repro.net.transport` run unchanged on top — coupled
via `transport.simulate_flows`, or one flow at a time via
`single_flow_stepper` + `transport.simulate_message_on`.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TopologyParams",
    "EventSchedule",
    "SharedFabricState",
    "leaf_spine",
    "FatTreeGrid",
    "fat_tree",
    "null_schedule",
    "init_shared_fabric",
    "scatter_delivery",
    "shared_fabric_tick",
    "single_flow_stepper",
    "link_backlog",
    "link_telemetry",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TopologyParams:
    """Static leaf–spine description.

    Shapes: H = hops (2 for leaf–spine), F = flows, n = logical paths per
    flow, L = shared links (uplinks + downlinks).
    """

    route: jax.Array          # int32[H, F, n] link id traversed at each hop
    capacity: jax.Array       # float32[L] packets served per tick
    queue_limit: jax.Array    # float32[L] tail-drop threshold
    ecn_threshold: jax.Array  # float32[L] mark when backlog exceeds this
    latency: jax.Array        # int32[F, n] base propagation delay (ticks)
    degrade_p: jax.Array      # float32[L] P[healthy -> degraded] per tick
    recover_p: jax.Array      # float32[L] P[degraded -> healthy] per tick
    degrade_factor: jax.Array  # float32[L] capacity multiplier while degraded
    fb_delay: int = dataclasses.field(metadata=dict(static=True))
    ring_len: int = dataclasses.field(metadata=dict(static=True))

    @property
    def hops(self) -> int:
        return int(self.route.shape[0])

    @property
    def flows(self) -> int:
        return int(self.route.shape[1])

    @property
    def n(self) -> int:
        return int(self.route.shape[2])

    @property
    def links(self) -> int:
        return int(self.capacity.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventSchedule:
    """Deterministic per-tick events; tick t reads row min(t, T-1) (the last
    row persists), so a schedule of length 1 is a static environment."""

    cap_scale: jax.Array     # float32[T, L] capacity multiplier
    bg_arrivals: jax.Array   # float32[T, L] background packets injected

    @property
    def horizon(self) -> int:
        return int(self.cap_scale.shape[0])


def null_schedule(links: int, horizon: int = 1) -> EventSchedule:
    """No events: full capacity, no background traffic."""
    return EventSchedule(
        cap_scale=jnp.ones((horizon, links), jnp.float32),
        bg_arrivals=jnp.zeros((horizon, links), jnp.float32),
    )


def uplink_id(leaf, spine, n_leaves: int, n_spines: int):
    return leaf * n_spines + spine


def downlink_id(spine, leaf, n_leaves: int, n_spines: int):
    return n_leaves * n_spines + spine * n_leaves + leaf


def leaf_spine(
    n_leaves: int,
    n_spines: int,
    flow_pairs,                      # [(src_leaf, dst_leaf), ...]
    *,
    uplink_capacity: float = 8.0,
    downlink_capacity: float | None = None,
    queue_limit: float = 48.0,
    ecn_threshold: float = 12.0,
    latency_ticks: int = 4,
    degrade_p: float = 0.0,
    recover_p: float = 0.05,
    degrade_factor: float = 0.05,
    fb_delay: int = 8,
    ring_len: int = 128,
) -> TopologyParams:
    """Build a 2-tier leaf–spine topology.

    Flow f between leaves (src, dst) gets n = n_spines logical paths; path p
    traverses uplink(src, p) then downlink(p, dst).  Links: uplinks first
    (leaf-major), then downlinks (spine-major); L = 2 * n_leaves * n_spines.
    """
    if downlink_capacity is None:
        downlink_capacity = uplink_capacity
    pairs = np.asarray(flow_pairs, dtype=np.int32)
    if pairs.ndim != 2 or pairs.shape[1] != 2:
        raise ValueError("flow_pairs must be a sequence of (src, dst) leaves")
    if np.any(pairs < 0) or np.any(pairs >= n_leaves):
        raise ValueError("flow endpoints out of leaf range")
    if np.any(pairs[:, 0] == pairs[:, 1]):
        raise ValueError("intra-leaf flows never reach the spine layer")
    F, n = pairs.shape[0], n_spines
    spines = np.arange(n_spines, dtype=np.int32)
    up = uplink_id(pairs[:, :1], spines[None, :], n_leaves, n_spines)
    down = downlink_id(spines[None, :], pairs[:, 1:], n_leaves, n_spines)
    route = np.stack([up, down], axis=0)  # [2, F, n]
    L = 2 * n_leaves * n_spines
    cap = np.concatenate(
        [
            np.full(n_leaves * n_spines, uplink_capacity, np.float32),
            np.full(n_leaves * n_spines, downlink_capacity, np.float32),
        ]
    )
    return TopologyParams(
        route=jnp.asarray(route, jnp.int32),
        capacity=jnp.asarray(cap),
        queue_limit=jnp.full((L,), queue_limit, jnp.float32),
        ecn_threshold=jnp.full((L,), ecn_threshold, jnp.float32),
        latency=jnp.full((F, n), latency_ticks, jnp.int32),
        degrade_p=jnp.full((L,), degrade_p, jnp.float32),
        recover_p=jnp.full((L,), recover_p, jnp.float32),
        degrade_factor=jnp.full((L,), degrade_factor, jnp.float32),
        fb_delay=fb_delay,
        ring_len=ring_len,
    )


@dataclasses.dataclass(frozen=True)
class FatTreeGrid:
    """Host-side descriptor of a 3-tier fat-tree / multi-pod Clos fabric.

    Pods of `leaves_per_pod` leaves x `spines_per_pod` spines, joined by a
    core layer organized as `spines_per_pod` PLANES of `cores_per_spine`
    switches: spine s of EVERY pod connects to all cores of plane s (the
    k-ary fat-tree wiring, where picking a core fixes the destination
    pod's spine).  An inter-pod flow therefore has n = spines_per_pod *
    cores_per_spine distinct 4-hop paths — path (s, j) climbs
    leaf -> spine s -> core (s, j), then descends core -> spine s of the
    destination pod -> leaf.  Intra-pod flows turn around at the pod spine:
    their middle two hops ride the BYPASS link (an infinite-capacity
    virtual wire, id `links - 1`) so every path in the fabric has the same
    hop count and one [hop, flow, path] routing matrix covers both.

    Link id layout (four physical tiers then the bypass):
      [0, P*Lp*S)                              leaf->spine uplinks
      [P*Lp*S, P*Lp*S + P*S*C)                 spine->core uplinks
      [P*Lp*S + P*S*C, P*Lp*S + 2*P*S*C)      core->spine downlinks
      [.., .. + P*S*Lp)                        spine->leaf downlinks
      links - 1                                bypass (virtual)
    """

    n_pods: int
    leaves_per_pod: int
    spines_per_pod: int
    cores_per_spine: int

    def __post_init__(self):
        if min(self.n_pods, self.leaves_per_pod, self.spines_per_pod,
               self.cores_per_spine) < 1:
            raise ValueError("every fat-tree dimension must be >= 1")

    @property
    def n_leaves(self) -> int:
        return self.n_pods * self.leaves_per_pod

    @property
    def n_paths(self) -> int:
        return self.spines_per_pod * self.cores_per_spine

    @property
    def links(self) -> int:
        P, Lp = self.n_pods, self.leaves_per_pod
        S, C = self.spines_per_pod, self.cores_per_spine
        return 2 * P * Lp * S + 2 * P * S * C + 1

    @property
    def bypass(self) -> int:
        return self.links - 1

    # --- link id helpers (vectorized over numpy int arrays) ---

    def up_leaf_spine(self, pod, leaf, spine):
        return (pod * self.leaves_per_pod + leaf) * self.spines_per_pod + spine

    def up_spine_core(self, pod, spine, core):
        base = self.n_pods * self.leaves_per_pod * self.spines_per_pod
        return base + (
            (pod * self.spines_per_pod + spine) * self.cores_per_spine + core
        )

    def down_core_spine(self, spine, core, pod):
        P, Lp = self.n_pods, self.leaves_per_pod
        S, C = self.spines_per_pod, self.cores_per_spine
        base = P * Lp * S + P * S * C
        return base + (spine * C + core) * P + pod

    def down_spine_leaf(self, pod, spine, leaf):
        P, Lp = self.n_pods, self.leaves_per_pod
        S, C = self.spines_per_pod, self.cores_per_spine
        base = P * Lp * S + 2 * P * S * C
        return base + (pod * S + spine) * Lp + leaf

    def pod_of(self, leaf_global):
        return leaf_global // self.leaves_per_pod

    def tier_slices(self):
        """(name -> slice) over the link axis, one entry per physical tier
        plus the bypass — the conservation tests sum these."""
        P, Lp = self.n_pods, self.leaves_per_pod
        S, C = self.spines_per_pod, self.cores_per_spine
        a, b, c, d = P * Lp * S, P * S * C, P * S * C, P * S * Lp
        edges = np.cumsum([0, a, b, c, d])
        return {
            "leaf_spine_up": slice(int(edges[0]), int(edges[1])),
            "spine_core_up": slice(int(edges[1]), int(edges[2])),
            "core_spine_down": slice(int(edges[2]), int(edges[3])),
            "spine_leaf_down": slice(int(edges[3]), int(edges[4])),
            "bypass": slice(int(edges[4]), int(edges[4]) + 1),
        }


# capacity/limit assigned to the virtual bypass link: effectively infinite
# (the fluid queue then serves everything the same tick, adds no queueing
# delay, never drops and never ECN-marks), while staying far below the
# float32 range where capacity * horizon sums would lose integer precision.
_BYPASS_CAPACITY = 1e9


def fat_tree(
    n_pods: int,
    leaves_per_pod: int,
    spines_per_pod: int,
    cores_per_spine: int,
    flow_pairs,                      # [(src_leaf_global, dst_leaf_global)]
    *,
    uplink_capacity: float = 8.0,
    downlink_capacity: float | None = None,
    core_capacity: float | None = None,
    queue_limit: float = 48.0,
    ecn_threshold: float = 12.0,
    latency_ticks: int = 6,
    intra_latency_ticks: int = 4,
    degrade_p: float = 0.0,
    recover_p: float = 0.05,
    degrade_factor: float = 0.05,
    fb_delay: int = 8,
    ring_len: int = 128,
) -> TopologyParams:
    """Build a 3-tier fat-tree topology (see `FatTreeGrid` for the wiring).

    Flow f between global leaves (src, dst) gets n = spines_per_pod *
    cores_per_spine logical paths.  Inter-pod flows traverse four physical
    links (leaf->spine, spine->core, core->spine, spine->leaf); intra-pod
    flows (same pod, different leaf) turn around at the pod spine — their
    middle hops ride the infinite-capacity bypass link, and path (s, j)
    collapses to spine s for every core j (spraying over the duplicates is
    equivalent to spraying over the pod's spines).  The result honours the
    exact `TopologyParams` [hop, flow, path] contract, so `sender_tick`,
    telemetry, goldens and every sweep run unchanged on top.

    `core_capacity` covers both spine->core and core->spine links and
    defaults to `uplink_capacity` (scale it down for pod-level
    oversubscription).  Inter-pod paths get `latency_ticks` base
    propagation, intra-pod paths `intra_latency_ticks` (two fewer physical
    hops; the store-and-forward pipeline itself still charges every flow
    the same `hops` ticks of forwarding).
    """
    grid = FatTreeGrid(n_pods, leaves_per_pod, spines_per_pod, cores_per_spine)
    if downlink_capacity is None:
        downlink_capacity = uplink_capacity
    if core_capacity is None:
        core_capacity = uplink_capacity
    if n_pods < 2:
        raise ValueError(
            "fat_tree needs >= 2 pods (a 1-pod grid has a dead core tier: "
            "use leaf_spine)"
        )
    pairs = np.asarray(flow_pairs, dtype=np.int32)
    if pairs.ndim != 2 or pairs.shape[1] != 2:
        raise ValueError("flow_pairs must be a sequence of (src, dst) leaves")
    if np.any(pairs < 0) or np.any(pairs >= grid.n_leaves):
        raise ValueError("flow endpoints out of leaf range")
    if np.any(pairs[:, 0] == pairs[:, 1]):
        raise ValueError("intra-leaf flows never reach the spine layer")
    F, n = pairs.shape[0], grid.n_paths
    Lp, S, C = leaves_per_pod, spines_per_pod, cores_per_spine

    src_pod, src_leaf = pairs[:, 0] // Lp, pairs[:, 0] % Lp
    dst_pod, dst_leaf = pairs[:, 1] // Lp, pairs[:, 1] % Lp
    # path q = s * cores_per_spine + j: spine plane s, core j within it
    s = np.repeat(np.arange(S, dtype=np.int32), C)[None, :]      # [1, n]
    j = np.tile(np.arange(C, dtype=np.int32), S)[None, :]        # [1, n]
    inter = (src_pod != dst_pod)[:, None]                        # [F, 1]
    hop0 = grid.up_leaf_spine(src_pod[:, None], src_leaf[:, None], s)
    hop1 = np.where(inter, grid.up_spine_core(src_pod[:, None], s, j),
                    grid.bypass)
    hop2 = np.where(inter, grid.down_core_spine(s, j, dst_pod[:, None]),
                    grid.bypass)
    hop3 = grid.down_spine_leaf(dst_pod[:, None], s, dst_leaf[:, None])
    route = np.stack([hop0, hop1, hop2, hop3]).astype(np.int32)  # [4, F, n]

    tiers = grid.tier_slices()
    L = grid.links
    cap = np.empty((L,), np.float32)
    cap[tiers["leaf_spine_up"]] = uplink_capacity
    cap[tiers["spine_core_up"]] = core_capacity
    cap[tiers["core_spine_down"]] = core_capacity
    cap[tiers["spine_leaf_down"]] = downlink_capacity
    cap[grid.bypass] = _BYPASS_CAPACITY
    qlim = np.full((L,), queue_limit, np.float32)
    ecn = np.full((L,), ecn_threshold, np.float32)
    qlim[grid.bypass] = ecn[grid.bypass] = _BYPASS_CAPACITY
    # the virtual bypass never degrades, whatever the physical-link rates
    deg_p = np.full((L,), degrade_p, np.float32)
    deg_p[grid.bypass] = 0.0
    latency = np.where(
        inter, np.int32(latency_ticks), np.int32(intra_latency_ticks)
    ) * np.ones((F, n), np.int32)

    return TopologyParams(
        route=jnp.asarray(route),
        capacity=jnp.asarray(cap),
        queue_limit=jnp.asarray(qlim),
        ecn_threshold=jnp.asarray(ecn),
        latency=jnp.asarray(latency),
        degrade_p=jnp.asarray(deg_p),
        recover_p=jnp.full((L,), recover_p, jnp.float32),
        degrade_factor=jnp.full((L,), degrade_factor, jnp.float32),
        fb_delay=fb_delay,
        ring_len=ring_len,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SharedFabricState:
    """Dynamic shared-fabric state (per-flow views + per-link aggregates)."""

    queue: jax.Array       # float32[H, F, n] backlog attributed per flow-path
    forward: jax.Array     # float32[H-1, F, n] served at hop h, enters h+1 next tick
    bg_queue: jax.Array    # float32[L] background backlog
    degraded: jax.Array    # bool[L] Markov mole state
    arrive_ring: jax.Array  # float32[F, ring_len] deliveries landing at t+d
    # per-flow delayed-feedback rings (same contract as FabricState)
    sent_ring: jax.Array   # float32[F, fbwin, n]
    mark_ring: jax.Array   # float32[F, fbwin, n]
    drop_ring: jax.Array   # float32[F, fbwin, n]
    qdelay_ring: jax.Array  # float32[F, fbwin, n]
    received: jax.Array    # float32[F] cumulative delivered packets
    dropped: jax.Array     # float32[F, n] cumulative drops (any hop)
    bg_served: jax.Array   # float32[L] cumulative background served
    bg_dropped: jax.Array  # float32[L] cumulative background drops
    # per-link cumulative accounting (conservation: per link, over any
    # horizon, arrivals == served + dropped + current backlog)
    link_arrivals: jax.Array  # float32[L] all traffic that entered the link
    link_served: jax.Array    # float32[L] all traffic the link served
    link_dropped: jax.Array   # float32[L] all traffic tail-dropped
    link_busy: jax.Array      # float32[L] ticks with nonzero service
    t: jax.Array           # int32 tick counter


def init_shared_fabric(topo: TopologyParams) -> SharedFabricState:
    H, F, n, L = topo.hops, topo.flows, topo.n, topo.links
    fbwin = topo.fb_delay
    f32 = jnp.float32
    return SharedFabricState(
        queue=jnp.zeros((H, F, n), f32),
        forward=jnp.zeros((H - 1, F, n), f32),
        bg_queue=jnp.zeros((L,), f32),
        degraded=jnp.zeros((L,), bool),
        arrive_ring=jnp.zeros((F, topo.ring_len), f32),
        sent_ring=jnp.zeros((F, fbwin, n), f32),
        mark_ring=jnp.zeros((F, fbwin, n), f32),
        drop_ring=jnp.zeros((F, fbwin, n), f32),
        qdelay_ring=jnp.zeros((F, fbwin, n), f32),
        received=jnp.zeros((F,), f32),
        dropped=jnp.zeros((F, n), f32),
        bg_served=jnp.zeros((L,), f32),
        bg_dropped=jnp.zeros((L,), f32),
        link_arrivals=jnp.zeros((L,), f32),
        link_served=jnp.zeros((L,), f32),
        link_dropped=jnp.zeros((L,), f32),
        link_busy=jnp.zeros((L,), f32),
        t=jnp.zeros((), jnp.int32),
    )


def _link_sum(vals: jax.Array, route: jax.Array, links: int) -> jax.Array:
    """Segment-sum per-(hop, flow, path) values onto their links: [L]."""
    return jnp.zeros((links,), vals.dtype).at[route.reshape(-1)].add(
        vals.reshape(-1)
    )


def scatter_delivery(
    arrive_ring: jax.Array,  # float32[F, ring_len]
    slot: jax.Array,         # int32[F, n] landing slot per (flow, path)
    exiting: jax.Array,      # float32[F, n] packets leaving the last hop
) -> jax.Array:
    """Deposit each (flow, path)'s exiting packets into its landing slot.

    Replaces the historical ``one_hot(slot, ring_len)`` + einsum update,
    which materialized an [F, n, ring_len] tensor every tick.  The per-slot
    contributions are accumulated into a zero buffer first and added to the
    ring in one op, preserving the einsum's float association
    (ring + sum_n(contribs)) bit for bit.  The barrier keeps XLA from
    folding that add into the scatter, which would add each contribution
    to the ring one at a time.
    """
    F = arrive_ring.shape[0]
    fidx = jnp.broadcast_to(jnp.arange(F)[:, None], slot.shape)
    deposits = jnp.zeros_like(arrive_ring).at[fidx, slot].add(exiting)
    return arrive_ring + jax.lax.optimization_barrier(deposits)


def shared_fabric_tick(
    topo: TopologyParams,
    sched: EventSchedule,
    state: SharedFabricState,
    arrivals: jax.Array,  # float32[F, n] packets injected by each source
    key: jax.Array,
    *,
    axis_name: str | None = None,
    route_global: jax.Array | None = None,
) -> Tuple[SharedFabricState, dict]:
    """Advance one tick.  Feedback entries are per flow ([F, n] / landed [F]),
    echoing what each source saw `fb_delay` ticks ago — the `fabric_tick`
    contract, now with cross-flow coupling through the shared link queues.

    With `axis_name` set, the tick runs inside a `shard_map`/`vmap` body that
    holds a contiguous slice of the flow axis: `topo.route` is the local
    [H, F_local, n] slice, `route_global` the full [H, F_global, n] matrix,
    and the two per-link segment-sums all_gather the flow axis first so
    every device computes the SAME global backlog/incoming — and hence the
    same drop/serve fractions and link counters — in the exact float order
    of the unsharded path (tiled gather concatenates shards in axis order,
    matching the unsharded flow layout).  Everything else is local-flow
    indexing, so per-shard results are bit-identical to the unsharded tick.
    """
    L = topo.links
    route = topo.route
    t = state.t
    if axis_name is None:
        flow_sum = lambda v: _link_sum(v, route, L)  # noqa: E731
    else:
        if route_global is None:
            raise ValueError("axis_name requires route_global")
        flow_sum = lambda v: _link_sum(  # noqa: E731
            jax.lax.all_gather(v, axis_name, axis=1, tiled=True),
            route_global, L,
        )

    # --- link environment: Markov moles x scheduled capacity scaling ---
    u = jax.random.uniform(key, (L,))
    go_down = (~state.degraded) & (u < topo.degrade_p)
    go_up = state.degraded & (u < topo.recover_p)
    degraded = (state.degraded | go_down) & ~go_up
    ti = jnp.clip(t, 0, sched.horizon - 1)
    cap = (
        topo.capacity
        * sched.cap_scale[ti]
        * jnp.where(degraded, topo.degrade_factor, 1.0)
    )
    bg_in = sched.bg_arrivals[ti]

    # --- inflows: sources at hop 0, last tick's forwarded traffic after ---
    inflow = jnp.concatenate([arrivals[None], state.forward], axis=0)
    q_in = state.queue + inflow            # [H, F, n]
    bg_q = state.bg_queue + bg_in          # [L]

    # The named scopes below label these ops in the compiled HLO's metadata
    # (`tick/fabric/link_sums`, ...), for the benchmark's trace reduction.
    with jax.named_scope("link_sums"):
        # --- shared tail-drop: charge incoming traffic proportionally ---
        backlog = flow_sum(q_in) + bg_q                     # [L]
        incoming = flow_sum(inflow) + bg_in                 # [L]
        dropable = jnp.minimum(
            jnp.maximum(backlog - topo.queue_limit, 0.0), incoming
        )
        drop_frac = jnp.where(
            incoming > 0, dropable / jnp.maximum(incoming, 1e-9), 0.0
        )
        drops = inflow * drop_frac[route]                   # [H, F, n]
        bg_drop = bg_in * drop_frac
        q_in = q_in - drops
        bg_q = bg_q - bg_drop
        backlog = backlog - dropable

        # --- fluid FIFO service: share capacity in proportion to backlog ---
        # A link that can serve its whole backlog serves it all: the
        # fraction is exactly 1, so its queues drain to exact zeros (what
        # `fabric_quiescent` tests) and the flows are served what the link
        # counter books.  Only an over-capacity link serves a fraction.
        served_l = jnp.minimum(backlog, cap)
        short = backlog > cap
        serve_frac = jnp.where(
            short,
            served_l / jnp.where(short, backlog, 1.0),
            (backlog > 0).astype(jnp.float32),
        )
        served = q_in * serve_frac[route]                   # [H, F, n]
        bg_out = bg_q * serve_frac
        queue = q_in - served
        bg_queue = bg_q - bg_out
        residual = backlog - served_l                       # [L]

    with jax.named_scope("signals"):
        # --- per-path signals accumulated along the hops ---
        qdelay_l = jnp.where(cap > 0, residual / jnp.maximum(cap, 1e-6), 0.0)
        path_qdelay = jnp.sum(qdelay_l[route], axis=0)      # [F, n]
        path_drops = jnp.sum(drops, axis=0)                 # [F, n]
        over = residual > topo.ecn_threshold                # [L]
        path_marked = jnp.any(over[route], axis=0)          # [F, n]
        exiting = served[-1]                                # [F, n] last hop
        marked = jnp.where(path_marked, exiting, 0.0)

    with jax.named_scope("delivery"):
        # --- schedule deliveries: propagation + rounded queueing delay ---
        delay = topo.latency + jnp.round(path_qdelay).astype(jnp.int32)
        delay = jnp.minimum(delay, topo.ring_len - 1)
        slot = (t + 1 + delay) % topo.ring_len              # [F, n]
        arrive_ring = scatter_delivery(state.arrive_ring, slot, exiting)
        cur = t % topo.ring_len
        landed = arrive_ring[:, cur]
        arrive_ring = arrive_ring.at[:, cur].set(0.0)
        received = state.received + landed

    with jax.named_scope("feedback"):
        # --- delayed feedback rings (per flow, fabric_tick contract) ---
        fbwin = topo.fb_delay
        w = t % fbwin
        fb = dict(
            sent=state.sent_ring[:, w, :],
            marked=state.mark_ring[:, w, :],
            dropped=state.drop_ring[:, w, :],
            qdelay=state.qdelay_ring[:, w, :],
            landed=landed,
        )
    forward = served[:-1]
    with jax.named_scope("feedback"):
        rings = dict(
            sent_ring=state.sent_ring.at[:, w, :].set(arrivals),
            mark_ring=state.mark_ring.at[:, w, :].set(marked),
            drop_ring=state.drop_ring.at[:, w, :].set(path_drops),
            qdelay_ring=state.qdelay_ring.at[:, w, :].set(path_qdelay),
        )
    new_state = SharedFabricState(
        queue=queue,
        forward=forward,
        bg_queue=bg_queue,
        degraded=degraded,
        arrive_ring=arrive_ring,
        **rings,
        received=received,
        dropped=state.dropped + path_drops,
        bg_served=state.bg_served + bg_out,
        bg_dropped=state.bg_dropped + bg_drop,
        link_arrivals=state.link_arrivals + incoming,
        link_served=state.link_served + served_l,
        link_dropped=state.link_dropped + dropable,
        link_busy=state.link_busy + (served_l > 0).astype(jnp.float32),
        t=t + 1,
    )
    return new_state, fb


def link_backlog(topo: TopologyParams, state: SharedFabricState) -> jax.Array:
    """Instantaneous per-link backlog [L]: flow traffic (all hops, all
    flow-paths crossing the link) plus the background queue.  Equal to the
    post-service `residual` of the tick that produced `state`."""
    return _link_sum(state.queue, topo.route, topo.links) + state.bg_queue


def link_telemetry(topo: TopologyParams, state: SharedFabricState):
    """Telemetry reader: per-link (queue, served, dropped, ecn), each [L].

    `queue` is the instantaneous backlog, `served`/`dropped` the cumulative
    link counters, `ecn` a 0/1 indicator of backlog over the mark threshold
    — the same predicate `shared_fabric_tick` uses to mark exiting packets.
    """
    q = link_backlog(topo, state)
    over = (q > topo.ecn_threshold).astype(jnp.float32)
    return q, state.link_served, state.link_dropped, over


def single_flow_stepper(topo: TopologyParams, sched: EventSchedule):
    """Adapt a one-flow shared topology to the `fabric_tick` stepper shape.

    Returns (state0, stepper) for `transport.simulate_message_on` — arrivals
    and feedback lose their F=1 leading dim so existing single-flow senders
    run unchanged on the shared engine.  Pass
    ``received_fn=lambda s: s.received[0]`` and
    ``dropped_fn=lambda s: s.dropped[0]`` to the caller.
    """
    if topo.flows != 1:
        raise ValueError(f"single-flow stepper needs F=1, got F={topo.flows}")

    def stepper(state, arrivals, key):
        state, fb = shared_fabric_tick(topo, sched, state, arrivals[None], key)
        return state, {k: v[0] for k, v in fb.items()}

    return init_shared_fabric(topo), stepper
