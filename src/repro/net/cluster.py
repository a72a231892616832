"""Cluster layer: J co-scheduled training jobs contending on ONE fabric.

The paper's headline metrics (CCT, ETTR) matter because training jobs SHARE
a fabric — yet a single `repro.net.jobs` run gives a job the whole
leaf–spine topology to itself, and the only cross-job scenario below it
(`crossjob_background`) injects a synthetic open-loop arrival trace.  That
trace never reacts: it cannot slow down when WaM whacks load off a hot
link, and it cannot speed up when the foreground job stalls.  This module
makes the interference EMERGENT instead of injected:

  1. `place_jobs` maps J heterogeneous `JobSchedule`s (different models,
     worker counts, start offsets) onto the leaves of one shared topology —
     each job keeps its own ring placement (worker w -> worker (w+1) % W_j),
     either on disjoint leaves (the uncontended reference) or co-located on
     the same leaves (jobs share every uplink/downlink, the multi-tenant
     regime PRIME and the AI-training load-balancing literature evaluate).
  2. `cluster_round_table` aligns the jobs' flattened step tables into
     global ROUNDS: round r runs step (r - start_j) of every job j that is
     active then.  All active steps execute as ONE coupled-flow simulation
     (`sender.run_flows_sized` with a per-flow size vector): a flow whose
     job is idle or not yet started gets size 0, completes at tick 0 and
     emits nothing.  One job's burst therefore raises the queues the other
     job's packets sit in — and a whacked-down path sheds load the OTHER
     job immediately feels — with no injected trace anywhere.
  3. `run_cluster` / `sweep_cluster` keep the one-compile idiom: jobs x
     5 policies x PRNG draws x rounds x (contended + per-job solo) variants
     are a single XLA program per scenario.  The solo variants (every other
     job's flows silenced to size 0, same PRNG stream) run INSIDE that
     program, so cross-job slowdown is a paired comparison for free.

Metrics beyond per-job ETTR (`jobs.job_ettr` applied per job):

  * slowdown      — (compute + exposed comm, contended) / (same, solo): how
                    much whole-job time co-location costs this job.
  * Jain fairness — (sum x)^2 / (J * sum x^2) over x_j = 1/slowdown_j: 1.0
                    when co-location taxes every job equally.
  * link utilization — per-link served packets (including background) over
                    nominal capacity x busy ticks, read straight from the
                    shared fabric's conservation counters.

Approximation note: rounds are a bulk-synchronous alignment — job A's step
r and job B's step r start together even though real jobs drift.  This is
the same per-step discretization the job layer already makes (actual
completion times feed the metrics, planned times feed the event clock), and
it is what keeps the whole cluster one `jax.vmap`-able program.  The global
planned timeline (for positioning scenario events such as a mid-run flap)
is anchored to job 0's planned offsets, extended at its trailing cadence
past its end; staggered jobs read events from the rounds they are active
in, exactly like `jobs.scheduled_events`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.net.jobs import JobSchedule, job_ettr, scheduled_events, step_table
from repro.net.sender import (
    FLOW_AXIS,
    SenderParams,
    SenderSpec,
    run_flows_sized,
)
from repro.net.topology import (
    EventSchedule,
    TopologyParams,
    fat_tree,
    leaf_spine,
)

__all__ = [
    "ClusterJob",
    "Cluster",
    "ClusterResult",
    "place_jobs",
    "place_jobs_pods",
    "cluster_topology",
    "cluster_fat_tree_topology",
    "cluster_round_table",
    "solo_size_variants",
    "cluster_inputs",
    "run_cluster_rounds",
    "sweep_cluster_rounds",
    "sweep_cluster_rounds_scenarios",
    "shard_run_cluster_rounds",
    "shard_sweep_cluster_rounds",
    "jain_index",
    "link_utilization",
    "cluster_metrics",
    "run_cluster",
    "sweep_cluster",
]


@dataclasses.dataclass(frozen=True)
class ClusterJob:
    """One job's placement on the shared fabric (static, host-side)."""

    job: JobSchedule
    start_step: int           # global round in which the job's step 0 runs
    leaves: Tuple[int, ...]   # leaf hosting each worker (len == job.workers)

    def __post_init__(self):
        if len(self.leaves) != self.job.workers:
            raise ValueError(
                f"{self.job.arch}: {len(self.leaves)} leaves for "
                f"{self.job.workers} workers"
            )
        if self.start_step < 0:
            raise ValueError(f"start_step must be >= 0, got {self.start_step}")


@dataclasses.dataclass(frozen=True)
class Cluster:
    """J placed jobs sharing one leaf–spine fabric."""

    jobs: Tuple[ClusterJob, ...]
    n_leaves: int

    @property
    def flows(self) -> int:
        """Total coupled flows: one per (job, worker)."""
        return sum(cj.job.workers for cj in self.jobs)

    @property
    def rounds(self) -> int:
        """Global rounds R = max over jobs of start_step + total_steps."""
        return max(cj.start_step + cj.job.total_steps for cj in self.jobs)

    @property
    def flow_job(self) -> np.ndarray:
        """int32[F] owning job index of each flow (jobs' flows contiguous)."""
        return np.concatenate(
            [
                np.full(cj.job.workers, j, np.int32)
                for j, cj in enumerate(self.jobs)
            ]
        )

    def flow_pairs(self) -> np.ndarray:
        """int32[F, 2] (src_leaf, dst_leaf) — each job's own ring."""
        pairs = []
        for cj in self.jobs:
            W = cj.job.workers
            for w in range(W):
                pairs.append((cj.leaves[w], cj.leaves[(w + 1) % W]))
        return np.asarray(pairs, np.int32)

    def job_flows(self, j: int) -> slice:
        """Flow-axis slice owned by job j."""
        lo = sum(cj.job.workers for cj in self.jobs[:j])
        return slice(lo, lo + self.jobs[j].job.workers)


def place_jobs(
    jobs: Sequence[JobSchedule],
    *,
    colocated: bool = True,
    start_steps: Optional[Sequence[int]] = None,
) -> Cluster:
    """Place J jobs' rings on one fabric.

    `colocated=True` puts every job's worker w on leaf w — jobs share the
    per-leaf uplinks and downlinks, the contended multi-tenant regime.
    `colocated=False` gives each job its own disjoint block of leaves —
    with a 2-tier leaf–spine there is then NO shared link, which makes it
    the emergence-free reference placement ("uncontended").

    Job 0 anchors the global planned timeline, so `start_steps[0]` must be
    0 (stagger the others relative to it).
    """
    if not jobs:
        raise ValueError("need at least one job")
    if any(j.workers < 2 for j in jobs):
        raise ValueError("every job needs >= 2 workers to form a ring")
    starts = tuple(start_steps) if start_steps is not None else (0,) * len(jobs)
    if len(starts) != len(jobs):
        raise ValueError(f"{len(starts)} start_steps for {len(jobs)} jobs")
    if starts[0] != 0:
        raise ValueError(
            "job 0 anchors the planned timeline: start_steps[0] must be 0"
        )
    placed, base = [], 0
    for job, start in zip(jobs, starts):
        if colocated:
            leaves = tuple(range(job.workers))
        else:
            leaves = tuple(range(base, base + job.workers))
            base += job.workers
        placed.append(ClusterJob(job=job, start_step=int(start), leaves=leaves))
    n_leaves = 1 + max(max(cj.leaves) for cj in placed)
    return Cluster(jobs=tuple(placed), n_leaves=n_leaves)


def cluster_topology(
    cluster: Cluster,
    n_spines: int = 4,
    *,
    n_leaves: Optional[int] = None,
    **leaf_spine_kwargs,
) -> TopologyParams:
    """The shared leaf–spine fabric under a placed cluster: F = sum(W_j)
    coupled flows, each job riding its own ring over the common links.

    `n_leaves` may over-provision the grid beyond the placement's own leaf
    count so that different placements (e.g. co-located vs disjoint) share
    one link-array shape and can ride a stacked scenario axis
    (`scenarios.stack_scenarios`); the extra leaves' links idle and change
    nothing.
    """
    return leaf_spine(
        max(cluster.n_leaves, n_leaves or 0),
        n_spines,
        cluster.flow_pairs(),
        **leaf_spine_kwargs,
    )


def place_jobs_pods(
    jobs: Sequence[JobSchedule],
    leaves_per_pod: int,
    *,
    start_steps: Optional[Sequence[int]] = None,
    pack: bool = False,
) -> Cluster:
    """Pod-aligned placement for 3-tier fat-tree fabrics.

    Each job's leaf block starts at a POD boundary: a job whose worker
    count fits `leaves_per_pod` forms an intra-pod ring (its traffic turns
    around at the pod spines and never crosses the core), a larger job
    spans consecutive pods and its ring wraps through the core layer —
    which is where the paper's inter-pod path diversity (spines x cores
    paths) actually gets exercised.

    `pack=True` co-locates instead: every job's worker w rides leaf w (the
    multi-tenant regime of `place_jobs(colocated=True)`, here confined to
    the first ceil(max workers / leaves_per_pod) pods), so intra-pod
    contention between jobs plus inter-pod self-traffic coexist.
    """
    if leaves_per_pod < 1:
        raise ValueError("leaves_per_pod must be >= 1")
    if not jobs:
        raise ValueError("need at least one job")
    if any(j.workers < 2 for j in jobs):
        raise ValueError("every job needs >= 2 workers to form a ring")
    starts = tuple(start_steps) if start_steps is not None else (0,) * len(jobs)
    if len(starts) != len(jobs):
        raise ValueError(f"{len(starts)} start_steps for {len(jobs)} jobs")
    if starts[0] != 0:
        raise ValueError(
            "job 0 anchors the planned timeline: start_steps[0] must be 0"
        )
    placed, base = [], 0
    for job, start in zip(jobs, starts):
        if pack:
            leaves = tuple(range(job.workers))
        else:
            leaves = tuple(range(base, base + job.workers))
            # the next job starts at the next pod boundary
            base = -(-(base + job.workers) // leaves_per_pod) * leaves_per_pod
        placed.append(ClusterJob(job=job, start_step=int(start), leaves=leaves))
    # round the grid itself up to whole pods
    n_leaves = 1 + max(max(cj.leaves) for cj in placed)
    n_leaves = -(-n_leaves // leaves_per_pod) * leaves_per_pod
    return Cluster(jobs=tuple(placed), n_leaves=n_leaves)


def cluster_fat_tree_topology(
    cluster: Cluster,
    leaves_per_pod: int,
    spines_per_pod: int = 2,
    cores_per_spine: int = 2,
    *,
    n_pods: Optional[int] = None,
    **fat_tree_kwargs,
) -> TopologyParams:
    """The 3-tier fat-tree fabric under a placed cluster (the fat-tree
    counterpart of `cluster_topology`): F = sum(W_j) coupled flows with
    n = spines_per_pod * cores_per_spine paths each; intra-pod ring hops
    stay off the core, inter-pod hops spray across it.

    `n_pods` may over-provision beyond the placement's own pod count so
    different placements share one link-array shape on a stacked scenario
    axis (idle pods change nothing).
    """
    need_pods = -(-cluster.n_leaves // leaves_per_pod)
    return fat_tree(
        max(need_pods, n_pods or 0),
        leaves_per_pod,
        spines_per_pod,
        cores_per_spine,
        cluster.flow_pairs(),
        **fat_tree_kwargs,
    )


def cluster_round_table(
    cluster: Cluster,
) -> Tuple[np.ndarray, np.ndarray]:
    """Align the jobs' step tables into global rounds (host, static).

    Returns ``(sizes[R, F], offsets[R])``: sizes[r, f] is flow f's message
    for round r — its job's shard for step (r - start_j), or 0 when the job
    is not active (not yet started, or already done) — and offsets[r] the
    round's planned start tick on the global timeline (job 0's planned
    offsets, extended past its last step at its trailing cadence), which is
    where scenario event schedules are read from (`jobs.scheduled_events`).
    """
    R, F = cluster.rounds, cluster.flows
    sizes = np.zeros((R, F), np.int32)
    tables = [step_table(cj.job) for cj in cluster.jobs]
    for j, (cj, (shard, _, _)) in enumerate(zip(cluster.jobs, tables)):
        sl = cluster.job_flows(j)
        lo, hi = cj.start_step, cj.start_step + len(shard)
        sizes[lo:hi, sl] = shard[:, None]
    base = tables[0][2].astype(np.float64)  # job 0's planned offsets
    if R > len(base):
        cadence = base[-1] - base[-2] if len(base) > 1 else 1.0
        cadence = max(cadence, 1.0)
        extra = base[-1] + cadence * np.arange(1, R - len(base) + 1)
        base = np.concatenate([base, extra])
    offsets = np.asarray(np.round(base[:R]), np.int64)
    return sizes, offsets


def solo_size_variants(cluster: Cluster, sizes: np.ndarray) -> np.ndarray:
    """Stack the contended run with J solo variants: ``[1 + J, R, F]``.

    Variant 0 is the full cluster; variant 1 + j silences every flow NOT
    owned by job j (size 0 -> completes at tick 0, emits nothing), so the
    solo baseline runs on the identical fabric, events and PRNG stream —
    slowdown is a paired comparison inside one compiled program.
    """
    variants = [sizes]
    flow_job = cluster.flow_job
    for j in range(len(cluster.jobs)):
        v = sizes.copy()
        v[:, flow_job != j] = 0
        variants.append(v)
    return np.stack(variants)


def cluster_inputs(
    cluster: Cluster,
    sched: EventSchedule,
    horizon: int,
    rounds: Optional[int] = None,
) -> Tuple[EventSchedule, jax.Array]:
    """Batched runner inputs: per-round event schedules re-based at each
    round's planned offset, plus the [1 + J, R, F] size variants.

    `rounds` pads the round axis up to a common length (R = rounds) with
    all-silent rounds — every flow size 0, so they complete at tick 0 and
    emit nothing — letting clusters with different round counts (e.g. a
    staggered placement next to an aligned one) share one array shape on a
    stacked scenario axis.  Padded rounds read events past the planned
    timeline at job 0's trailing cadence and are never consulted by
    `cluster_metrics` (each job's slice ends at its real last round).
    """
    sizes, offsets = cluster_round_table(cluster)
    if rounds is not None:
        if rounds < cluster.rounds:
            raise ValueError(
                f"rounds={rounds} < the cluster's {cluster.rounds} rounds"
            )
        pad = rounds - cluster.rounds
        if pad:
            sizes = np.concatenate(
                [sizes, np.zeros((pad, cluster.flows), np.int32)]
            )
            cadence = (
                max(float(offsets[-1] - offsets[-2]), 1.0)
                if len(offsets) > 1 else 1.0
            )
            extra = offsets[-1] + np.round(
                cadence * np.arange(1, pad + 1)
            ).astype(offsets.dtype)
            offsets = np.concatenate([offsets, extra])
    scheds = scheduled_events(sched, offsets, horizon)
    return scheds, jnp.asarray(solo_size_variants(cluster, sizes))


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def run_cluster_rounds(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    sizes: jax.Array,
    key: jax.Array,
    horizon: int = 2048,
) -> Dict[str, jax.Array]:
    """Every round x size-variant of the cluster, ONE compiled computation.

    `scheds` carries a leading round axis R (from `cluster_inputs`),
    `sizes[..., R, F]` the traced per-flow messages (any leading variant
    axes).  Round r folds r into `key` — the SAME stream for every variant,
    so contended-vs-solo differences are contention, not noise.  Returns
    ``{"cct": [..., R, F], "finished": [..., R, F],
    "link_served": [..., R, L]}``.

    The round axis runs as a SEQUENTIAL `lax.map` (variant axes vmap
    inside each round): with the engine's early-exit mode every round then
    stops at its own last completion instead of synchronizing with the
    slowest round of the whole batch — silent rounds (size 0 everywhere,
    e.g. staggered-start padding) cost one chunk, not the global maximum.

    With `spec.telemetry` set, a "telemetry" key carries the in-scan
    `TelemetryFrame`; unlike the metric arrays (round axis moved to -2),
    the frame's leaves keep the ROUND axis leading, then any variant axes:
    ``telemetry.frame_select(frame, (r, v))`` reads round r of variant v.
    """
    R = sizes.shape[-2]

    def one_round(sched_r, sizes_rf, idx):
        k = jax.random.fold_in(key, idx)
        r = run_flows_sized(topo, sched_r, spec, sp, sizes_rf, k, horizon)
        frame = None
        if spec.telemetry is not None:
            r, frame = r
        out = dict(
            cct=r.cct, finished=r.finished,
            link_served=r.link_served, link_busy=r.link_busy,
        )
        if frame is not None:
            out["telemetry"] = frame
        return out

    def per_round(sched_r, sizes_r, idx):
        f = lambda s: one_round(sched_r, s, idx)  # noqa: E731
        for _ in range(sizes.ndim - 2):  # map any leading variant axes
            f = jax.vmap(f)
        return f(sizes_r)

    out = jax.lax.map(
        lambda args: per_round(*args),
        (scheds, jnp.moveaxis(sizes, -2, 0), jnp.arange(R)),
    )
    # the telemetry frame is a nested pytree with non-uniform leaf ranks —
    # keep its round axis leading rather than forcing it to -2
    frame = out.pop("telemetry", None)
    res = {k: jnp.moveaxis(v, 0, -2) for k, v in out.items()}
    if frame is not None:
        res["telemetry"] = frame
    return res


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def sweep_cluster_rounds(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    sizes: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
) -> Dict[str, jax.Array]:
    """The one-compile cluster sweep: policies x draws x variants x rounds.

    `sp` carries a leading policy/config axis P, `keys` is [D, 2] PRNG
    draws, `sizes` is [V, R, F] (from `cluster_inputs`: V = 1 + J solo
    variants).  Returns ``{"cct": [P, D, V, R, F], "finished": ...,
    "link_served": [P, D, V, R, L]}`` — one XLA program per (scenario,
    spec, shapes): jobs, policies, draws, solo baselines and every round
    all ride the same compile.
    """
    return jax.vmap(
        lambda s: jax.vmap(
            lambda k: run_cluster_rounds(topo, scheds, spec, s, sizes, k, horizon)
        )(keys)
    )(sp)


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def sweep_cluster_rounds_scenarios(
    topos: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    sizes: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
) -> Dict[str, jax.Array]:
    """`sweep_cluster_rounds` with a leading SCENARIO axis C everywhere.

    `topos` / `scheds` / `sizes` carry stacked per-scenario arrays (uniform
    shapes — pad round counts via `cluster_inputs(..., rounds=R_max)` and
    build placements on a common leaf grid), so the whole cluster scenario
    library x policies x draws x variants x rounds compiles ONCE:
    ``{"cct": [C, P, D, V, R, F], ...}``.  Scenario c computes exactly what
    `sweep_cluster_rounds(topos[c], scheds[c], ..., sizes[c], ...)` would.

    Like the round axis, the scenario axis is a SEQUENTIAL `lax.map`
    (policies/draws/variants stay vmapped inside): early-exit then settles
    per scenario, so an uncontended library entry doesn't pay for the
    oversubscribed one's tail ticks.
    """
    return jax.lax.map(
        lambda args: sweep_cluster_rounds(
            args[0], args[1], spec, sp, args[2], keys, horizon
        ),
        (topos, scheds, sizes),
    )


def _shard_round_scan(local_run, topo_g, scheds, sp, sizes_g, key):
    """The round-axis `lax.map` of `run_cluster_rounds`, per shard: the
    per-flow metric arrays stay local (the caller's out_specs stitch the
    flow axis back together), the link counters are already global."""
    R = sizes_g.shape[-2]

    def one_round(sched_r, sizes_rf, idx):
        k = jax.random.fold_in(key, idx)
        r = local_run(topo_g, sched_r, sp, sizes_rf, k)
        return dict(
            cct=r.cct, finished=r.finished,
            link_served=r.link_served, link_busy=r.link_busy,
        )

    def per_round(sched_r, sizes_r, idx):
        f = lambda s: one_round(sched_r, s, idx)  # noqa: E731
        for _ in range(sizes_g.ndim - 2):  # map any leading variant axes
            f = jax.vmap(f)
        return f(sizes_r)

    out = jax.lax.map(
        lambda args: per_round(*args),
        (scheds, jnp.moveaxis(sizes_g, -2, 0), jnp.arange(R)),
    )
    return {k: jnp.moveaxis(v, 0, -2) for k, v in out.items()}


def _shard_cluster_setup(topo, spec, sizes, horizon, mesh):
    from repro.net.sender import _local_flow_run, _pad_flow_axis, _pad_topology

    n_shards = int(mesh.shape[FLOW_AXIS])
    F = int(topo.route.shape[-2])
    F_pad = -(-F // n_shards) * n_shards
    topo_g = _pad_topology(topo, F_pad)
    sizes_g = _pad_flow_axis(jnp.asarray(sizes), F_pad, -1, fill=0)
    local_run = _local_flow_run(spec, horizon, F, n_shards)
    return topo_g, sizes_g, local_run, F


def _cluster_out_specs(n_lead: int):
    """{cct, finished} sharded on the trailing flow axis (after `n_lead`
    sweep/variant/round axes), link counters replicated."""
    P = jax.sharding.PartitionSpec
    f = P(*([None] * n_lead + [FLOW_AXIS]))
    return dict(cct=f, finished=f, link_served=P(), link_busy=P())


def _strip_cluster_pad(out, F):
    cut = lambda x: jax.lax.slice_in_dim(x, 0, F, axis=x.ndim - 1)  # noqa: E731
    return {
        k: cut(v) if k in ("cct", "finished") else v for k, v in out.items()
    }


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_run_cluster_rounds(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    sizes: jax.Array,
    key: jax.Array,
    horizon: int = 2048,
    *,
    mesh,
) -> Dict[str, jax.Array]:
    """`run_cluster_rounds` with the cluster's flow axis sharded over `mesh`
    (see `sender.flow_mesh`): bit-identical ``{"cct": [..., R, F], ...}``,
    each round's coupled simulation split across the mesh devices (flow counts
    that don't divide the device count are padded with silent flows and
    sliced back off).  Telemetry is not supported on this path."""
    from jax.experimental.shard_map import shard_map

    topo_g, sizes_g, local_run, F = _shard_cluster_setup(
        topo, spec, sizes, horizon, mesh
    )
    P = jax.sharding.PartitionSpec
    out = shard_map(
        functools.partial(_shard_round_scan, local_run),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=_cluster_out_specs(sizes_g.ndim - 1),
        check_rep=False,
    )(topo_g, scheds, sp, sizes_g, key)
    return _strip_cluster_pad(out, F)


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_sweep_cluster_rounds(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    sizes: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
    *,
    mesh,
) -> Dict[str, jax.Array]:
    """`sweep_cluster_rounds` sharded over the flow axis: bit-identical
    ``{"cct": [P, D, V, R, F], ...}``, policies x draws riding vmaps inside
    the shard body."""
    from jax.experimental.shard_map import shard_map

    topo_g, sizes_g, local_run, F = _shard_cluster_setup(
        topo, spec, sizes, horizon, mesh
    )
    P = jax.sharding.PartitionSpec

    def body(topo_b, scheds_b, sp_b, sizes_b, keys_b):
        return jax.vmap(
            lambda s: jax.vmap(
                lambda k: _shard_round_scan(
                    local_run, topo_b, scheds_b, s, sizes_b, k
                )
            )(keys_b)
        )(sp_b)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=_cluster_out_specs(sizes_g.ndim + 1),
        check_rep=False,
    )(topo_g, scheds, sp, sizes_g, keys)
    return _strip_cluster_pad(out, F)


def jain_index(x: np.ndarray, axis: int = -1) -> np.ndarray:
    """Jain's fairness index (sum x)^2 / (J * sum x^2) along `axis`: 1.0
    when every job gets an equal share, -> 1/J under total capture."""
    x = np.asarray(x, np.float64)
    num = x.sum(axis=axis) ** 2
    den = x.shape[axis] * (x**2).sum(axis=axis)
    return num / np.maximum(den, 1e-12)


def link_utilization(
    topo: TopologyParams, link_served: np.ndarray, link_busy: np.ndarray
) -> np.ndarray:
    """Per-link utilization over the whole cluster run.

    ``link_served[..., R, L]`` / ``link_busy[..., R, L]`` are the fabric's
    cumulative served-packets and busy-ticks conservation counters per
    round.  Utilization = served / (nominal capacity x busy ticks): 1.0 is
    a link serving at line rate whenever it serves at all; events that
    scale capacity below nominal read as REDUCED utilization, matching how
    operators read link counters against line rate.  Links that never serve
    report 0.
    """
    served = np.asarray(link_served, np.float64).sum(axis=-2)   # [..., L]
    busy = np.asarray(link_busy, np.float64).sum(axis=-2)       # [..., L]
    cap = np.asarray(topo.capacity, np.float64)                 # [L]
    return served / np.maximum(cap * busy, 1e-9)


@dataclasses.dataclass(frozen=True)
class ClusterResult:
    """Host-side result of one cluster run (see `cluster_metrics`)."""

    cluster: Cluster
    step_cct: Tuple[np.ndarray, ...]   # per job: [..., S_j] contended barriers
    ettr: np.ndarray                   # [..., J] contended per-job ETTR
    solo_ettr: np.ndarray              # [..., J] same fabric, job alone
    slowdown: np.ndarray               # [..., J] contended time / solo time
    jain: np.ndarray                   # [...] fairness over 1/slowdown
    link_util: np.ndarray              # [..., L] contended-run utilization
    finished: np.ndarray               # bool [...] all variants/rounds done


def cluster_metrics(
    cluster: Cluster,
    topo: TopologyParams,
    raw: Dict[str, jax.Array],
) -> ClusterResult:
    """Fold the raw ``[..., V, R, F]`` sweep output into per-job metrics.

    Per job j: its contended step barriers come from variant 0's rounds
    [start_j, start_j + S_j) maxed over its own flows, its solo barriers
    from variant 1 + j; `jobs.job_ettr` turns both into (ETTR, exposed).
    slowdown_j = (compute + exposed contended) / (compute + exposed solo),
    Jain fairness over x_j = 1 / slowdown_j, and link utilization from the
    contended variant's conservation counters.
    """
    cct = np.asarray(raw["cct"], np.float64)          # [..., V, R, F]
    finished = np.asarray(raw["finished"], bool)      # [..., V, R, F]
    link_served = np.asarray(raw["link_served"])      # [..., V, R, L]
    link_busy = np.asarray(raw["link_busy"])          # [..., V, R, L]
    lead = cct.shape[:-3]
    J = len(cluster.jobs)

    step_cct, ettrs, solos, slowdowns = [], [], [], []
    for j, cj in enumerate(cluster.jobs):
        S = cj.job.total_steps
        rounds = slice(cj.start_step, cj.start_step + S)
        fl = cluster.job_flows(j)
        barrier = cct[..., 0, rounds, fl].max(axis=-1)        # [..., S]
        barrier_solo = cct[..., 1 + j, rounds, fl].max(axis=-1)
        e, exp = job_ettr(cj.job, barrier)
        e_solo, exp_solo = job_ettr(cj.job, barrier_solo)
        compute = cj.job.compute_ticks * cj.job.iterations
        step_cct.append(barrier)
        ettrs.append(e)
        solos.append(e_solo)
        slowdowns.append((compute + exp) / (compute + exp_solo))
    ettr = np.stack(ettrs, axis=-1)                   # [..., J]
    solo = np.stack(solos, axis=-1)
    slowdown = np.stack(slowdowns, axis=-1)
    jain = jain_index(1.0 / np.maximum(slowdown, 1e-9), axis=-1)
    util = link_utilization(
        topo, link_served[..., 0, :, :], link_busy[..., 0, :, :]
    )
    return ClusterResult(
        cluster=cluster,
        step_cct=tuple(step_cct),
        ettr=ettr,
        solo_ettr=solo,
        slowdown=slowdown,
        jain=jain,
        link_util=util,
        finished=finished.reshape(lead + (-1,)).all(axis=-1),
    )


def run_cluster(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    cluster: Cluster,
    key: jax.Array,
    horizon: int = 2048,
) -> ClusterResult:
    """Run the whole cluster under one scenario with scalar sender params."""
    if topo.flows != cluster.flows:
        raise ValueError(
            f"topology has {topo.flows} flows but the cluster places "
            f"{cluster.flows}"
        )
    scheds, sizes = cluster_inputs(cluster, sched, horizon)
    raw = run_cluster_rounds(topo, scheds, spec, sp, sizes, key, horizon)
    return cluster_metrics(cluster, topo, raw)


def sweep_cluster(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    cluster: Cluster,
    keys: jax.Array,
    horizon: int = 2048,
    *,
    mesh=None,
) -> ClusterResult:
    """Host convenience over `sweep_cluster_rounds`: P policies x D draws,
    one compile.  Metric fields carry leading [P, D] axes
    (``ettr[P, D, J]``, ``jain[P, D]``, ``link_util[P, D, L]``, ...).

    With `mesh` (a `sender.flow_mesh`) the raw sweep runs flow-sharded via
    `shard_sweep_cluster_rounds` — bit-identical raw outputs, so every
    derived metric is too."""
    if topo.flows != cluster.flows:
        raise ValueError(
            f"topology has {topo.flows} flows but the cluster places "
            f"{cluster.flows}"
        )
    scheds, sizes = cluster_inputs(cluster, sched, horizon)
    if mesh is not None:
        raw = shard_sweep_cluster_rounds(
            topo, scheds, spec, sp, sizes, keys, horizon, mesh=mesh
        )
    else:
        raw = sweep_cluster_rounds(topo, scheds, spec, sp, sizes, keys, horizon)
    return cluster_metrics(cluster, topo, raw)
