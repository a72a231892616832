"""Job-level schedule compiler + runner: training steps as collective
schedules on the shared fabric (the paper's §1 ETTR claim at job scope).

The paper's headline metric is not per-message CCT but *job-level*
effective training time ratio — how much of a training job's wall clock is
compute versus communication exposed by stragglers, flaps and contention.
This module closes that loop:

  1. `compile_job` turns a model config (`repro.configs`) plus a DP x TP
     layout into a `JobSchedule`: per iteration, a compute window (ticks,
     from the `analysis.costs` roofline terms) and a sequence of ring
     collectives — allreduce of the bf16 gradients, allgather of the
     updated parameter shards — each sized from the REAL per-arch byte
     counts (`analysis.costs.job_comm_terms`) and mapped into simulator
     packets.
  2. `run_job` / `sweep_job` execute every ring step of every phase of
     every iteration on the shared leaf–spine fabric through the unified
     sender engine.  Message sizes ride the TRACED path
     (`sender.run_flows_sized`), so policies x model configs x PRNG draws
     x all schedule steps are ONE compiled program per scenario — the same
     one-compile idiom as `sender.sweep_flows`, extended with a model axis.
  3. `job_ettr` folds the simulated step barriers back into the job metric:

         ETTR = compute_ticks / (compute_ticks + exposed_comm_ticks)

     where a phase's exposed communication is max(0, CCT - overlap window)
     — collectives hide under the compute they overlap with (grads
     allreduce under the backward pass, params allgather under the next
     forward), and only the overhang stalls the accelerators.

Scenario composition: event schedules from `repro.net.scenarios` are
positioned against the job's PLANNED timeline (ideal compute + ideal comm,
host-computed, static) — each step's simulation reads the scenario's events
starting at that step's planned offset.  A `link_flap` therefore lands
mid-iteration and a `straggler_worker` persists across iterations, while
every step still compiles into one fused program (actual completion times
feed the ETTR, not the event clock; this keeps the whole sweep a single
XLA computation instead of a host-side serial replay).

Calibration: one fabric tick is anchored so the job's ideal communication
ticks equal its ideal communication seconds (`tick_seconds`); the compute
window is then `compute_comm_ratio` x ideal comm ticks.  Byte-to-packet
mapping compresses real shard sizes into the simulator's regime
(`pkt_bytes * pkt_scale` real bytes per simulated packet, clipped to
[min_shard, max_shard]) — the same regime compression the cross-layer
bench uses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.costs import job_comm_terms
from repro.configs.base import ArchConfig, ShapeSpec
from repro.configs.registry import get_config
from repro.net.sender import (
    FLOW_AXIS,
    SenderParams,
    SenderSpec,
    run_flows_sized,
)
from repro.net.topology import EventSchedule, TopologyParams

__all__ = [
    "JobPhase",
    "JobSchedule",
    "JobResult",
    "compile_job",
    "step_table",
    "total_packets",
    "scheduled_events",
    "job_step_inputs",
    "run_job_steps",
    "sweep_job_steps",
    "sweep_job_steps_scenarios",
    "shard_run_job_steps",
    "shard_sweep_job_steps",
    "run_job",
    "sweep_job",
    "job_ettr",
]

# default per-phase overlap budget, as a fraction of the compute window:
# the gradient allreduce hides under the backward pass, the parameter
# allgather under (the start of) the next forward.
DEFAULT_OVERLAP = {"allreduce": 0.5, "allgather": 0.25}


@dataclasses.dataclass(frozen=True)
class JobPhase:
    """One collective phase of a training iteration (static, host-side)."""

    kind: str                # "allreduce" | "allgather"
    shard_packets: int       # simulator packets per ring step per worker
    ring_steps: int          # 2(W-1) for allreduce, W-1 for allgather
    overlap_ticks: float     # compute window this phase can hide under
    ideal_step_ticks: float  # fluid lower bound for one step (planning)

    @property
    def payload_packets(self) -> int:
        """Per-worker payload of the whole phase (all ring steps)."""
        return self.ring_steps * self.shard_packets


@dataclasses.dataclass(frozen=True)
class JobSchedule:
    """A compiled training job: iterations of compute + collective phases."""

    arch: str
    workers: int             # DP degree == ring flows on the fabric
    iterations: int
    compute_ticks: float     # per-iteration compute window (fabric ticks)
    tick_seconds: float      # calibration: seconds of real time per tick
    compute_comm_ratio: float
    phases: Tuple[JobPhase, ...]

    @property
    def steps_per_iteration(self) -> int:
        return sum(p.ring_steps for p in self.phases)

    @property
    def total_steps(self) -> int:
        return self.iterations * self.steps_per_iteration

    @property
    def ideal_comm_ticks(self) -> float:
        """Per-iteration fluid lower bound on total collective time."""
        return sum(p.ring_steps * p.ideal_step_ticks for p in self.phases)


@dataclasses.dataclass(frozen=True)
class JobResult:
    """Host-side result of one job run (see `job_ettr` for the math)."""

    job: JobSchedule
    step_cct: np.ndarray         # [..., total_steps] barrier per ring step
    ettr: np.ndarray             # [...] compute / (compute + exposed comm)
    exposed_comm_ticks: np.ndarray  # [...] summed over iterations + phases
    # per-step: every worker finished within the horizon.  A False entry
    # means that step's barrier is the horizon sentinel — the ETTR built on
    # it is an upper bound, not a measurement.
    finished: np.ndarray         # bool [..., total_steps]


def compile_job(
    arch: str | ArchConfig,
    *,
    workers: int = 4,
    tp: int = 8,
    shape: ShapeSpec | None = None,
    iterations: int = 2,
    pkt_bytes: float = 4096.0,
    pkt_scale: float = 64.0,
    min_shard: int = 16,
    max_shard: int = 2048,
    rate: int = 32,
    n_spines: int = 4,
    link_capacity: float = 8.0,
    latency_ticks: int = 4,
    overlap: Mapping[str, float] | None = None,
    include_allgather: bool = True,
) -> JobSchedule:
    """Compile a model config into a per-iteration collective schedule.

    `shape` defaults to a one-sample-per-rank training microbatch
    (`global_batch == workers`), the regime where gradient synchronization
    is actually exposed; the full-batch `SHAPES["train_4k"]` would bury
    communication under ~100x more compute and every policy would tie at
    ETTR ~= 1.  `workers` is the DP degree (each worker is one flow on the
    ring fabric) and `tp` the model-parallel degree that shards the
    parameter/gradient bytes before they hit the DCN fabric.
    """
    cfg = get_config(arch) if isinstance(arch, str) else arch
    if shape is None:
        shape = ShapeSpec("train_micro", 4096, workers, "train")
    if iterations < 1:
        raise ValueError(f"need iterations >= 1, got {iterations}")
    overlap = dict(DEFAULT_OVERLAP, **(overlap or {}))
    terms = job_comm_terms(cfg, shape, dp=workers, tp=tp)

    bytes_per_sim_pkt = pkt_bytes * pkt_scale
    eff_rate = min(float(rate), n_spines * link_capacity)

    def shard_of(total_bytes: float) -> int:
        return int(
            np.clip(total_bytes / workers / bytes_per_sim_pkt, min_shard, max_shard)
        )

    def ideal_ticks(shard: int) -> float:
        return shard / eff_rate + latency_ticks + 1.0

    phase_specs = [("allreduce", terms["grad_bytes"], 2 * (workers - 1))]
    if include_allgather:
        phase_specs.append(("allgather", terms["param_bytes"], workers - 1))

    # calibration pass: tick_seconds anchors ideal comm ticks to ideal comm
    # seconds, then the compute window follows from the roofline ratio.
    prelim = [
        (kind, shard_of(b), steps) for kind, b, steps in phase_specs
    ]
    ideal_comm = sum(steps * ideal_ticks(shard) for _, shard, steps in prelim)
    t_comm_s = sum(
        terms[f"t_{kind}_s"] for kind, _, _ in phase_specs
    )
    tick_seconds = t_comm_s / max(ideal_comm, 1e-9)
    ratio = float(np.clip(terms["compute_comm_ratio"], 0.05, 50.0))
    compute_ticks = ratio * ideal_comm

    phases = tuple(
        JobPhase(
            kind=kind,
            shard_packets=shard,
            ring_steps=steps,
            overlap_ticks=overlap.get(kind, 0.0) * compute_ticks,
            ideal_step_ticks=ideal_ticks(shard),
        )
        for kind, shard, steps in prelim
    )
    return JobSchedule(
        arch=cfg.name,
        workers=workers,
        iterations=iterations,
        compute_ticks=compute_ticks,
        tick_seconds=tick_seconds,
        compute_comm_ratio=ratio,
        phases=phases,
    )


def total_packets(job: JobSchedule) -> int:
    """Total packets the schedule injects into the fabric over the whole
    job: workers x iterations x sum of phase payloads.  Conservation
    contract with `step_table`: equals `workers * step_table(job)[0].sum()`.
    """
    return job.workers * job.iterations * sum(
        p.payload_packets for p in job.phases
    )


def step_table(job: JobSchedule) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flatten the schedule into per-ring-step arrays (host, static).

    Returns ``(shard[S], phase_idx[S], planned_offset[S])`` with
    S = job.total_steps.  Planned offsets place each step on the job's
    IDEAL timeline: every iteration opens with its compute window, each
    phase starts as soon as its overlap budget allows (it may begin
    `overlap_ticks` before the compute window closes, but never before the
    previous phase's planned finish), and steps within a phase serialize at
    their fluid lower bound.  Scenario event schedules are read from these
    offsets (`scheduled_events`), which is what makes a mid-run link flap
    hit a mid-iteration step.
    """
    shard, phase_idx, offsets = [], [], []
    iter_start = 0.0
    for _ in range(job.iterations):
        compute_end = iter_start + job.compute_ticks
        cursor = iter_start  # planned finish of the previous phase
        for pi, ph in enumerate(job.phases):
            start = max(compute_end - ph.overlap_ticks, cursor, iter_start)
            cursor = start
            for _s in range(ph.ring_steps):
                shard.append(ph.shard_packets)
                phase_idx.append(pi)
                offsets.append(cursor)
                cursor += ph.ideal_step_ticks
        iter_start = max(cursor, compute_end)
    return (
        np.asarray(shard, np.int32),
        np.asarray(phase_idx, np.int32),
        np.asarray(np.round(offsets), np.int64),
    )


def scheduled_events(
    sched: EventSchedule, offsets: np.ndarray, horizon: int
) -> EventSchedule:
    """Re-base a scenario's event schedule at each planned step offset.

    `offsets` may have any shape (e.g. [S] or [models, S]); the returned
    `EventSchedule` arrays gain those leading axes:
    ``cap_scale[*offsets.shape, horizon, L]``.  Row t of slice o is the
    scenario's row min(o + t, T-1) — the same "last row persists" contract
    as the fabric stepper, shifted to the step's planned start time.
    """
    cap = np.asarray(sched.cap_scale)
    bg = np.asarray(sched.bg_arrivals)
    T = cap.shape[0]
    idx = np.minimum(offsets[..., None] + np.arange(horizon), T - 1)
    return EventSchedule(
        cap_scale=jnp.asarray(cap[idx], jnp.float32),
        bg_arrivals=jnp.asarray(bg[idx], jnp.float32),
    )


def job_step_inputs(
    jobs: Sequence[JobSchedule], sched: EventSchedule, horizon: int
) -> Tuple[EventSchedule, jax.Array]:
    """Build the batched runner inputs for M jobs sharing one scenario.

    Returns ``(scheds, shard)`` with scheds' arrays shaped
    [M, S, horizon, L] and shard [M, S] (traced int32).  All jobs must
    share the schedule *structure* (workers, iterations, phase step
    counts) so S matches — shard sizes, compute windows and planned
    offsets are free to differ per model.
    """
    struct = {(j.workers, j.iterations, tuple(p.ring_steps for p in j.phases))
              for j in jobs}
    if len(struct) != 1:
        raise ValueError(
            f"jobs must share workers/iterations/phase structure, got {struct}"
        )
    tables = [step_table(j) for j in jobs]
    shard = np.stack([t[0] for t in tables])                    # [M, S]
    offsets = np.stack([t[2] for t in tables])                  # [M, S]
    return scheduled_events(sched, offsets, horizon), jnp.asarray(shard)


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def run_job_steps(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    shard: jax.Array,
    key: jax.Array,
    horizon: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """Barrier time of every schedule step, ONE compiled computation.

    `scheds` carries a leading step axis S (from `scheduled_events`),
    `shard[S]` the traced per-step message sizes.  Step s folds s into
    `key`, runs the W coupled ring flows via the traced-size sender core,
    and reports the synchronous barrier (max over workers).  Returns
    ``(cct[S], finished[S])`` — finished is True only when every worker
    completed within the horizon (False: the barrier is the sentinel).

    The step axis is a SEQUENTIAL `lax.map` so that, with the engine's
    early-exit mode, each ring step stops at its own barrier instead of
    synchronizing with the slowest step of the schedule.

    With `spec.telemetry` set the engine's in-scan capture rides along:
    the return value becomes ``(cct[S], finished[S], frame)`` where the
    `TelemetryFrame` leaves carry a leading step axis S (peel with
    `telemetry.frame_select(frame, s)` to read step s's series).
    """
    S = shard.shape[0]

    def one(args):
        sched_s, shard_s, idx = args
        k = jax.random.fold_in(key, idx)
        r = run_flows_sized(topo, sched_s, spec, sp, shard_s, k, horizon)
        if spec.telemetry is not None:
            r, frame = r
            return jnp.max(r.cct), jnp.all(r.finished), frame
        return jnp.max(r.cct), jnp.all(r.finished)

    return jax.lax.map(one, (scheds, shard, jnp.arange(S)))


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def sweep_job_steps(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    shard: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """The one-compile job sweep: policies x draws x models x steps.

    `sp` carries a leading policy/config axis P, `keys` is [D, 2] PRNG
    draws, `scheds`/`shard` carry leading [M, S] axes (from
    `job_step_inputs`).  Returns ``(cct[P, D, M, S], finished[P, D, M, S])``
    — one XLA program per (scenario, spec, shapes), exactly like
    `sender.sweep_flows` but with the message-size and event-offset axes of
    the job layer on top.
    """
    def per_model(s, k):
        return jax.vmap(
            lambda sched_m, shard_m: run_job_steps(
                topo, sched_m, spec, s, shard_m, k, horizon
            )
        )(scheds, shard)

    return jax.vmap(
        lambda s: jax.vmap(lambda k: per_model(s, k))(keys)
    )(sp)


@functools.partial(jax.jit, static_argnames=("spec", "horizon"))
def sweep_job_steps_scenarios(
    topos: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    shard: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """`sweep_job_steps` with a leading SCENARIO axis C on topology/events.

    `topos` carries stacked per-scenario `TopologyParams` arrays and
    `scheds` stacked [C, M, S, horizon, L] event schedules (one
    `job_step_inputs` per scenario, tree-stacked; the job scenario library
    already shares one topology shape).  `shard[M, S]` is scenario-
    independent.  Returns ``(cct[C, P, D, M, S], finished[...])`` — the
    WHOLE scenario library x policies x draws x models x steps as ONE
    compiled XLA program; scenario c computes exactly what
    `sweep_job_steps(topos[c], scheds[c], ...)` would.

    The scenario axis is a SEQUENTIAL `lax.map` (policies/draws/models stay
    vmapped inside): with early-exit enabled each scenario settles at its
    own pace instead of paying for the slowest library entry's tail.
    """
    return jax.lax.map(
        lambda args: sweep_job_steps(
            args[0], args[1], spec, sp, shard, keys, horizon
        ),
        (topos, scheds),
    )


def _shard_job_setup(topo, spec, shard, horizon, mesh):
    """Shared plumbing of the flow-sharded job runners: pad the ring-flow
    axis to a device multiple, broadcast the per-step scalar shard sizes to
    per-flow vectors (padding flows get size 0 and stay silent), and build
    the per-shard sender body."""
    from repro.net.sender import _local_flow_run, _pad_flow_axis, _pad_topology

    n_shards = int(mesh.shape[FLOW_AXIS])
    F = int(topo.route.shape[-2])
    F_pad = -(-F // n_shards) * n_shards
    topo_g = _pad_topology(topo, F_pad)
    sizes = _pad_flow_axis(
        jnp.broadcast_to(
            jnp.asarray(shard)[..., None], shard.shape + (F,)
        ),
        F_pad, -1, fill=0,
    )
    local_run = _local_flow_run(spec, horizon, F, n_shards)
    return topo_g, sizes, local_run, n_shards


def _shard_step_scan(local_run, topo_g, scheds, sp, sizes, key, n_shards):
    """The step-axis `lax.map` of `run_job_steps`, per shard: each step's
    flow reductions become cross-shard collectives — `pmax` for the barrier
    (max is exact, so the sharded barrier is bitwise the unsharded one) and
    a psum-AND for the finished mask."""
    S = sizes.shape[0]

    def one(args):
        sched_s, sizes_s, idx = args
        k = jax.random.fold_in(key, idx)
        r = local_run(topo_g, sched_s, sp, sizes_s, k)
        cct = jax.lax.pmax(jnp.max(r.cct), FLOW_AXIS)
        fin = jax.lax.psum(
            jnp.all(r.finished).astype(jnp.int32), FLOW_AXIS
        ) == n_shards
        return cct, fin

    return jax.lax.map(one, (scheds, sizes, jnp.arange(S)))


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_run_job_steps(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    shard: jax.Array,
    key: jax.Array,
    horizon: int = 2048,
    *,
    mesh,
) -> Tuple[jax.Array, jax.Array]:
    """`run_job_steps` with the W ring flows sharded over `mesh` (see
    `sender.flow_mesh`): bit-identical ``(cct[S], finished[S])``, the
    per-step coupled simulation split across the mesh devices."""
    from jax.experimental.shard_map import shard_map

    topo_g, sizes, local_run, n_shards = _shard_job_setup(
        topo, spec, shard, horizon, mesh
    )
    P = jax.sharding.PartitionSpec

    def body(topo_b, scheds_b, sp_b, sizes_b, key_b):
        return _shard_step_scan(
            local_run, topo_b, scheds_b, sp_b, sizes_b, key_b, n_shards
        )

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_rep=False,
    )(topo_g, scheds, sp, sizes, key)


@functools.partial(jax.jit, static_argnames=("spec", "horizon", "mesh"))
def shard_sweep_job_steps(
    topo: TopologyParams,
    scheds: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    shard: jax.Array,
    keys: jax.Array,
    horizon: int = 2048,
    *,
    mesh,
) -> Tuple[jax.Array, jax.Array]:
    """`sweep_job_steps` sharded over the ring-flow axis: bit-identical
    ``(cct[P, D, M, S], finished[P, D, M, S])``, the policy/draw/model
    sweep axes riding vmaps inside the shard body."""
    from jax.experimental.shard_map import shard_map

    topo_g, sizes, local_run, n_shards = _shard_job_setup(
        topo, spec, shard, horizon, mesh
    )
    P = jax.sharding.PartitionSpec

    def body(topo_b, scheds_b, sp_b, sizes_b, keys_b):
        def per_model(s, k):
            return jax.vmap(
                lambda sched_m, sizes_m: _shard_step_scan(
                    local_run, topo_b, sched_m, s, sizes_m, k, n_shards
                )
            )(scheds_b, sizes_b)

        return jax.vmap(
            lambda s: jax.vmap(lambda k: per_model(s, k))(keys_b)
        )(sp_b)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_rep=False,
    )(topo_g, scheds, sp, sizes, keys)


def job_ettr(
    job: JobSchedule, step_cct: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Fold per-step barriers into (ettr, exposed_comm_ticks).

    `step_cct[..., S]` may carry any leading sweep axes.  Per iteration and
    phase, exposed communication is max(0, phase CCT - overlap window);
    ETTR = compute / (compute + exposed), in (0, 1] by construction (zero
    exposure means the job runs at full accelerator utilization).
    """
    step_cct = np.asarray(step_cct, np.float64)
    it, spi = job.iterations, job.steps_per_iteration
    arr = step_cct.reshape(step_cct.shape[:-1] + (it, spi))
    exposed = np.zeros(arr.shape[:-1], np.float64)  # [..., it]
    pos = 0
    for ph in job.phases:
        phase_cct = arr[..., pos:pos + ph.ring_steps].sum(axis=-1)
        exposed += np.maximum(phase_cct - ph.overlap_ticks, 0.0)
        pos += ph.ring_steps
    exposed_total = exposed.sum(axis=-1)            # [...]
    compute_total = job.compute_ticks * it
    ettr = compute_total / (compute_total + exposed_total)
    return ettr, exposed_total


def run_job(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    job: JobSchedule,
    key: jax.Array,
    horizon: int = 2048,
) -> JobResult:
    """Run one job under one scenario with scalar sender params.

    With `spec.telemetry` set, returns ``(JobResult, frame)`` — the frame's
    leaves carry a leading step axis S (see `run_job_steps`)."""
    if topo.flows != job.workers:
        raise ValueError(
            f"topology has {topo.flows} flows but job.workers={job.workers}"
        )
    shard, _, offsets = step_table(job)
    scheds = scheduled_events(sched, offsets, horizon)
    out = run_job_steps(
        topo, scheds, spec, sp, jnp.asarray(shard), key, horizon
    )
    frame = None
    if spec.telemetry is not None:
        cct, finished, frame = out
    else:
        cct, finished = out
    cct, finished = np.asarray(cct), np.asarray(finished)
    ettr, exposed = job_ettr(job, cct)
    result = JobResult(
        job=job, step_cct=cct, ettr=ettr, exposed_comm_ticks=exposed,
        finished=finished,
    )
    return result if frame is None else (result, frame)


def sweep_job(
    topo: TopologyParams,
    sched: EventSchedule,
    spec: SenderSpec,
    sp: SenderParams,
    jobs: Sequence[JobSchedule],
    keys: jax.Array,
    horizon: int = 2048,
    *,
    mesh=None,
) -> Dict[str, np.ndarray]:
    """Host convenience over `sweep_job_steps`: M jobs x P policies x D
    draws under one scenario, one compile.  Returns
    ``{"cct": [P, D, M, S], "finished": [P, D, M, S], "ettr": [P, D, M],
    "exposed": [P, D, M]}``; with `spec.telemetry` set, a "telemetry" key
    holds the `TelemetryFrame` whose leaves carry leading [P, D, M, S]
    sweep axes (peel with `telemetry.frame_select`).

    With `mesh` (a `sender.flow_mesh`) the raw sweep runs flow-sharded via
    `shard_sweep_job_steps` — bit-identical outputs, so every derived
    metric is too; telemetry capture is unsupported sharded.
    """
    if any(topo.flows != j.workers for j in jobs):
        raise ValueError("every job's workers must equal the topology's flows")
    scheds, shard = job_step_inputs(jobs, sched, horizon)
    if mesh is not None:
        out = shard_sweep_job_steps(
            topo, scheds, spec, sp, shard, keys, horizon, mesh=mesh
        )
    else:
        out = sweep_job_steps(
            topo, scheds, spec, sp, shard, keys, horizon
        )
    frame = None
    if spec.telemetry is not None:
        cct, finished, frame = out
    else:
        cct, finished = out
    cct, finished = np.asarray(cct), np.asarray(finished)
    ettr = np.zeros(cct.shape[:-1])
    exposed = np.zeros(cct.shape[:-1])
    for m, job in enumerate(jobs):
        ettr[..., m], exposed[..., m] = job_ettr(job, cct[..., m, :])
    res = {"cct": cct, "finished": finished, "ettr": ettr, "exposed": exposed}
    if frame is not None:
        res["telemetry"] = frame
    return res
