"""Job-level ETTR driver: compile a training step, run it on the fabric.

Turns a model config into a per-iteration collective schedule
(`repro.net.jobs.compile_job`), runs it against a job scenario
(`repro.net.scenarios.job_scenarios`) for each requested policy, and
prints the compiled schedule plus per-policy ETTR / exposed-communication
numbers.  The policy grid rides the one-compile sweep
(`jobs.sweep_job`) — adding policies does not add XLA programs.

    PYTHONPATH=src python -m repro.launch.jobsim \
        --arch qwen3-8b --scenario link_flap --workers 4 --iterations 2

    PYTHONPATH=src python -m repro.launch.jobsim --arch xlstm-350m \
        --scenario pfc_storm --policies WAM,ECMP --draws 4 --json out.json

``--devices N`` runs the sweep through the flow-sharded engine
(`jobs.shard_sweep_job_steps`) on a flow mesh over the first N devices of
the platform JAX runs on — bit-identical results, so it is a scale-out
execution knob, not a model change.  On the CPU (``JAX_PLATFORMS=cpu``)
the N host devices are forced before JAX initializes, which is why the
jax imports below live inside `main` (see `repro.launch.devices`).
Compiled programs persist in JAX's compilation cache
(`repro.launch.devices.setup_compile_cache`).
"""
from __future__ import annotations

import argparse
import json

from repro.launch.devices import (
    add_devices_arg, request_devices, setup_compile_cache,
)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--scenario", default="link_flap")
    ap.add_argument("--policies", default="ECMP,RR,RAND_STATIC,RAND_ADAPTIVE,WAM",
                    help="comma-separated Policy names")
    ap.add_argument("--workers", type=int, default=4, help="DP degree")
    ap.add_argument("--tp", type=int, default=8, help="model-parallel degree")
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--draws", type=int, default=2, help="PRNG repeats")
    ap.add_argument("--rate", type=int, default=32)
    ap.add_argument("--max-shard", type=int, default=512)
    ap.add_argument("--horizon", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", metavar="PATH", help="also dump results as JSON")
    add_devices_arg(ap)
    return ap


def job_sweep(args: argparse.Namespace, mesh=None):
    """Compile `args.arch` into its collective schedule and run the policy
    sweep under `args.scenario`: returns ``(job, policies, out)``, `out` as
    `jobs.sweep_job` returns it (flow-sharded over `mesh` when given)."""
    import jax

    from repro.net.jobs import compile_job, sweep_job
    from repro.net.scenarios import job_scenarios
    from repro.net.sender import SenderSpec, sender_params, stack_params
    from repro.net.transport import Policy

    policies = [Policy[p.strip()] for p in args.policies.split(",")]
    job = compile_job(
        args.arch, workers=args.workers, tp=args.tp,
        iterations=args.iterations, rate=args.rate,
        max_shard=args.max_shard,
    )
    scens = job_scenarios(
        workers=args.workers, horizon=max(args.horizon, 2048)
    )
    topo, sched = scens[args.scenario]
    spec = SenderSpec(rate_cap=args.rate)
    sp = stack_params([sender_params(p, rate=args.rate) for p in policies])
    keys = jax.random.split(jax.random.PRNGKey(args.seed), args.draws)
    out = sweep_job(
        topo, sched, spec, sp, [job], keys, horizon=args.horizon, mesh=mesh
    )
    return job, policies, out


def main(argv=None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.devices is not None:
        request_devices(args.devices)

    # post---devices imports: nothing above may initialize jax
    import jax
    import numpy as np

    setup_compile_cache()

    from repro.net.jobs import step_table, total_packets
    from repro.net.scenarios import JOB_SCENARIO_NAMES

    if args.scenario not in JOB_SCENARIO_NAMES:
        ap.error(
            f"--scenario {args.scenario!r}: choose from {JOB_SCENARIO_NAMES}"
        )
    mesh = None
    if args.devices is not None:
        from repro.net.sender import flow_mesh

        mesh = flow_mesh(args.devices)
        print(f"devices: flow mesh over {args.devices} "
              f"{jax.default_backend()} device(s) "
              f"(flow-sharded sweep, bit-identical to unsharded)")

    job, policies, out = job_sweep(args, mesh)
    shard, _, offsets = step_table(job)
    print(f"job {job.arch}: DP={job.workers} TP={args.tp} "
          f"iterations={job.iterations}")
    print(f"  compute window  {job.compute_ticks:8.1f} ticks "
          f"(compute:comm ratio {job.compute_comm_ratio:.2f}, "
          f"tick = {job.tick_seconds * 1e6:.1f} us)")
    for ph in job.phases:
        print(f"  {ph.kind:<10} {ph.ring_steps} steps x {ph.shard_packets} "
              f"pkt/worker, overlap window {ph.overlap_ticks:.1f} ticks")
    print(f"  total {total_packets(job)} packets over "
          f"{job.total_steps} ring steps; planned span "
          f"{int(offsets[-1])}+ ticks")

    print(f"\nscenario {args.scenario} ({args.draws} draws, "
          f"horizon {args.horizon}):")
    if not bool(np.all(out["finished"])):
        print("  WARNING: some ring steps hit the horizon sentinel — ETTR "
              "below is an upper bound, not a measurement (raise --horizon)")
    rows = {}
    for i, pol in enumerate(policies):
        ettr = out["ettr"][i, :, 0]
        exposed = out["exposed"][i, :, 0]
        rows[pol.name] = {
            "ettr_mean": float(ettr.mean()),
            "ettr_min": float(ettr.min()),
            "exposed_ticks_mean": float(exposed.mean()),
        }
        print(f"  {pol.name:<14} ETTR {ettr.mean():.4f} "
              f"(min {ettr.min():.4f})  exposed comm "
              f"{exposed.mean():8.1f} ticks")
    if args.json:
        payload = {
            "arch": job.arch, "scenario": args.scenario,
            "workers": job.workers, "iterations": job.iterations,
            "compute_ticks": job.compute_ticks,
            "policies": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
