"""Benchmark harness: one module per paper table/claim.
Prints ``name,us_per_call,derived`` CSV (plus section separators).

Flags:
  --smoke       fast small-shape pass (CI sanity, not paper-sized tables)
  --json PATH   also write results as a BENCH_*.json-compatible dict
  --only NAME   run a single section (substring match)
  --devices N   flow mesh over the first N devices (shard_map scale-out)

On the CPU (``JAX_PLATFORMS=cpu``) `--devices` works by exporting
``--xla_force_host_platform_device_count`` into XLA_FLAGS, which XLA reads
once, when JAX initializes — so this module must stay import-light: nothing
that (transitively) imports jax may run before `main` has handled the flag.
`benchmarks.common` is therefore imported inside `main`, after the
environment is set.  A section that cannot be imported fails the run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import platform
import sys
import time

from repro.launch.devices import request_devices, setup_compile_cache

# (section, module) — modules import lazily, after `--devices` is handled
SECTION_MODULES = [
    ("sec9_deviation_bounds", "bench_deviation"),
    ("sec4_worked_example", "bench_example_discrepancy"),
    ("sec8_time_varying", "bench_timevarying"),
    ("sec12_cct_ettr", "bench_cct"),
    ("topology_scenarios", "bench_topology"),
    ("scaleout_3tier", "bench_scaleout"),
    ("job_ettr", "bench_job_ettr"),
    ("cluster_contention", "bench_cluster"),
    ("policy_bakeoff", "bench_bakeoff"),
    ("recovery_dynamics", "bench_recovery"),
    ("spray_throughput", "bench_spray_throughput"),
    ("fountain_transport", "bench_fountain"),
    ("arch_ettr_crosslayer", "bench_arch_ettr"),
]


def _load_sections(only=None):
    return [
        (name, importlib.import_module(f"benchmarks.{mod}").main)
        for name, mod in SECTION_MODULES
        if only is None or only in name
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="fast small-shape pass")
    ap.add_argument("--json", metavar="PATH", help="write results dict to PATH")
    ap.add_argument("--only", metavar="NAME", help="run sections matching NAME")
    ap.add_argument(
        "--devices", type=int, metavar="N", default=None,
        help="make N devices of the platform JAX runs on available to the "
        "shard_map scale-out benches (flow meshes over the first N). On the "
        "CPU (JAX_PLATFORMS=cpu) N host devices are forced (XLA_FLAGS="
        "--xla_force_host_platform_device_count=N, set before jax "
        "initializes); on an accelerator N may not exceed the devices "
        "present",
    )
    ap.add_argument(
        "--telemetry", action="store_true",
        help="run the in-scan telemetry sections: one extra compiled "
        "program per bench family, recovery-time rows for link_flap / "
        "pfc_storm in meta.telemetry (see docs/BENCHMARKS.md)",
    )
    ap.add_argument(
        "--trace-dir", metavar="DIR", default=None,
        help="with --telemetry: export JSONL series + Perfetto trace JSON "
        "artifacts per telemetry row into DIR",
    )
    ap.add_argument(
        "--max-compiles", type=int, metavar="N", default=None,
        help="fail if the run compiles more than N programs in total "
        "(the scenario-family batching gate: see docs/BENCHMARKS.md)",
    )
    ap.add_argument(
        "--audit", action="store_true",
        help="run the jaxpr program audit (repro.analysis.jaxpr_audit) "
        "over every bench family: dtype/effect/telemetry discipline plus "
        "golden fingerprint pins — rows land in meta.audit and "
        "AUDIT_report.json; any violation or fingerprint drift fails "
        "the run (regen pins via `python -m repro.analysis.jaxpr_audit "
        "--write` after an intended program change)",
    )
    args = ap.parse_args(argv)
    if args.devices is not None:
        request_devices(args.devices)

    # deferred so --devices lands in XLA_FLAGS before jax initializes
    from benchmarks import common
    from repro.net.sender import flow_mesh

    if args.devices is not None:
        flow_mesh(args.devices)  # fail now if the platform has too few
    print(f"# compile cache: {setup_compile_cache()}", file=sys.stderr)
    common.set_smoke(args.smoke)
    common.set_telemetry(args.telemetry, args.trace_dir)

    sections = _load_sections(args.only)
    if not sections:
        raise SystemExit(f"no section matches --only {args.only!r}")

    print("name,us_per_call,derived")
    timings = {}
    for name, fn in sections:
        print(f"# === {name} ===", file=sys.stderr)
        t0 = time.time()
        fn()
        timings[name] = round(time.time() - t0, 1)
        print(f"# {name} done in {timings[name]:.1f}s", file=sys.stderr)

    audit_rows, audit_problems = [], []
    if args.audit:
        # static program audit: trace (don't compile) each family and check
        # dtype/effect/telemetry discipline + the golden fingerprint pins
        from repro.analysis import jaxpr_audit

        print("# === jaxpr audit ===", file=sys.stderr)
        t0 = time.time()
        audit_results = jaxpr_audit.audit_all()
        audit_rows = [r.row() for r in audit_results]
        audit_problems = [
            f"{r.family}: {v}" for r in audit_results for v in r.violations
        ]
        try:
            golden = jaxpr_audit.load_golden()
        except FileNotFoundError:
            audit_problems.append(
                f"{jaxpr_audit.GOLDEN_PATH} missing — run "
                "`python -m repro.analysis.jaxpr_audit --write`"
            )
        else:
            audit_problems.extend(
                jaxpr_audit.check_against_golden(audit_results, golden)
            )
        report = {
            "golden": jaxpr_audit.GOLDEN_PATH,
            "ok": not audit_problems,
            "problems": audit_problems,
            "rows": audit_rows,
        }
        with open("AUDIT_report.json", "w") as f:
            json.dump(report, f, indent=1)
        print(
            f"# jaxpr audit: {len(audit_rows)} families, "
            f"{len(audit_problems)} problem(s) in {time.time() - t0:.1f}s "
            "-> AUDIT_report.json",
            file=sys.stderr,
        )

    total_compiles = sum(r["compile_count"] for r in common.COMPILE_STATS)
    if args.json:
        payload = {
            "meta": {
                "smoke": args.smoke,
                "sections": timings,
                "python": platform.python_version(),
                "platform": platform.platform(),
                # execution environment: backend, device count (forced host
                # devices under --devices on the CPU), the requested flow
                # mesh and XLA flags — scaling rows in meta.perf are
                # uninterpretable without it
                "env": common.env_info(requested_devices=args.devices),
                # sweep-speed visibility: every row that reported compile
                # accounting, plus totals — a compile-count regression (e.g.
                # a sweep silently falling back to per-policy programs)
                # shows up directly in the bench trajectory.
                "compile": {
                    "total_compiles": total_compiles,
                    "total_compile_s": round(
                        sum(r["compile_s"] for r in common.COMPILE_STATS), 3
                    ),
                    "rows": common.COMPILE_STATS,
                },
                # simulator throughput trajectory: fabric ticks/s and path
                # decisions/s per family sweep, with the run-vs-compile wall
                # split (see benchmarks.common.perf / docs/BENCHMARKS.md)
                "perf": {
                    "rows": common.PERF_STATS,
                    "total_run_s": round(
                        sum(r["run_s"] for r in common.PERF_STATS), 3
                    ),
                    "total_compile_s": round(
                        sum(r["compile_s"] for r in common.PERF_STATS), 3
                    ),
                },
            },
            "results": common.RESULTS,
        }
        if common.BAKEOFF_STATS:
            # policy bake-off ranking rows: one per (family, scenario,
            # metric), with the full 8-policy ordering and the explicit
            # wam_wins/margin verdict (see docs/BENCHMARKS.md meta.bakeoff)
            payload["meta"]["bakeoff"] = {"rows": common.BAKEOFF_STATS}
        if common.RECOVERY_STATS:
            # correlated-failure recovery rows: one per (fabric, scenario),
            # all 8 policies' onset -> re-convergence clocks plus the
            # wam_wins verdict (see docs/BENCHMARKS.md meta.recovery)
            payload["meta"]["recovery"] = {"rows": common.RECOVERY_STATS}
        if common.DEGRADED_STATS:
            # stranded-by-design flows from allow_unfinished cells, named
            # by scenario/policy/flow (see docs/BENCHMARKS.md meta.degraded)
            payload["meta"]["degraded"] = {"rows": common.DEGRADED_STATS}
        if args.telemetry:
            # observability rows: recovery ticks per fault-injection event
            # (onset -> allocation re-converged), discrepancy-gauge max,
            # hot-link queue p99 — plus pointers to the exported traces
            payload["meta"]["telemetry"] = {
                "trace_dir": args.trace_dir,
                "rows": common.TELEMETRY_STATS,
            }
        if args.audit:
            # static program audit: per-family jaxpr fingerprints + any
            # dtype/effect/telemetry violations or golden-pin drift
            payload["meta"]["audit"] = {
                "ok": not audit_problems,
                "problems": audit_problems,
                "rows": audit_rows,
            }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(common.RESULTS)} rows to {args.json}", file=sys.stderr)

    # compile-count gate: the family sweeps promise one program per family,
    # so the whole run's program count is a small constant — fail loudly if
    # a change reintroduces per-scenario (or per-policy) compiles.  Gate on
    # BOTH the self-declared emit rows and the actual `aot_compile` call
    # count, so a section that loops aot_compile without emitting a
    # compile_count row cannot pass vacuously.
    actual = max(total_compiles, common.AOT_COMPILES)
    if args.max_compiles is not None and actual > args.max_compiles:
        raise SystemExit(
            f"compile-count gate: {actual} compiled programs (declared "
            f"{total_compiles}, aot_compile calls {common.AOT_COMPILES}) > "
            f"--max-compiles {args.max_compiles} (per-scenario compiles "
            f"have crept back in; see meta.compile rows)"
        )

    # jaxpr audit gate: a dtype/effect/telemetry violation or fingerprint
    # drift fails the run loudly (details already in AUDIT_report.json)
    if audit_problems:
        for p in audit_problems:
            print(f"# audit: {p}", file=sys.stderr)
        raise SystemExit(
            f"jaxpr audit gate: {len(audit_problems)} problem(s) — see "
            "AUDIT_report.json; after an INTENDED program change regen "
            "pins via `python -m repro.analysis.jaxpr_audit --write`"
        )


if __name__ == "__main__":
    main()
