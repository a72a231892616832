"""Scale-out: the 3-tier fat-tree family at 4096 flows + device scaling.

The headline sweep runs `scenarios.fat_tree_scenarios` — 4 inter-pod
contention scenarios on ONE 8-pod fat-tree grid (32 leaves, 2 spine
planes x 2 cores: n = 4 distinct 4-hop paths per inter-pod flow) — at
4096 coupled flows x {ECMP, WAM}, as one compiled program under
`common.compile_gate`, exactly the `bench_topology` idiom lifted to the
3-tier fabric.

A scale-out diagnostic rides along in `meta.perf`: scaling rows — the
SAME family through the flow-sharded engine
(`sender.shard_sweep_flows_scenarios`) over `flow_mesh(d)` for each d of
the 1/2/4/8 ladder that does not exceed the devices JAX sees, all in this
process (a chip belongs to one process, so a child could not reach it).
Each row reports ticks/s plus a digest of its `cct` tensor, and the bench
FAILS if any digest differs from the unsharded sweep's: the scaling curve
and the bit-identity claim are checked by the same run.  On the CPU the
devices are forced host devices (`run.py --devices N` under
``JAX_PLATFORMS=cpu``); they share the host's cores, so read those rows as
a partition-overhead (not speedup) measurement (see docs/BENCHMARKS.md).
Where the tick's device time goes is read from a chip trace by the
benchmark in `bench/` (its `tick.*` metrics), not here.
"""
from __future__ import annotations

import argparse
import hashlib
import os

import jax
import numpy as np

from benchmarks import common
from benchmarks.common import (
    aot_compile,
    check_finished,
    compile_gate,
    emit,
    timed_call,
)
from repro.net.scenarios import fat_tree_scenarios, stack_scenarios
from repro.net.sender import (
    SenderSpec,
    flow_mesh,
    policy_sweep_params,
    shard_sweep_flows_scenarios,
    sweep_flows_scenarios,
)
from repro.net.transport import Policy

POLICIES = (Policy.ECMP, Policy.WAM)
RATE = 32


def _shapes(smoke: bool) -> dict:
    """Family + scaling shapes, shared by the unsharded and the sharded
    sweeps (the bit-identity gate compares their cct digests).

    The full pass keeps the headline 4096 coupled flows but provisions the
    fabric generously (link_capacity 32, host_rate 64, 4-packet messages)
    so the slowest scenario (the 4096-to-one-leaf incast) completes in a
    few hundred ticks — at this flow count the per-tick cost dominates
    wall-clock, and an under-provisioned incast runs for hours without
    changing what the scaling rows measure."""
    if smoke:
        return dict(
            flows=256, n_packets=4, horizon=1024, draws=1,
            link_capacity=8.0, host_rate=32.0,
            grid=dict(n_pods=4, leaves_per_pod=2, spines_per_pod=2,
                      cores_per_spine=2),
            scaling=(1, 2),
        )
    return dict(
        flows=4096, n_packets=4, horizon=2048, draws=1,
        link_capacity=32.0, host_rate=64.0,
        grid=dict(n_pods=8, leaves_per_pod=4, spines_per_pod=2,
                  cores_per_spine=2),
        scaling=(1, 2, 4, 8),
    )


def _family(sh: dict):
    scens = fat_tree_scenarios(
        flows=sh["flows"], horizon=sh["horizon"],
        link_capacity=sh["link_capacity"], host_rate=sh["host_rate"],
        **sh["grid"],
    )
    topos, scheds = stack_scenarios(list(scens.values()))
    spec = SenderSpec(rate_cap=RATE, early_exit=True)
    sp = policy_sweep_params(POLICIES, rate=RATE)
    keys = jax.random.split(jax.random.PRNGKey(7), sh["draws"])
    return scens, topos, scheds, spec, sp, keys


def _digest(cct) -> str:
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(cct, np.float32)
    ).tobytes()).hexdigest()[:16]


def _scaling_row(topos, scheds, spec, sp, keys, sh: dict, n_devices: int
                 ) -> dict:
    """One scaling point: shard the family over `flow_mesh(n_devices)`,
    compile once, time one run."""
    compiled, compile_s = aot_compile(
        shard_sweep_flows_scenarios, topos, scheds, spec, sp,
        sh["n_packets"], keys, horizon=sh["horizon"],
        mesh=flow_mesh(n_devices),
    )
    r, run_s = timed_call(compiled, topos, scheds, sp, sh["n_packets"], keys)
    sims = int(np.asarray(r.cct).size // sh["flows"])
    return {
        "compile_s": compile_s,
        "run_s": run_s,
        "fabric_ticks": sims * sh["horizon"],
        "path_decisions": int(np.asarray(r.sent_total).sum()),
        "cct_digest": _digest(r.cct),
    }


def main() -> None:
    smoke = common.SMOKE
    sh = _shapes(smoke)
    scens, topos, scheds, spec, sp, keys = _family(sh)
    F, horizon = sh["flows"], sh["horizon"]

    # --- the headline family: ONE compile, scenarios x policies x draws
    # x 4096 coupled flows on the 3-tier fabric ---
    with compile_gate("scaleout family", max_compiles=1):
        swept, compile_s = aot_compile(
            sweep_flows_scenarios, topos, scheds, spec, sp,
            sh["n_packets"], keys, horizon=horizon,
        )
        r, run_s = timed_call(swept, topos, scheds, sp, keys)
    ccts = np.asarray(r.cct)  # [scenarios, policies, draws, F]
    check_finished(
        "scaleout family", r.finished,
        axes=("scenario", "policy", "draw", "flow"),
        labels={"policy": [p.name for p in POLICIES]},
    )
    base_digest = _digest(r.cct)
    sims = ccts.size // F

    common.perf(
        "scaleout_3tier_family",
        fabric_ticks=sims * horizon,
        path_decisions=float(np.asarray(r.sent_total).sum()),
        compile_s=compile_s,
        run_s=run_s,
    )

    for si, scen_name in enumerate(scens):
        p99s = {}
        for pi, pol in enumerate(POLICIES):
            flat = ccts[si, pi].reshape(-1)
            p50, p99 = np.percentile(flat, 50), np.percentile(flat, 99)
            p99s[pol] = p99
            emit(
                f"scaleout/{scen_name}/{pol.name}",
                run_s * 1e6 / ccts.size,
                f"p50={p50:.1f};p99={p99:.1f};mean={flat.mean():.1f}"
                f";flows={F};draws={sh['draws']}",
            )
        emit(
            f"scaleout/{scen_name}/wam_vs_ecmp",
            0.0,
            f"p99_speedup={p99s[Policy.ECMP] / max(p99s[Policy.WAM], 1e-9):.2f}",
        )

    sweep_total = compile_s + run_s
    emit(
        "scaleout/family/sweep",
        sweep_total * 1e6,
        f"compiles=1_for_{len(scens)}_scenarios_x_{len(POLICIES)}"
        f"_policies_at_{F}_flows_3tier",
        compile_count=1,
        compile_s=round(compile_s, 3),
        run_s=round(run_s, 3),
        total_s=round(sweep_total, 3),
    )

    # --- scaling rows: same family, flow-sharded over every mesh size the
    # platform has devices for; digest equality against the unsharded
    # sweep is a hard gate (a scaling curve over different numbers is
    # worthless) ---
    ladder = [d for d in sh["scaling"] if d <= jax.device_count()]
    ticks_per_s = {}
    for n_dev in ladder:
        row = _scaling_row(topos, scheds, spec, sp, keys, sh, n_dev)
        if row["cct_digest"] != base_digest:
            raise RuntimeError(
                f"scaleout scaling: sharded cct digest {row['cct_digest']} "
                f"(devices={n_dev}) != unsharded {base_digest} — the "
                f"flow-sharded engine has diverged from the reference sweep"
            )
        tps = row["fabric_ticks"] / max(row["run_s"], 1e-9)
        ticks_per_s[n_dev] = tps
        common.perf(
            f"scaleout_3tier_sharded_d{n_dev}",
            fabric_ticks=row["fabric_ticks"],
            path_decisions=row["path_decisions"],
            compile_s=row["compile_s"],
            run_s=row["run_s"],
            devices=n_dev,
        )
        emit(
            f"scaleout/scaling/d{n_dev}",
            row["run_s"] * 1e6 / max(row["fabric_ticks"], 1),
            f"devices={n_dev};ticks_per_s={tps:.0f}"
            f";speedup_vs_d1={tps / max(ticks_per_s[ladder[0]], 1e-9):.2f}"
            f";bit_identical=1",
            compile_count=1,
            compile_s=row["compile_s"],
            run_s=row["run_s"],
        )
    emit(
        "scaleout/scaling/curve",
        0.0,
        ";".join(f"d{n}={ticks_per_s[n]:.0f}" for n in ladder)
        + f";host_cores={os.cpu_count()}",
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    common.set_smoke(ap.parse_args().smoke)
    main()
