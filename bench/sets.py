"""Sets of runs of one cell, each in a process of its own, and their spread.

    python3 bench/sets.py --workload <cell> --seeds 1,2,3 [--sets 2]
        [--seconds 10] [--trace 0|1] [--out DIR] [--stop]

Runs `bench/run.py` once per seed, the seeds in order, `--sets` times over;
writes every run (seed, exit code, wall seconds, result line, the end of
standard error) to `DIR/<cell>.jsonl`, and prints per set and metric the
median and the spread: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.  It never
imports JAX, so each run has the chips to itself.  The benchmark's own runs
never run this; it measures what the bounds are set from.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(cell: str, seed: int, seconds: float, trace: int, keep=None) -> dict:
    cmd = [sys.executable, os.path.join("bench", "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    if keep:
        cmd += ["--keep-trace", keep]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"seed": seed, "rc": p.returncode, "wall_s": time.time() - t0,
            "result": result, "stderr_tail": p.stderr[-2000:]}


def spread(values) -> tuple[float, float]:
    """(median, interquartile distance over the median)."""
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="keep the first traced run's raw trace in DIR")
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--stop", action="store_true",
                    help="stop at the first run that fails or is not correct")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    log = os.path.join(ROOT, args.out, args.workload + ".jsonl")
    keep = args.keep_trace
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            r = one_run(args.workload, seed, args.seconds, args.trace, keep)
            keep = None
            r["set"] = k
            runs.append(r)
            with open(log, "a") as f:
                f.write(json.dumps(r) + "\n")
            res = r["result"] or {}
            print(json.dumps({
                "set": k, "seed": seed, "rc": r["rc"],
                "wall_s": round(r["wall_s"], 3),
                "correct": res.get("correct"),
                "metrics": {m: v["value"] for m, v in res.get("metrics", {}).items()},
                "checks": {c: v["value"] for c, v in res.get("checks", {}).items()},
                "peak": res.get("device", {}).get("memory_peak_bytes"),
                "run": [ln for ln in r["stderr_tail"].splitlines()
                        if ln.startswith(("cell ", "setup ", "diagnostic "))],
                "busy_s": res.get("device", {}).get("busy_s"),
                "window_s": res.get("device", {}).get("window_s"),
                "breakdown": res.get("breakdown"),
            }), flush=True)
            if r["rc"] != 0 or not res.get("correct"):
                print(r["stderr_tail"], flush=True)
                if args.stop:
                    return 1
        ok = [r["result"] for r in runs if r["result"]]
        names = sorted({m for res in ok for m in res["metrics"]})
        for m in names:
            vals = [res["metrics"][m]["value"] for res in ok if m in res["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"set {k} {m}: median {med!r} spread {sp!r} "
                      f"min {min(vals)!r} max {max(vals)!r} n {len(vals)}",
                      flush=True)


if __name__ == "__main__":
    sys.exit(main())
