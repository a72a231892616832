"""The benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  `BENCHMARK.json` maps the cell to its
configuration (`bench/configs/<config>.json`), its traffic mix
(`bench/traffic/<traffic>.json`, whose `kind` names a generator,
`bench/generators/<kind>.py`) and its chips; `bench/cells/<cell>.json` holds the
cell's correctness limits and how many calls the check and the trace take;
each per-layer metric is read by `bench/metrics/<metric>.py` from the trace
and the named scopes of the programs it ran (a `bench.scopes.ScopedTrace`:
`trace.shares()`, `trace.scope_seconds("tick/assign")`).  A new cell,
configuration, mix, generator or metric is new files, and no edit here.

A run has three parts:

1. Set-up: start JAX with its compile cache at a fixed path inside the
   checkout, build every call's inputs from the seed, and make one warm-up
   call at the cell's shapes.  `setup_s` runs from process start to the end
   of the warm-up.
2. Window: calls back to back, each ending with its results on the host,
   until `--seconds` have passed.  `points_per_s` is every point of every
   call the window started over the time from its start to the end of its
   last call.  With `--trace 1` the first calls of the window run under the
   profiler, and the per-layer metrics are read from that trace.
3. Check and report: after the window, a sample of its calls, drawn from the
   seed, is compared with the reference simulator, and the result line is
   printed last on standard output.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

from bench import common, generators, trace as tracing
from bench.scopes import ScopedTrace, op_scopes

CACHE_DIR = ".bench_cache/jax"   # inside the checkout; fixed, never moved


class NoAccelerator(RuntimeError):
    pass


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell's definition, resolved by name under `root`."""

    def __init__(self, root: str, name: str):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"choose from {sorted(cells)}")
        w = cells[name]
        here = os.path.join(root, "bench")
        self.name = name
        self.chips = int(w["chips"])
        self.config = load_json(os.path.join(here, "configs", w["config"] + ".json"))
        self.traffic = load_json(os.path.join(here, "traffic", w["traffic"] + ".json"))
        self.settings = load_json(os.path.join(here, "cells", name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.metrics_dir = os.path.join(here, "metrics")
        self.generators_dir = os.path.join(here, "generators")

    def generator(self):
        """The traffic mix's generator class, found by its `kind`."""
        return generators.load(self.traffic["kind"], self.generators_dir)

    def reader(self, metric: str):
        path = os.path.join(self.metrics_dir, metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def start_jax(root: str, chips: int, need_accelerator: bool,
              compile_cache: bool = True):
    """Import JAX with the compile cache at its fixed path; return the
    devices the cell uses.  A measuring run needs an accelerator with at
    least `chips` devices and never falls back to the CPU."""
    import jax

    if compile_cache:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if need_accelerator and devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator; a measuring run "
                            "does not fall back to the CPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} devices, JAX found "
                            f"{len(devices)} ({devices[0].platform})")
    return devices[:chips]


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, need_accelerator: bool = True,
             compile_cache: bool = True, keep_trace: str | None = None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; prints the check lines on `err` and returns the
    result line (also printed last on `out`).  `keep_trace` names a
    directory that keeps the raw trace, instead of a temporary one, and the
    optimized HLO of the traced programs (`program<k>.hlo.txt`); tests
    turn the compile cache and the look for an accelerator off."""
    import jax

    cell = Cell(root, name)
    devices = start_jax(root, cell.chips, need_accelerator, compile_cache)
    compiles = common.CompileCounter()
    t_jax = time.time()
    wl = cell.generator()(cell.config, cell.traffic, cell.chips, seed)
    t_inputs = time.time()
    jax.block_until_ready(wl.call(0))
    setup_s = time.time() - t_start
    setup_parts = (t_jax - t_start, t_inputs - t_jax, t_start + setup_s - t_inputs)

    # --- window ---
    results = []
    trace_calls = int(cell.settings["trace_calls"]) if trace else 0
    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="bench_trace_")
                               if trace else None)
    compiles.active = True
    t0 = time.perf_counter()
    traced_window = None
    i = 1
    while True:
        if i == 1 and trace_calls:
            jax.profiler.start_trace(trace_dir)
            tw0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dispatch"):
            res = wl.call(i)
        with jax.profiler.TraceAnnotation("read_results"):
            res = jax.device_get(res)
        with jax.profiler.TraceAnnotation("post_process"):
            results.append((i, res))
        if trace_calls and i == trace_calls:
            traced_window = time.perf_counter() - tw0
            jax.profiler.stop_trace()
        i += 1
        if time.perf_counter() - t0 >= seconds and i > trace_calls:
            break
    window_s = time.perf_counter() - t0
    compiles.active = False
    n_calls = len(results)
    attempted = n_calls * wl.points_per_call
    failed = sum(
        int(np.count_nonzero(~np.asarray(r["finished"]).reshape(
            wl.points_per_call, -1).all(axis=1)))
        for _, r in results
    )
    device = common.device_tag(devices)

    # --- per-layer metrics from the trace ---
    metrics, breakdown = {}, None
    if trace:
        programs = wl.programs(1)
        if keep_trace:
            for k, text in enumerate(programs):
                with open(os.path.join(keep_trace, f"program{k}.hlo.txt"), "w") as f:
                    f.write(text)
        tr = tracing.load(trace_dir, n_devices=cell.chips,
                          remove=keep_trace is None, programs=programs)
        tr = ScopedTrace.of(tr, op_scopes(programs) if programs else None)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        for m in cell.per_layer:
            value = cell.reader(m["name"])(tr)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = tr.breakdown()
    else:
        values = {"points_per_s": attempted / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # --- correctness: a sample of the window's calls against the reference
    t_check = time.perf_counter()
    checks, diagnostics = check(wl, results, cell.settings, seed)
    check_s = time.perf_counter() - t_check
    correct = all(v <= lim for v, lim in checks.values())
    print(f"cell {name} entry {wl.entry} calls {n_calls} "
          f"compiles_in_window {compiles.count} "
          f"traced_window_s {traced_window!r} check_s {check_s!r}", file=err)
    print("setup jax_start_s {!r} inputs_s {!r} warm_up_s {!r} programs {!r} "
          "cache_hits {!r}".format(*setup_parts, compiles.total,
                                   compiles.cache_hits), file=err)
    for k, v in diagnostics.items():
        print(f"diagnostic {k} {v!r}", file=err)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=err)
    err.flush()
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), file=out)
    out.flush()
    return result


def compare(points) -> dict:
    """The numbers compared, from (program, reference) completion times of
    each compared point: `mismatch_share`, the percent of completion times
    that differ (a NaN differs); `mean_gap`, the widest difference of a
    point's mean completion time, in percent of the reference's; and
    `cct_gap_ticks`, the widest difference of one completion time.  A point
    whose shape differs from the reference's differs everywhere."""
    points = [(g if np.shape(g) == np.shape(w) else np.full(np.shape(w), np.nan), w)
              for g, w in points]
    got = np.concatenate([g for g, _ in points])
    want = np.concatenate([w for _, w in points])
    return {
        "mismatch_share": float(100.0 * np.mean(got != want)),
        "mean_gap": float(np.max([100.0 * abs(g.mean() - w.mean()) / w.mean()
                                  for g, w in points])),
        "cct_gap_ticks": float(np.max(np.abs(got - want))),
    }


def check(wl, results, settings: dict, seed: int) -> tuple[dict, dict]:
    """The numbers compared: `unfinished`, the flows of any window call
    that missed the horizon, which the configuration guarantees never
    happens; then `compare`'s numbers over a sample of the window's calls
    drawn from the seed, checked against the reference, and the widest of
    each further gap a generator's check reports under "gaps".  Numbers
    without a limit in the cell's settings are returned apart, as
    diagnostics: ({name: (value, limit)}, {name: value})."""
    limits = settings["limits"]
    unfinished = sum(common.unfinished(r["finished"]) for _, r in results)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    take = min(int(settings["check_calls"]), len(results))
    points, gaps = [], {}
    for k in sorted(rng.choice(len(results), size=take, replace=False)):
        i, r = results[k]
        c = wl.check(i, r)
        points += c["points"]
        for name, v in c.get("gaps", {}).items():
            gaps[name] = max(gaps.get(name, v), v)
    values = dict(unfinished=unfinished, **compare(points), **gaps)
    return ({k: (v, limits[k]) for k, v in values.items() if k in limits},
            {k: v for k, v in values.items() if k not in limits})


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, keep the raw profiler trace in DIR")
    return ap.parse_args(argv)


def main(argv, *, root: str, t_start: float) -> int:
    args = parse(argv)
    if not glob.glob(os.path.join(root, "src", "repro", "net", "*.py")):
        print("the system under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        run_cell(root, args.workload, args.seed, args.seconds,
                 bool(args.trace), t_start=t_start, keep_trace=args.keep_trace)
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    return 0
