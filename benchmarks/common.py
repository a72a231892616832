"""Benchmark utilities: timing + CSV emission (name,us_per_call,derived).

Rows printed through `emit` are also recorded in `RESULTS` so `run.py
--json PATH` can dump the whole run as a BENCH_*.json-compatible dict.
Extra keyword fields passed to `emit` (e.g. ``compile_s=...``,
``compile_count=...``) are attached to the JSON row — and compile-cost
fields are additionally aggregated into `COMPILE_STATS`, which `run.py`
surfaces in the JSON meta block so sweep-speed (compile-count) regressions
show up in the bench trajectory.

`aot_compile` splits compile from run wall-clock via the jit AOT path
(``fn.lower(...).compile()``); the compiled callable takes the dynamic
arguments only (statics are baked in).

`SMOKE` (set by `run.py --smoke`) asks benchmarks for a fast, small-shape
pass — CI-sized sanity numbers rather than paper-sized tables.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, Dict, List, Tuple

import jax
import numpy as np

__all__ = [
    "timeit",
    "emit",
    "perf",
    "env_info",
    "aot_compile",
    "compile_gate",
    "timed_call",
    "check_finished",
    "sentinel_free_p99",
    "telemetry_row",
    "RESULTS",
    "COMPILE_STATS",
    "PERF_STATS",
    "TELEMETRY_STATS",
    "BAKEOFF_STATS",
    "RECOVERY_STATS",
    "DEGRADED_STATS",
    "SMOKE",
    "TELEMETRY",
    "TRACE_DIR",
    "set_smoke",
    "set_telemetry",
]

# (name, us_per_call, derived, ...fields) rows accumulated this process
RESULTS: List[Dict[str, object]] = []

# per-emit compile accounting: {"name", "compile_count", "compile_s"} rows
COMPILE_STATS: List[Dict[str, object]] = []

# per-family perf accounting (meta.perf in the bench JSON): fabric
# throughput + run-vs-compile wall split rows appended by `perf`
PERF_STATS: List[Dict[str, object]] = []

# total `aot_compile` invocations this process (the compile-count gate
# reads deltas of this around a family sweep — see `compile_gate`)
AOT_COMPILES = 0

SMOKE = False

# set by `run.py --telemetry`: benches run their in-scan telemetry section
# (one extra compiled program per family) and report recovery-time rows
TELEMETRY = False

# set by `run.py --trace-dir`: directory for exported trace artifacts
# (JSONL series + Perfetto trace JSON per telemetry row)
TRACE_DIR: str | None = None

# recovery/queue observability rows (meta.telemetry in the bench JSON):
# appended by `telemetry_row`
TELEMETRY_STATS: List[Dict[str, object]] = []

# policy bake-off ranking rows (meta.bakeoff in the bench JSON): one row
# per (family, scenario, metric) appended by bench_bakeoff — schema in
# docs/BENCHMARKS.md (`meta.bakeoff`)
BAKEOFF_STATS: List[Dict[str, object]] = []

# recovery-dynamics rows (meta.recovery in the bench JSON): one row per
# (fabric family, correlated scenario) appended by bench_recovery —
# schema in docs/BENCHMARKS.md (`meta.recovery`)
RECOVERY_STATS: List[Dict[str, object]] = []

# graceful-degradation rows (meta.degraded in the bench JSON): one row per
# flow that `check_finished(..., allow_unfinished=True)` found stranded at
# the horizon sentinel, naming its scenario/policy/flow indices — schema
# in docs/BENCHMARKS.md (`meta.degraded`)
DEGRADED_STATS: List[Dict[str, object]] = []


def set_smoke(value: bool) -> None:
    global SMOKE
    SMOKE = value


def set_telemetry(value: bool, trace_dir: str | None = None) -> None:
    global TELEMETRY, TRACE_DIR
    TELEMETRY = value
    TRACE_DIR = trace_dir
    if trace_dir:
        import os

        os.makedirs(trace_dir, exist_ok=True)


def timeit(fn: Callable, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds (blocks on jax arrays)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out) if out is not None else None
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out) if out is not None else None
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us_per_call: float, derived: str = "", **fields) -> None:
    """Record one bench row.  Extra keyword fields land in the JSON row;
    `compile_count`/`compile_s` are also tallied into COMPILE_STATS."""
    row: Dict[str, object] = {
        "name": name, "us_per_call": round(us_per_call, 2), "derived": derived
    }
    row.update(fields)
    RESULTS.append(row)
    if "compile_count" in fields or "compile_s" in fields:
        COMPILE_STATS.append(
            {
                "name": name,
                "compile_count": int(fields.get("compile_count", 0)),
                "compile_s": round(float(fields.get("compile_s", 0.0)), 3),
            }
        )
    print(f"{name},{us_per_call:.2f},{derived}")


def check_finished(
    name: str,
    finished,
    axes: Tuple[str, ...] | None = None,
    labels: Dict[str, List[str]] | None = None,
    *,
    allow_unfinished: bool = False,
) -> np.ndarray:
    """Fail LOUDLY when any gated flow hit the horizon sentinel.

    An unfinished flow reports `cct == horizon`, which silently flattens
    every tail-latency statistic and caps ETTR exposure — a gate computed
    over such rows compares sentinels, not completions.  Benchmarks that
    gate on WAM-vs-ECMP must pass their `SimResult.finished` masks (any
    shape) through this before emitting the gate row.

    The error names the offending indices so a CI log alone identifies
    which scenario/policy/draw/flow stalled; pass `axes` (one name per
    array dimension, e.g. ``("scenario", "policy", "draw", "flow")``) to
    label them, else they print positionally.  `labels` maps an axis name
    to the value names along it (e.g. ``{"policy": [p.name for p in
    sweep_policies]}``) — indices on that axis then print by NAME from the
    sweep's OWN axis order, never by assuming the historical five-policy
    enum order (an 8-policy bake-off sweep and a baseline sweep put
    different policies at the same index).

    `allow_unfinished=True` is the graceful-degradation escape for benches
    whose scenarios can LEGITIMATELY strand flows (a full-SRLG blackout
    window never restores a path): instead of raising, every stranded
    index becomes one `DEGRADED_STATS` row (surfaced as ``meta.degraded``)
    naming its scenario/policy/flow, and the boolean mask is returned so
    the caller can exclude the sentinel CCTs from its percentile gates —
    pair the mask with `sentinel_free_p99`, which hard-asserts no sentinel
    leaked through.  Returns the mask in every case (all-True when nothing
    stranded).
    """
    arr = np.asarray(finished).astype(bool)
    if arr.size and not arr.all():
        frac = float(1.0 - arr.mean())
        bad = np.argwhere(~arr)
        if axes is not None and len(axes) != arr.ndim:
            raise ValueError(
                f"{name}: {len(axes)} axis names for a {arr.ndim}-d mask"
            )
        if labels is not None and axes is None:
            raise ValueError(f"{name}: labels without axes cannot attach")

        def tag(axis: str, i: int) -> str:
            names = (labels or {}).get(axis)
            return str(names[i]) if names is not None else str(i)

        def fmt(idx) -> str:
            if axes is None:
                return "[" + ",".join(str(int(i)) for i in idx) + "]"
            return "[" + " ".join(
                f"{a}={tag(a, int(i))}" for a, i in zip(axes, idx)
            ) + "]"

        if allow_unfinished:
            for idx in bad:
                index = (
                    {a: tag(a, int(i)) for a, i in zip(axes, idx)}
                    if axes is not None
                    else {str(d): int(i) for d, i in enumerate(idx)}
                )
                DEGRADED_STATS.append({"name": name, "index": index})
            return arr

        shown = ", ".join(fmt(i) for i in bad[:8])
        more = f" (+{len(bad) - 8} more)" if len(bad) > 8 else ""
        raise RuntimeError(
            f"{name}: {frac:.1%} of gated flows unfinished (cct == horizon "
            f"sentinel) — the gate would compare sentinels, not completions; "
            f"raise the horizon.  Offending indices: {shown}{more}"
        )
    return arr


def sentinel_free_p99(
    cct, finished, horizon: int, q: float = 99.0
) -> float | None:
    """Percentile over FINISHED flows only, sentinel leakage asserted out.

    The companion to `check_finished(allow_unfinished=True)`: a degraded
    cell's p99 must be computed over the flows that completed, with the
    horizon sentinels of the stranded flows asserted OUT of the sample.
    `finished` is the only disambiguator — a flow completing on the very
    last tick legitimately records ``cct == horizon``, the same value the
    sentinel uses (see `SimResult.finished`) — so the leak check is the
    inverse: every flow OUTSIDE the mask must carry the sentinel.  An
    unfinished flow with ``cct < horizon`` means the mask and the ccts
    came from different runs (or axes got transposed), and admitting it
    would silently flatten the tail — it raises here instead of polluting
    the gate.  Returns None when NO flow finished (the metric does not
    exist for that cell).
    """
    cct = np.asarray(cct, np.float64)
    fin = np.asarray(finished).astype(bool)
    if cct.shape != fin.shape:
        raise ValueError(
            f"cct shape {cct.shape} != finished shape {fin.shape}"
        )
    if (cct[~fin] < horizon).any():
        raise RuntimeError(
            f"non-sentinel CCT (< horizon {horizon}) outside the finished "
            f"mask — cct and finished disagree, the degraded-row exclusion "
            f"would drop real completions or admit sentinels"
        )
    good = cct[fin]
    if good.size == 0:
        return None
    return float(np.percentile(good, q))


def telemetry_row(
    name: str,
    runs,
    *,
    tol: float = 0.0,
    min_hold: int = 2,
    export: bool = True,
    meta: Dict[str, object] | None = None,
) -> Dict[str, object]:
    """Fold one telemetry series group into a meta.telemetry row.

    `runs` is a list of ``(series, onsets)`` pairs (from
    `repro.net.telemetry.series` / `event_onsets`) — e.g. one pair per
    schedule step or cluster round.  Recovery ticks pool over ALL pairs
    (`recovery_ticks` on each, concatenated), queue percentiles and the
    discrepancy-gauge max aggregate over all pairs; the row lands in
    `TELEMETRY_STATS` (surfaced as ``meta.telemetry.rows`` in the bench
    JSON) and an `emit` line summarizes it in the CSV stream.  With
    `TRACE_DIR` set and `export=True`, the FIRST pair's series is written
    as ``<name>.jsonl`` + ``<name>.trace.json`` artifacts (slashes in
    `name` become underscores).
    """
    import os

    from repro.net.telemetry import (
        chrome_trace,
        queue_percentiles,
        recovery_ticks,
        summarize_recovery,
        write_series_jsonl,
    )

    recs, disc_max, q_hot99 = [], 0.0, 0.0
    samples = 0
    for ser, onsets in runs:
        samples += len(ser.get("tick", ()))
        if len(onsets) and "alloc" in ser and ser["alloc"].size:
            recs.append(
                recovery_ticks(
                    ser["tick"], ser["alloc"], onsets,
                    tol=tol, min_hold=min_hold,
                ).reshape(-1)
            )
        if "disc" in ser and ser["disc"].size:
            disc_max = max(disc_max, float(np.max(ser["disc"])))
        if "link_queue" in ser and ser["link_queue"].size:
            q_hot99 = max(q_hot99, queue_percentiles(ser)["hot_p99"])
    pooled = np.concatenate(recs) if recs else np.zeros((0,))
    recovery = summarize_recovery(pooled)
    row: Dict[str, object] = {
        "name": name,
        "samples": int(samples),
        "recovery_ticks": recovery,
        "disc_max": round(disc_max, 4),
        "queue_hot_p99": round(q_hot99, 2),
    }
    if meta:
        row.update(meta)
    if TRACE_DIR and export and runs:
        ser0, onsets0 = runs[0]
        stem = os.path.join(TRACE_DIR, name.replace("/", "_"))
        write_series_jsonl(
            stem + ".jsonl", ser0,
            meta={"name": name, "onsets": np.asarray(onsets0).tolist(),
                  **(meta or {})},
        )
        with open(stem + ".trace.json", "w") as f:
            json.dump(chrome_trace(ser0, onsets=onsets0, max_links=4), f)
        row["trace"] = stem + ".jsonl"
    TELEMETRY_STATS.append(row)
    emit(
        f"{name}/telemetry",
        0.0,
        f"rec_p50={recovery['p50']:.1f};rec_max={recovery['max']:.1f}"
        f";recovered={recovery['recovered_frac']:.2f}"
        f";events={recovery['events']}"
        f";disc_max={disc_max:.2f};q_hot_p99={q_hot99:.1f}",
    )
    return row


def env_info(requested_devices: int | None = None) -> Dict[str, object]:
    """The meta.env block: where this bench ran.

    Captures the platform JAX runs on, its device count and kinds (on the
    CPU, host devices forced by `run.py --devices`), the flow mesh that
    `--devices` asked for (the shard_* engines' mesh over the first N
    devices; None when no mesh was asked for), and the XLA flags in
    effect — enough to interpret a scaling row without the shell that
    launched it.  Each `meta.perf` row carries the device count its own
    program ran on.
    """
    import os

    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": len(devs),
        "device_kinds": sorted({d.device_kind for d in devs}),
        "requested_devices": requested_devices,
        "mesh_shape": (
            {"flows": requested_devices} if requested_devices else None
        ),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "host_cpu_count": os.cpu_count(),
    }


def perf(
    name: str,
    *,
    fabric_ticks: float,
    path_decisions: float,
    compile_s: float,
    run_s: float,
    nominal_decisions: bool = False,
    devices: int = 1,
    breakdown: Dict[str, float] | None = None,
) -> None:
    """Record one meta.perf row: simulator throughput + wall split.

    `fabric_ticks` is the NOMINAL tick count of the family sweep (number of
    flow-coupled simulations x horizon) — with early-exit enabled the
    engine may retire dead ticks early, so ticks/s is a lower bound on true
    throughput and exactly comparable across bench runs of the same shapes.
    `path_decisions` is the total packets assigned to paths: the ACTUAL sum
    of `sent_total` where the sweep returns it, else the nominal payload
    (message sizes x grid — excludes coded overhead and retransmissions);
    pass `nominal_decisions=True` in the latter case so the JSON row says
    which one it is and rows are never cross-compared as the same metric.
    run.py surfaces these rows as `meta.perf` in the bench JSON so the perf
    trajectory is diffable run over run.

    Every row is tagged with the device count its program ran on
    (`devices`: 1 for an unsharded program, the mesh size for a
    flow-sharded one) so single- and multi-device rows of the same family
    are never conflated.  An
    optional `breakdown` maps tick-component names (e.g. ``scatter_ring``,
    ``path_assign``, ``rng``) to measured seconds; shares are normalized
    over the components so the row reads as "fraction of accounted
    component time", not of total wall (see `bench_scaleout`).
    """
    total = compile_s + run_s
    row: Dict[str, object] = {
        "name": name,
        "devices": int(devices),
        "fabric_ticks": int(fabric_ticks),
        "path_decisions": int(path_decisions),
        "path_decisions_nominal": bool(nominal_decisions),
        "fabric_ticks_per_s": round(fabric_ticks / max(run_s, 1e-9), 1),
        "path_decisions_per_s": round(
            path_decisions / max(run_s, 1e-9), 1
        ),
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 3),
        "run_frac": round(run_s / max(total, 1e-9), 3),
    }
    if breakdown:
        comp_total = max(sum(breakdown.values()), 1e-12)
        row["breakdown"] = {
            k: {"seconds": round(v, 6), "share": round(v / comp_total, 3)}
            for k, v in breakdown.items()
        }
    PERF_STATS.append(row)


def aot_compile(jit_fn, *args, **kwargs) -> Tuple[Callable, float]:
    """Compile a jitted function ahead of time; returns (compiled,
    compile_seconds).  Call `compiled` with the dynamic args only."""
    global AOT_COMPILES
    AOT_COMPILES += 1
    t0 = time.perf_counter()
    compiled = jit_fn.lower(*args, **kwargs).compile()
    return compiled, time.perf_counter() - t0


@contextlib.contextmanager
def compile_gate(name: str, max_compiles: int = 1):
    """Fail LOUDLY if a block compiles more than `max_compiles` programs.

    The scenario-family sweeps stake their speed on compiling ONE program
    per family (scenarios ride a vmap axis, not a Python loop).  Wrapping
    the family's `aot_compile` + run in this gate turns a regression that
    quietly reintroduces per-scenario compiles back into a hard error
    instead of a slow CI run someone has to notice.
    """
    start = AOT_COMPILES
    yield
    used = AOT_COMPILES - start
    if used > max_compiles:
        raise RuntimeError(
            f"{name}: {used} programs compiled where <= {max_compiles} "
            f"allowed — a scenario-family sweep has split back into "
            f"per-scenario compiles"
        )


def timed_call(compiled: Callable, *args) -> Tuple[object, float]:
    """One blocking call; returns (result, seconds)."""
    t0 = time.perf_counter()
    out = compiled(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0
