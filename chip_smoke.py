"""Chip smoke test: the fabric simulator's main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the flow-sharded fat-tree family

With one chip, one process runs these phases in order:

  1. device check: fail unless JAX's first device is a TPU (no fallback);
  2. the 4096-flow fat-tree family at full size
     (`benchmarks.bench_scaleout`) through `sender.sweep_flows_scenarios`:
     one compile, two runs; every flow must finish, `cct` and `sent_total`
     must hold no NaN, and the two runs' `cct` digests must be equal;
  3. the job layer: one `jobs.sweep_job` at `repro.launch.jobsim`'s
     defaults; every ring step must finish (the horizon is raised, with a
     printed line, until it does);
  4. the Pallas kernels `spray_select` and `lt_encode` compiled for the
     chip (`tpu_custom_call` in their HLO), bit for bit against
     `repro.kernels.ref`;
  5. chip against CPU: the fat-tree family at smoke size on the chip and
     on the host CPU; `finished` must agree everywhere and the `cct`
     differences must stay inside the bounds stated below.

With ``--chips 4`` it runs only the flow-sharded family over
`flow_mesh(4)` and the unsharded family on one of those chips, and
requires equal `cct` digests.

Every input comes from the scenario builders and fixed seeds.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``; a
failed phase exits non-zero before it.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# chip against CPU (phase 5), set from the first chip run (TPU v5 lite:
# every one of the 2048 flow results identical, largest difference 0.0)
# and never widened: at least this share of flows must report the
# identical cct, and no flow's cct may differ by more than this many ticks
MIN_IDENTICAL_CCT_SHARE = 1.0
MAX_CCT_DIFF_TICKS = 0.0


class SmokeFailure(RuntimeError):
    pass


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _phase(name: str) -> None:
    print(f"\n== {name} ==", flush=True)


def device_check() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX runs on {dev.platform!r} "
            f"({dev.device_kind}); this smoke test has no CPU fallback"
        )
    return dev


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


def _nan_free(r) -> bool:
    return not (np.isnan(np.asarray(r.cct)).any()
                or np.isnan(np.asarray(r.sent_total)).any())


def fat_tree_family(dev) -> None:
    from benchmarks import bench_scaleout as bs
    from benchmarks.common import aot_compile, check_finished, timed_call
    from repro.net.sender import sweep_flows_scenarios

    sh = bs._shapes(False)
    _, topos, scheds, spec, sp, keys = bs._family(sh)
    compiled, compile_s = aot_compile(
        sweep_flows_scenarios, topos, scheds, spec, sp, sh["n_packets"],
        keys, horizon=sh["horizon"],
    )
    print(f"fat-tree family: {sh['flows']} flows, grid {sh['grid']}, "
          f"horizon {sh['horizon']}; compile {compile_s:.3f} s")
    digests = []
    for run in (1, 2):
        r, run_s = timed_call(compiled, topos, scheds, sp, keys)
        sims = np.asarray(r.cct).size // sh["flows"]
        ticks = sims * sh["horizon"]
        digests.append(bs._digest(r.cct))
        print(f"  run {run}: {run_s:.6f} s, {ticks / run_s:.1f} nominal "
              f"fabric ticks/s, finished "
              f"{float(np.asarray(r.finished).mean()):.6f}, "
              f"cct digest {digests[-1]}")
        check_finished(
            "fat-tree family", r.finished,
            axes=("scenario", "policy", "draw", "flow"),
        )
        _require(_nan_free(r), "NaN in the fat-tree family's cct/sent_total")
    _require(
        digests[0] == digests[1],
        f"two runs of one program disagree: {digests[0]} != {digests[1]}",
    )
    print(f"  deterministic: both runs' cct digests are {digests[0]}")
    print(f"  peak device memory: {_peak_bytes(dev)}")


def job_layer() -> None:
    from repro.launch import jobsim

    args = jobsim.build_parser().parse_args([])
    for _ in range(4):
        _, policies, out = jobsim.job_sweep(args)
        if bool(np.all(out["finished"])):
            break
        print(f"  horizon {args.horizon} left ring steps unfinished; "
              f"raising it to {2 * args.horizon}")
        args.horizon *= 2
    _require(
        bool(np.all(out["finished"])),
        f"job layer: ring steps unfinished at horizon {args.horizon}",
    )
    print(f"job {args.arch} under {args.scenario}, DP {args.workers} x "
          f"TP {args.tp}, horizon {args.horizon}: every ring step finished")
    for i, pol in enumerate(policies):
        print(f"  {pol.name:<14} ETTR {out['ettr'][i, :, 0].mean():.6f}")


def _hlo_has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def kernels() -> None:
    from repro.core.profile import quantize_profile
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    ell, method = 10, 1
    prof = quantize_profile(rng.random(8) + 0.01, ell)
    counters = jnp.asarray(rng.integers(0, 2**31, 1 << 16, dtype=np.uint32))
    select = functools.partial(
        ops.spray_select, ell=ell, method=method, backend="pallas"
    )
    got = select(counters, prof.c, 333, 735)
    want = ref.spray_select_ref(counters, prof.c, 333, 735,
                                ell=ell, method=method)
    _require(np.array_equal(np.asarray(got), np.asarray(want)),
             "spray_select differs from the reference")
    _require(_hlo_has_kernel(select, counters, prof.c, 333, 735),
             "spray_select: no tpu_custom_call in its HLO")
    print(f"spray_select: batch {counters.size}, ell {ell}: matches the "
          f"reference bit for bit; tpu_custom_call in HLO")

    K, P, R, dmax = 1024, 1024, 1024, 16
    payload = jnp.asarray(rng.integers(0, 2**32, (K, P), dtype=np.uint32))
    neigh = jnp.asarray(rng.integers(0, K, (R, dmax), dtype=np.int32))
    valid = jnp.asarray(rng.random((R, dmax)) < 0.7)
    encode = functools.partial(ops.lt_encode, backend="pallas")
    got = encode(payload, neigh, valid)
    want = ref.lt_encode_ref(payload, neigh, valid)
    _require(np.array_equal(np.asarray(got), np.asarray(want)),
             "lt_encode differs from the reference")
    _require(_hlo_has_kernel(encode, payload, neigh, valid),
             "lt_encode: no tpu_custom_call in its HLO")
    print(f"lt_encode: payload [{K}, {P}], {R} rows, dmax {dmax}: matches "
          f"the reference bit for bit; tpu_custom_call in HLO")


def chip_against_cpu(dev) -> None:
    from benchmarks import bench_scaleout as bs
    from repro.net.sender import sweep_flows_scenarios

    sh = bs._shapes(True)
    _, topos, scheds, spec, sp, keys = bs._family(sh)
    out = {}
    for name, where in (("chip", dev), ("cpu", jax.devices("cpu")[0])):
        t, c, p, k = jax.device_put((topos, scheds, sp, keys), where)
        r = sweep_flows_scenarios(
            t, c, spec, p, sh["n_packets"], k, horizon=sh["horizon"]
        )
        out[name] = (np.asarray(r.cct), np.asarray(r.finished))
    (cct_t, fin_t), (cct_c, fin_c) = out["chip"], out["cpu"]
    same = float(np.mean(cct_t == cct_c))
    diff = float(np.max(np.abs(cct_t - cct_c)))
    print(f"fat-tree family at smoke size ({sh['flows']} flows, "
          f"{cct_t.size} flow results): finished agrees everywhere: "
          f"{bool(np.array_equal(fin_t, fin_c))}; identical cct share "
          f"{same:.6f}; largest cct difference {diff} ticks")
    _require(np.array_equal(fin_t, fin_c),
             "chip and CPU disagree on which flows finished")
    _require(same >= MIN_IDENTICAL_CCT_SHARE,
             f"identical cct share {same} < {MIN_IDENTICAL_CCT_SHARE}")
    _require(diff <= MAX_CCT_DIFF_TICKS,
             f"cct difference {diff} > {MAX_CCT_DIFF_TICKS} ticks")


def sharded_family(n_chips: int) -> None:
    from benchmarks import bench_scaleout as bs
    from benchmarks.common import aot_compile, check_finished, timed_call
    from repro.net.sender import (
        flow_mesh,
        shard_sweep_flows_scenarios,
        sweep_flows_scenarios,
    )

    sh = bs._shapes(False)
    _, topos, scheds, spec, sp, keys = bs._family(sh)
    mesh = flow_mesh(n_chips)
    sharded, compile_s = aot_compile(
        shard_sweep_flows_scenarios, topos, scheds, spec, sp,
        sh["n_packets"], keys, horizon=sh["horizon"], mesh=mesh,
    )
    rs, run_s = timed_call(sharded, topos, scheds, sp, sh["n_packets"], keys)
    print(f"sharded over {n_chips} chips: compile {compile_s:.3f} s, "
          f"run {run_s:.6f} s, cct digest {bs._digest(rs.cct)}")
    for field in ("cct", "finished", "link_served"):
        arr = getattr(rs, field)
        where = sorted(s.device.id for s in arr.addressable_shards)
        print(f"  {field}: shape {arr.shape}, sharding {arr.sharding}, "
              f"shards on devices {where}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"  device {d.id}: bytes in use {stats.get('bytes_in_use')}, "
              f"peak {stats.get('peak_bytes_in_use')}")

    unsharded, compile_u = aot_compile(
        sweep_flows_scenarios, topos, scheds, spec, sp, sh["n_packets"],
        keys, horizon=sh["horizon"],
    )
    ru, run_u = timed_call(unsharded, topos, scheds, sp, keys)
    print(f"unsharded on device {jax.devices()[0].id}: compile "
          f"{compile_u:.3f} s, run {run_u:.6f} s, cct digest "
          f"{bs._digest(ru.cct)}")
    for r in (rs, ru):
        check_finished("fat-tree family", r.finished)
        _require(_nan_free(r), "NaN in the fat-tree family's cct/sent_total")
    _require(bs._digest(rs.cct) == bs._digest(ru.cct),
             "sharded and unsharded cct digests differ")
    print("  sharded and unsharded cct digests are equal")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = device_check()
    from repro.launch.devices import setup_compile_cache

    print(f"jax {jax.__version__}, libtpu "
          f"{importlib.metadata.version('libtpu')}, device kind "
          f"{dev.device_kind}, {len(jax.devices())} device(s)")
    print(f"compile cache: {setup_compile_cache()}")
    if args.chips > len(jax.devices()):
        raise SmokeFailure(
            f"--chips {args.chips}: only {len(jax.devices())} present"
        )

    t0 = time.perf_counter()
    if args.chips == 1:
        _phase("fat-tree family, full size")
        fat_tree_family(dev)
        _phase("job layer")
        job_layer()
        _phase("Pallas kernels")
        kernels()
        _phase("chip against CPU")
        chip_against_cpu(dev)
    else:
        _phase(f"flow-sharded fat-tree family, {args.chips} chips")
        sharded_family(args.chips)
    print(f"\npeak device memory: {_peak_bytes(dev)}; "
          f"all phases {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
