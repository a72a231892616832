"""Test fixtures: a copy of the benchmark with tiny cells added as new files.

The tiny cells keep every shape of the real ones (a k-ary fat-tree, a host
permutation, ECMP and WAM) at a size the CPU runs in seconds.  They are added the way a later change adds a
cell: new files under `bench/` and new entries in `BENCHMARK.json`.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the per-layer metrics that read the engine's named scopes: the four
# shares that partition the op time, then the ticks a call ran
SCOPE_METRICS = ("tick.assign_share", "tick.fabric_share", "tick.sender_share",
                 "engine.outside_tick_share", "engine.ticks_per_call")

TINY_FATTREE = {
    "source": "k=4-style fat-tree at test size", "fabric": "fat_tree",
    "n_pods": 4, "leaves_per_pod": 2, "spines_per_pod": 2, "cores_per_spine": 2,
    "hosts_per_leaf": 4, "link_capacity": 32.0, "rate": 32, "queue_limit": 48.0,
    "ecn_threshold": 12.0, "latency": 6, "intra_pod_latency": 4, "fb_delay": 8,
    "ring_len": 128, "ell": 10, "ctrl_interval": 4, "code_overhead": 0.05,
    "precision": "float32", "reduced": [], "assumed": {},
}
TINY_PERMUTATION = {
    "kind": "host_permutation", "message_packets": 256,
    "policies": ["ECMP", "WAM"], "draws": 1, "horizon": 256,
    "early_exit": True, "exit_chunk": 32, "pool": 2,
}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp_path, extra_metrics=None, extra_files=None) -> str:
    """A checkout holding the benchmark, the program (linked) and two tiny
    cells: `tiny.permutation` and `tiny.permutation.x4` (4 devices).
    `extra_metrics` maps new per-layer metric names to the source of their
    reader; `extra_files` maps paths under `bench/` to their text."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "bench")
    _dump(os.path.join(b, "configs", "fattree-tiny.json"), TINY_FATTREE)
    _dump(os.path.join(b, "traffic", "permutation-tiny.json"), TINY_PERMUTATION)
    for cell, like in (("tiny.permutation", "ft8.permutation"),
                       ("tiny.permutation.x4", "ft16.permutation.x4")):
        shutil.copy(os.path.join(b, "cells", like + ".json"),
                    os.path.join(b, "cells", cell + ".json"))
    bench["workloads"] += [
        {"name": "tiny.permutation", "config": "fattree-tiny",
         "traffic": "permutation-tiny", "chips": 1, "why": "test"},
        {"name": "tiny.permutation.x4", "config": "fattree-tiny",
         "traffic": "permutation-tiny", "chips": 4, "why": "test"},
    ]
    for name, source in (extra_metrics or {}).items():
        with open(os.path.join(b, "metrics", name + ".py"), "w") as f:
            f.write(source)
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "test",
             "moves": "points_per_s", "workloads": ["tiny.permutation"]})
    for rel, text in (extra_files or {}).items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def run(root, cell, *, seed=7, seconds=0.1, trace=False):
    """One in-process run of a cell on the CPU; returns the result line."""
    import io

    from bench.harness import run_cell

    out, err = io.StringIO(), io.StringIO()
    result = run_cell(root, cell, seed, seconds, trace, t_start=time.time(),
                      need_accelerator=False, compile_cache=False,
                      out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == json.loads(
        json.dumps(result))
    return result


def reader(name):
    """The `read` function of the per-layer metric `name`'s reader."""
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cpu_ops_as_device(monkeypatch):
    """Make the harness's traces on the CPU hold a device.  The CPU runs a
    program's ops on host threads (`tf_XLA...` lines of `/host:CPU`), which
    `Trace.from_profile` counts as no device; this lays every event there
    that names an instruction of the traced programs on one stand-in device,
    on the trace's own clock, so that the per-layer readers have ops and
    loop runs to read."""
    import jax

    from bench import trace as tracing

    load = tracing.load

    def with_cpu_ops(trace_dir, n_devices=None, remove=True, programs=()):
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        profile = jax.profiler.ProfileData.from_file(path)
        tr = load(trace_dir, n_devices=n_devices, remove=remove,
                  programs=programs)
        names = tracing.op_kinds(programs)
        tr.ops = {"/device:CPU:0": [
            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines if line.name.startswith("tf_XLA")
            for e in line.events if e.name in names]}
        return tr

    monkeypatch.setattr(tracing, "load", with_cpu_ops)
