"""The harness on the CPU: discovery by name, refusal without a chip, and
the traffic builders."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import generators
from bench.harness import Cell
from bench.tests.helpers import (ROOT, SCOPE_METRICS, TINY_FATTREE,
                                 TINY_PERMUTATION, cpu_ops_as_device, run,
                                 tiny_root)

NEW_METRIC = '''
def read(trace):
    """Milliseconds of the traced window: found by its file name alone."""
    return 1000.0 * trace.window_s() if trace.spans else None
'''

def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path, extra_metrics={"test.window_ms": NEW_METRIC})
    result = run(root, "tiny.permutation", trace=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] % 2 == 0 and result["attempted"] >= 2
    assert result["failed"] == 0
    assert result["metrics"]["test.window_ms"]["value"] > 0
    assert result["metrics"]["test.window_ms"]["unit"] == "ms"
    assert "mesh.collective_share" not in result["metrics"]
    # the CPU traces no device, so no op is found under a scope
    assert not set(SCOPE_METRICS) & set(result["metrics"])
    assert list(result)[-1] == "checks"
    untraced = run(root, "tiny.permutation")
    assert set(untraced["metrics"]) == {"points_per_s", "setup_s"}
    assert untraced["metrics"]["points_per_s"]["value"] > 0


def test_traced_run_reads_the_engine_scopes(tmp_path, monkeypatch):
    """Every reader is handed the trace with the named scopes of the
    programs it ran: with the CPU's ops laid on a device, a traced run of
    the tiny cell reports the scope metrics, the four shares partition the
    op time, and the chunk loops' runs give the ticks a call ran, within
    the mix's horizon."""
    cpu_ops_as_device(monkeypatch)
    result = run(tiny_root(tmp_path), "tiny.permutation", trace=True)
    assert result["correct"], result["checks"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SCOPE_METRICS) <= set(metrics)
    shares = [metrics[k] for k in SCOPE_METRICS[:4]]
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)
    assert min(shares) >= 0 and metrics["tick.assign_share"] > 0
    assert 0 < metrics["engine.ticks_per_call"] <= TINY_PERMUTATION["horizon"]
    assert result["metrics"]["engine.ticks_per_call"]["unit"] == "ticks"


# A generator of a new kind, added as a file: every host sends to the host
# `shift` edge leaves further on.  It reuses the permutation generator for
# everything but the draw.
NEW_KIND = '''
import numpy as np

from bench.generators import load

Permutation = load("host_permutation")


class Generator(Permutation):
    def _draw(self, call):
        pairs, sa, sb, key = super()._draw(call)
        leaves = self.n_hosts // self.cfg["hosts_per_leaf"]
        pairs = np.stack([pairs[:, 0], (pairs[:, 0] + self.mix["shift"]) % leaves], 1)
        return pairs, sa, sb, key
'''


def test_new_generator_kind_is_found_by_name(tmp_path):
    mix = dict(TINY_PERMUTATION, kind="leaf_shift", shift=3)
    root = tiny_root(tmp_path, extra_files={
        "generators/leaf_shift.py": NEW_KIND,
        "traffic/leaf_shift-tiny.json": json.dumps(mix),
    })
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.shift", "config": "fattree-tiny",
                               "traffic": "leaf_shift-tiny", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copy(os.path.join(root, "bench", "cells", "tiny.permutation.json"),
                os.path.join(root, "bench", "cells", "tiny.shift.json"))
    wl = Cell(root, "tiny.shift").generator()(
        TINY_FATTREE, mix, 1, 2**31 + 4)
    pairs = wl._draw(0)[0]
    np.testing.assert_array_equal(pairs[:, 1], (pairs[:, 0] + 3) % 8)
    result = run(root, "tiny.shift")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0


def _bench(argv, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + argv, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "ft8.permutation", "--seed", "2147483711",
        "--seconds", "1", "--trace", "0"]


def test_measuring_run_without_a_chip_fails():
    p = _bench(ARGS, ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(ARGS, str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("hosts_per_leaf", [2, 4, 8])
def test_permutations_never_stay_inside_a_leaf(hosts_per_leaf):
    n = 16 * hosts_per_leaf
    for seed in range(20):
        dst = generators.host_permutation(n, hosts_per_leaf,
                                          np.random.default_rng([seed, 1]))
        assert sorted(dst) == list(range(n))
        assert np.all(dst // hosts_per_leaf != np.arange(n) // hosts_per_leaf)


def test_inputs_come_from_the_seed_alone():
    kind = generators.load("host_permutation")
    a = kind(TINY_FATTREE, TINY_PERMUTATION, 1, 2**31 + 9)
    b = kind(TINY_FATTREE, TINY_PERMUTATION, 1, 2**31 + 9)
    c = kind(TINY_FATTREE, TINY_PERMUTATION, 1, 2**31 + 10)
    for i in range(2):
        pa, pb, pc = a._draw(i), b._draw(i), c._draw(i)
        np.testing.assert_array_equal(pa[0], pb[0])
        assert pa[1:] == pb[1:]
        assert not np.array_equal(pa[0], pc[0]) or pa[1:] != pc[1:]


def test_permutation_points_per_call():
    wl = generators.load("host_permutation")(TINY_FATTREE, TINY_PERMUTATION, 1, 5)
    out = wl.call(1)
    assert wl.points_per_call == 2
    assert out["cct"].shape == (2, 1, 32) == out["finished"].shape
    assert wl.entry == "sender.sweep_flows_scenarios"
