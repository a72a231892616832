"""Traffic generators.  A traffic mix is a data file (`bench/traffic/*.json`)
whose `kind` names a generator: the file `bench/generators/<kind>.py`, found
by that name and holding a class `Generator`.  A generator turns the mix and
a configuration (`bench/configs/*.json`) into the inputs of every call a run
makes, drives the program's entry with them, and checks a call's outputs
against the reference in `bench/reference/`.  A new kind is a new file here.

Every input comes from `--seed` and the call index alone, so the same seed
gives the same calls.  The program receives only the generated inputs.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, directory: str = HERE):
    """The `Generator` class of `<directory>/<kind>.py`."""
    path = os.path.join(directory, kind + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no generator {kind!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        "bench_generator_" + kind.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Generator


def rng(seed: int, call: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(call)])


def spray_seed(rng: np.random.Generator, ell: int):
    """A source's spray seed (sa, sb): sa in [0, m), sb odd."""
    m = 1 << ell
    return int(rng.integers(0, m)), int(rng.integers(0, m // 2)) * 2 + 1


def sweep_params(sender, cfg: dict, mix: dict, sa: int, sb: int, key: int):
    """The sweep's traced parameters: one `SenderParams` per policy of the
    mix (one spray seed for all), and one PRNG key per draw."""
    import jax

    sp = sender.stack_params([
        sender.sender_params(
            sender.Policy[p], rate=cfg["rate"], seed=(sa, sb),
            code_overhead=cfg["code_overhead"],
            ctrl_interval=cfg["ctrl_interval"],
        )
        for p in mix["policies"]
    ])
    return sp, jax.random.split(jax.random.PRNGKey(key), mix["draws"])


def host_permutation(n_hosts: int, hosts_per_leaf: int, rng) -> np.ndarray:
    """A uniform permutation of the hosts in which no host sends to a host
    of its own edge leaf (redrawn until none does): the permutation traffic
    matrix of Raiciu et al., SIGCOMM 2011, section 4.  Returns dst[host]."""
    leaf = np.arange(n_hosts) // hosts_per_leaf
    while True:
        dst = rng.permutation(n_hosts)
        if np.all(dst // hosts_per_leaf != leaf):
            return dst


class Kind:
    """What every generator gives the harness: `Generator(cfg, mix, chips,
    seed)` builds every call's inputs in set-up."""

    points_per_call: int
    entry: str

    def call(self, i: int) -> dict:
        """Drive the entry with call i's inputs: {"cct": [points..., F],
        "finished": [points..., F]} as device arrays."""
        raise NotImplementedError

    def programs(self, i: int) -> list:
        """The optimized HLO text of each compiled program call i runs, so
        that the trace's op names can be told apart by what they compute."""
        return []

    def check(self, i: int, out: dict | None, dtype=np.float32) -> dict:
        """The reference's run of call i, in `dtype`: {"points": [(program's
        completion times, reference's), ...]}, the program's taken from
        `out` (None without it)."""
        raise NotImplementedError
