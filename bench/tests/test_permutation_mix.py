"""The `permutation` mix as `ft8.permutation` runs it: with 8 ECMP hash
draws a call, no call settles in the early exit's first chunk, so every call
runs the same two chunks whatever the seed draws.  (With one draw, the
calls of seed 2147514004 below settled in the first chunk and the others in
the second, so a run's points per second read the seed's pool.)"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import generators
from bench.tests.helpers import ROOT

SEED = 2147514004


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    mix = _load("traffic", "permutation.json")
    wl = generators.load(mix["kind"])(_load("configs", "fattree-k8.json"), mix,
                                      1, SEED)
    return wl, mix


@pytest.mark.parametrize("call", [0, 5])
def test_every_call_runs_two_chunks(cell, call):
    wl, mix = cell
    out = wl.call(call)
    cct, finished = np.asarray(out["cct"]), np.asarray(out["finished"])
    assert cct.shape == (len(mix["policies"]), mix["draws"], wl.n_hosts)
    assert finished.all()
    slowest = cct.max()
    assert mix["exit_chunk"] < slowest < 2 * mix["exit_chunk"], slowest
