"""The control, the reference in bfloat16 put in the program's place, fails
each cell's comparison; the reference in float32 against itself passes."""
from __future__ import annotations

import pytest

from bench.control import readings
from bench.harness import Cell
from bench.tests.helpers import tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("control"))


def _fails(values, limits):
    return any(values[k] > limits[k] for k in values if k in limits)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_control_is_not_correct(root, seed):
    c = Cell(root, "tiny.permutation")
    values = readings(c, seed, program=False)["control"]
    assert _fails(values, c.settings["limits"]), (seed, values)
