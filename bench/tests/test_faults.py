"""A run with the timed path broken underneath comes out not correct, on
the CPU at test size; the same run unbroken comes out correct."""
from __future__ import annotations

import pytest

from bench.tests import faults
from bench.tests.helpers import run, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


def test_sound_run_is_correct(root):
    assert run(root, "tiny.permutation", seed=2**31 + 77)["correct"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_is_not_correct(root, fault):
    with getattr(faults, fault)():
        result = run(root, "tiny.permutation", seed=2**31 + 77)
    assert not result["correct"], result["checks"]
