"""The four-chip cell's run with the exchange between chips left out comes
out not correct; the same run on four CPU devices unbroken is correct."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from bench.tests.helpers import ROOT, tiny_root


def test_exchange_left_out_is_not_correct(tmp_path):
    root = tiny_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.sharded_run", root],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "broken": False, "entry_devices": 4}
