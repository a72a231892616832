"""Run the tiny flow-sharded cell on four CPU devices, sound and with the
exchange between chips left out; prints both results' `correct`.

    python3 -m bench.tests.sharded_run <checkout>

Four host devices exist only if XLA_FLAGS asks for them before JAX starts,
so this runs in a process of its own.
"""
import json
import sys

from bench.tests import faults
from bench.tests.helpers import run

if __name__ == "__main__":
    root = sys.argv[1]
    sound = run(root, "tiny.permutation.x4", seed=2**31 + 3)
    with faults.exchange_left_out(4):
        broken = run(root, "tiny.permutation.x4", seed=2**31 + 3)
    print(json.dumps({"sound": sound["correct"], "broken": broken["correct"],
                      "entry_devices": sound["device"]["count"]}))
