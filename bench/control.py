"""Readings behind a cell's correctness limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program]

For each seed it builds the cell's calls as a run does and, for the first
`check_calls` of them, prints the numbers `harness.check` compares: for the
control, the reference in bfloat16 (the nearest precision below the float32
every configuration states) put in the program's place, against the
reference in float32 (the upper readings); with `--program`, for the
program's own calls at the cell's size on the devices here (the lower
readings).  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness import Cell, compare, start_jax  # noqa: E402


def readings(cell: Cell, seed: int, program: bool) -> dict:
    """The compared numbers of one seed: for the program's calls on the
    devices here (`program`), else for the control in the program's place."""
    import jax

    wl = cell.generator()(cell.config, cell.traffic, cell.chips, seed)
    points = []
    for i in range(1, 1 + int(cell.settings["check_calls"])):
        if program:
            points += wl.check(i, jax.device_get(wl.call(i)))["points"]
            continue
        ref = wl.check(i, None, np.float32)["points"]
        low = wl.check(i, None, ml_dtypes.bfloat16)["points"]
        points += [(lo[1], r[1]) for lo, r in zip(low, ref)]
    return {"program" if program else "control": compare(points)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--program", action="store_true",
                    help="read the program's calls on the devices here "
                    "instead of the control")
    args = ap.parse_args(argv)
    cell = Cell(ROOT, args.workload)
    start_jax(ROOT, cell.chips, need_accelerator=args.program)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **readings(cell, seed, args.program)}),
              flush=True)


if __name__ == "__main__":
    main()
