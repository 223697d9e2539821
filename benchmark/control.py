"""Runs a cell with the control in the program's place: the helper
accepts every report that decodes, skipping the joint verification
that the deployment's guarantees require (`faults.skip_verification`).
Every run must come out `correct: false`; the readings it compares are
the upper ends of the limits in `check.py`.

    python3 benchmark/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Runs the seeds one after another in one process, on the chip, and
prints one result line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys

import faults
import run
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = run.chip_devices(cell.chips)
    if devices is None:
        return 2
    run.enable_caches()
    with faults.skip_verification():
        for seed in args.seeds:
            out, _ = run.run_cell(cell, seed, args.seconds, False, devices)
            print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
