"""Stepped sweep of a cell's open-loop upload rate, on the chip: the
measurement behind the cell's `upload_rps`. Each step is a whole run of
the cell (`run.run_cell`) with only the upload rate changed, so the
uploads meet the drain in the cell's own window.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

One line of JSON per step: the offered rate, the window's uploads
(`Record.upload_stats`), whether the pair sustained the rate, the
drain rate and `correct`. Last, the knee (the highest rate sustained,
with every lower step sustained too) and `upload_rps`, four fifths of
it, rounded down to a half.

A rate is sustained when no upload was refused and the second half of
the schedule's median latency is at most `GROWTH` times the first
half's: the queue in front of the leader does not grow through the
window. (The halves' 95th percentiles, over 10–50 uploads each, swing
by 3x between runs at one rate, so they are printed but not judged.)
"""

from __future__ import annotations

import argparse
import copy
import json
import math
import sys

import run
import spec

GROWTH = 1.5


def sustained(stats: dict) -> bool:
    first, second = stats["p50_first_half_ms"], stats["p50_second_half_ms"]
    return stats["refused"] == 0 and first is not None and second is not None and second <= GROWTH * first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = run.chip_devices(cell.chips)
    if devices is None:
        return 2
    run.enable_caches()
    knee = None
    for k, rate in enumerate(sorted(args.rates)):
        step = copy.deepcopy(cell)
        step.traffic["upload_rps"] = rate
        out, rec = run.run_cell(step, args.seed + k, args.seconds, False, devices)
        stats = rec.upload_stats()
        ok = sustained(stats)
        if ok and knee == (sorted(args.rates)[k - 1] if k else None):
            knee = rate
        print(json.dumps({
            "rate": rate,
            **stats,
            "sustained": ok,
            "aggregated_rps": out["metrics"].get("aggregated_rps", {}).get("value"),
            "correct": out["correct"],
        }), flush=True)
    pick = math.floor(0.8 * knee * 2) / 2 if knee else None
    print(json.dumps({"knee": knee, "upload_rps": pick}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
