#!/usr/bin/env python3
"""The readings that set the check's limits, taken on the chip.

For each seed of `--seeds`, one call of the program at the cell's own
size is compared with the plain reference (the lower readings); for
each seed of `--control`, the control, the reference one step down
(see each engine's `reference`), is compared with it too (the upper
readings).  One set-up serves every seed.  One JSON line per seed, or,
with `--all-lanes N` for a lane mix of N lanes, per seed and lane
(the harness's check compares the one lane the seed draws).

    python3 bench/readings.py --workload sf_q19.uniform_ugal_l \
        --seeds 11,12,13 --control 11,12,13
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--all-lanes", type=int, default=0)
    args = ap.parse_args()

    cell = harness.Cell(harness.ROOT, args.workload)
    _, state = harness.prepare(cell, 0)
    engine = cell.engine
    lanes = [{"lane": i} for i in range(args.all_lanes)] or [{}]
    for s in sorted(set(args.seeds) | set(args.control)):
        got = (engine.observe(engine.call(state, s)) if s in args.seeds
               else None)
        for lane in lanes:
            t = time.perf_counter()
            want = engine.reference(state, s, **lane)
            line = {"seed": s, **lane,
                    "reference_s": time.perf_counter() - t}
            if got is not None:
                line["program"] = engine.parts(got, want)
            if s in args.control:
                ctrl = engine.reference(state, s, control=True, **lane)
                line["control"] = engine.parts(ctrl, want)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
