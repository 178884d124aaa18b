"""Readings the limits of ``correct`` are set from, many seeds in one process.

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 [--controls int8,fp8]
                                   [--seconds 3] [--tiny]

For each seed: the cell's own set-up and a short window at the cell's own
load, the program's numbers against the reference (the lower reading), and
for each ``--controls`` precision the reference computed in it and put in the
program's place, over the same sampled rows (the upper reading). One JSON
line per seed on standard output. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmarks import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.prepare(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            tiny=args.tiny))
        if run is None:
            return 3
        ctx, driver = run
        window = driver.run(ctx)
        line = {"workload": args.workload, "seed": seed,
                "requests": window["attempted"], "failed": window["failed"],
                "program": driver.check(ctx, window)}
        for mode in controls:
            line["control_" + mode] = driver.check(ctx, window, stand_in=mode)
        print(json.dumps(line), flush=True)
        del window, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
