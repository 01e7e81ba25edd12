#!/usr/bin/env python3
"""Readings that set a cell's correctness limit (bench/limits/<cell>.json).

    python3 bench/control.py --workload mixtral-l4.chat --seconds 20 \
        --seeds 1,2,3,...

For each seed, in one process: one run of the cell as bench/run.py makes
it (a shorter window at the cell's own load), then, on the same sample of
served requests, the control: the reference computed in fp8 put in the
program's place. Prints one JSON line per seed with the program's compared
numbers ("program"), the control's ("control") and the control judged by
the cell's limits ("control_correct"). A limit goes above the largest
program reading and below the smallest control reading. Exits non-zero
when the control comes out correct on any seed: the limits then do not
separate the program from the control.
"""
import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload, False)
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench_run.run_cell(cell, seed, args.seconds, False,
                                 control=True)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program": {k: c["value"] for k, c in out["compared"].items()},
            "control": out["control"],
            "control_correct": out["control_correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
        if out["control_correct"]:
            passed.append(seed)
    if passed:
        print(f"the control came out correct on seeds {passed}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
