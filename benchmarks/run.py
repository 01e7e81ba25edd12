"""Benchmark driver: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; every benchmark's machine-readable
``BENCH_<name>.json`` is written to the repo root (the committed perf
trajectory across PRs), and ``--json-dir DIR`` mirrors it into an artifact
dir. The dynamic benchmarks need multiple host devices: we force 8 (not
512 — that count is dry-run-only) before jax initializes.
"""
import pathlib
import sys
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from _bootstrap import ensure_env_and_path
ensure_env_and_path()

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--fast", action="store_true",
                    help="smaller workloads (CI mode)")
    ap.add_argument("--json-dir", default=None,
                    help="mirror each BENCH_<name>.json into this dir "
                         "(the repo-root copy is always written)")
    args = ap.parse_args()

    from benchmarks import (bench_bursty, bench_crossover,
                            bench_decode_hotloop, bench_graphs, bench_memory,
                            bench_roofline, bench_rollout, bench_switch_cost)
    benches = {
        "crossover": lambda: bench_crossover.run(measured=True),
        "switch_cost": bench_switch_cost.run,
        "decode_hotloop": (lambda: bench_decode_hotloop.run(smoke=True))
        if args.fast else bench_decode_hotloop.run,
        "graphs": bench_graphs.run,
        "memory": bench_memory.run,
        "rollout": (lambda: bench_rollout.run(steps=1, scale=0.008))
        if args.fast else (lambda: bench_rollout.run(steps=3, scale=0.012)),
        "bursty": (lambda: bench_bursty.run(smoke=True))
        if args.fast else (lambda: bench_bursty.run()),
        "roofline": bench_roofline.run,
    }
    names = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            rows = list(benches[name]())
            for nm, us, derived in rows:
                print(f"{nm},{us:.2f},{derived}", flush=True)
            from benchmarks.common import write_bench_json
            mirror = (str(pathlib.Path(args.json_dir) / f"BENCH_{name}.json")
                      if args.json_dir else None)
            write_bench_json({
                "benchmark": name,
                "fast": args.fast,
                "unix_time": time.time(),
                "rows": [{"name": nm, "value": us, "derived": derived}
                         for nm, us, derived in rows],
            }, mirror, name)
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            print(f"{name}.ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
