"""One run of a cell of the checkout this file lies in, on the CPU, for
test_arch_contract.py: the look for a chip is skipped, everything else
runs as bench/run.py runs it. argv: the cell, the seed, the window's
seconds, and a fault to plant in the timed path or "none". Prints the
result as the last line."""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import jax  # noqa: E402

import run as bench_run  # noqa: E402
import tiny  # noqa: E402


def shared_zeroed(eng):
    """The shared experts' output is 0 in every layout's weights."""
    for pk in eng.ex.packs.values():
        moe = pk["layers"]["moe"]
        moe["shared_w2"] = jax.tree.map(lambda a: a * 0, moe["shared_w2"])
    eng.ex._pack_cache.clear()


FAULTS = {"none": None, "shared_zeroed": shared_zeroed}


def main():
    name, seed, seconds, fault = sys.argv[1:]
    out = bench_run.run_cell(bench_run.load_cell(name, False), int(seed),
                             float(seconds), False, chip=False,
                             peaks=tiny.PEAKS, t_start=time.perf_counter(),
                             before_window=FAULTS[fault])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
