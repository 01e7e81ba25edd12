"""One tiny run of the four-chip switch cell on four CPU devices, for
test_switch_cell.py (which starts it with XLA_FLAGS giving the CPU four
devices). argv[1] names a fault to plant, or "none". Prints the result."""
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent),
                str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402

import run as bench_run  # noqa: E402
import tiny  # noqa: E402


def no_exchange():
    """Every psum between chips returns this chip's own part."""
    jax.lax.psum = lambda x, axis_name, **kw: x


def token_altered(eng):
    orig = eng.ex.run_mixed

    def run_mixed(plan, step_i):
        return (orig(plan, step_i) + 1) % eng.cfg.vocab_size
    eng.ex.run_mixed = run_mixed


def main():
    fault = sys.argv[1]
    kw = {}
    if fault == "no_exchange":
        no_exchange()
    elif fault == "token_altered":
        kw["before_window"] = token_altered
    keep = {}
    out = bench_run.run_cell(tiny.switch_cell(), 5, 16.0, False, chip=False,
                             peaks=tiny.PEAKS, t_start=time.perf_counter(),
                             keep=keep, **kw)
    out["switches"] = [s["direction"] for s in keep["run"].window.switches]
    out["compiles_in_window"] = keep["run"].window.compiles
    print(json.dumps(out))


if __name__ == "__main__":
    main()
