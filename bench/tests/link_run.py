"""One tiny run of the four-chip switch cell on four CPU devices, for
test_switch_link.py (which starts it with XLA_FLAGS giving the CPU four
devices), reading `switch_link_pct` beside the cell's other metrics.
Prints the result with the program's record of each switch in the window
and the configuration it ran."""
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent),
                str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[2] / "src")]

import run as bench_run  # noqa: E402
import tiny  # noqa: E402


def main():
    cell = tiny.switch_cell()
    cell.metrics.append({"name": "switch_link_pct", "unit": "%"})
    held, keep = {}, {}
    out = bench_run.run_cell(
        cell, 2**31 + 11, 16.0, False, chip=False, peaks=tiny.PEAKS,
        t_start=time.perf_counter(), keep=keep,
        before_window=lambda eng: held.update(eng=eng))
    n = len(keep["run"].window.switches)
    recs = held["eng"].switch_records[-n:] if n else []
    out["records"] = [
        {k: getattr(r, k) for k in ("direction", "kv_pages", "delta_pages",
                                    "bytes_sent")} for r in recs]
    out["conf"] = cell.conf
    out["chips"] = cell.chips
    print(json.dumps(out))


if __name__ == "__main__":
    main()
