#!/usr/bin/env python3
"""Find an open-loop cell's knee: one engine, one window per offered rate.

    python3 bench/sweep.py --workload mixtral-l4.chat --seconds 51 \
        --rates 0.2,0.25,0.3 [--seed 1]

For each rate the cell's traffic mix is sent with every phase at that rate;
after the window no new request is sent, the engine runs until every sent
request has its first token (as a cell's run does), and then until it is
empty again. Prints one line per rate: requests sent, TTFT and queue-wait
percentiles, TPOT p95, output tokens/s in the window, and the median TTFT
of the requests due in the window's first and last thirds. Rates run in
rising order and the sweep stops after the first rate the engine does not
sustain: queue-wait p95 over MAX_WAIT_MS, or a queue that grows through
the window, read as the last third's median TTFT over GROWTH times the
first third's. (A request leaves `waiting` as soon as a row is free, so
a queue can build inside the prefill rows while the queue wait stays
short; the TTFT shows it.) The last line is {"knee": r} for the highest
sustained rate. Used once when a cell is defined; the cell's rate is then
fixed in its traffic file.
"""
import argparse
import copy
import json
import sys
import time

import numpy as np

import run as bench_run

from benchlib import device, serve, traffic
from benchlib import readers as rd

MAX_WAIT_MS = 3000.0
GROWTH = 2.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from repro.launch.serve import use_compile_cache
    cell = bench_run.load_cell(args.workload, False)
    device.require_tpu(cell.chips)
    use_compile_cache(bench_run.ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    eng = serve.build(cell.arch, cell.conf, args.seed % 2**31)
    eng.warmup()
    knee = None
    for i, rate in enumerate(sorted(float(r) for r in args.rates.split(","))):
        mix = copy.deepcopy(cell.mix)
        for ph in mix["arrivals"]["phases"]:
            ph["rate"] = rate
        reqs = traffic.generate(mix, args.seconds, args.seed + i,
                                cell.conf["vocab_size"])
        win = serve.run_window(eng, reqs, args.seconds, "first_token")
        run = bench_run.Run(conf=cell.conf, arch=cell.arch, dims=None,
                            peaks={}, chips=cell.chips, setup_s=0.0,
                            window=win, trace=None)
        tt = [(r.times[0] - r.due) * 1e3 for r in win.sent if r.times]
        third = args.seconds / 3

        def median_ttft(lo, hi):
            v = [(r.times[0] - r.due) * 1e3 for r in win.sent
                 if r.times and lo <= r.due - win.t_open < hi]
            return float(np.median(v)) if v else None
        first = median_ttft(0.0, third)
        last = median_ttft(2 * third, args.seconds)
        line = {"rate": rate, "sent": len(win.sent),
                "ttft_p50_ms": float(np.median(tt)) if tt else None,
                "ttft_p95_ms": rd.p95(tt),
                "ttft_p50_first_third_ms": first,
                "ttft_p50_last_third_ms": last,
                "queue_wait_p95_ms": rd.queue_wait_p95_ms(run),
                "tpot_p95_ms": rd.tpot_p95_ms(run),
                "output_tok_s": rd.output_tok_s(run),
                "steps": len(win.steps), "step_ms": rd.step_ms(run),
                "drain_s": win.t_stop - win.t_close,
                "counters": win.counters}
        print(json.dumps(line), flush=True)
        wait = line["queue_wait_p95_ms"]
        grows = (first is not None and last is not None
                 and last > GROWTH * first)
        if (wait is not None and wait > MAX_WAIT_MS) or grows:
            break
        knee = rate
        t0 = time.perf_counter()
        while eng.sched.has_work():
            eng.step()
        print(f"# rate {rate}: drained in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
