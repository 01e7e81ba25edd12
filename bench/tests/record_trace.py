#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction tests read.

    python3 bench/tests/record_trace.py --workload mixtral-l4.chat \
        --seconds 3 --out bench/tests/data/trace_v5e_chat.json.gz [--ms 400]

Serves the cell for a few seconds under the profiler, prints every plane
and line of the raw trace with a few events each (what the reduction in
benchlib/trace.py relies on), and saves the reduced trace cut to its first
`--ms` milliseconds of harness spans.
"""
import argparse
import glob
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as bench_run  # noqa: E402

from benchlib import device, serve, traffic  # noqa: E402
from benchlib import trace as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--ms", type=float, default=400.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    from jax.profiler import ProfileData
    from repro.launch.serve import use_compile_cache
    cell = bench_run.load_cell(args.workload, True)
    device.require_tpu(cell.chips)
    use_compile_cache(bench_run.ROOT)
    eng = serve.build(cell.arch, cell.conf, 7)
    eng.warmup()
    reqs = traffic.generate(cell.mix, args.seconds, 7,
                            cell.conf["vocab_size"])
    logdir = tempfile.mkdtemp(prefix="bench_rec_")
    jax.profiler.start_trace(logdir)
    serve.run_window(eng, reqs, args.seconds, "none", trace=True)
    jax.profiler.stop_trace()
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), "events")
            for e in evs[:4]:
                stats = {k: v for k, v in list(e.stats)[:6]}
                print("    ", repr(e.name), e.start_ns, e.duration_ns, stats)
    t = tr.load(logdir)
    lo = t.window[0]
    hi = lo + int(args.ms * 1e6)
    cut = tr.Trace(
        ops={d: [e for e in v if lo <= e[1] and e[2] <= hi]
             for d, v in t.ops.items()},
        spans=[s for s in t.spans if lo <= s[1] and s[2] <= hi])
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    tr.save(cut, args.out)
    print("busy_s", tr.busy_s(cut), "window_s", cut.window_s)
    print("top_ops", tr.top_ops(cut))
    print("idle_gaps", tr.idle_gaps(cut))
    return 0


if __name__ == "__main__":
    sys.exit(main())
