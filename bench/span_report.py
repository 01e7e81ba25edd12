#!/usr/bin/env python3
"""Serve one cell under the profiler and reduce the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> \
        --seconds <s> [--save PATH]

Set-up and the window are run.py's: the cell's configuration and traffic,
every shape warmed, the checkout's fixed compile cache, the profiler on
for the window. Nothing is compared with the reference. Prints one JSON
line: the engine step's host time, decode and mixed step times and token
fill read from the `moebius.*` spans (benchlib/spans.py), the device's
idle time split by the innermost span the host was in, the self time of
each span per step, the spans a step opens, each switch's plan + commit
spans beside its `pause_s`, and what an idle and an active span cost in
this process. `--save` keeps the first `SAVE_MS` milliseconds of the
reduced trace, program spans included, for the tests in bench/tests.
"""
import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402

from benchlib import device, serve, spans, traffic  # noqa: E402
from benchlib import trace as tr  # noqa: E402

SAVE_MS = 400     # milliseconds of the window that `--save` keeps


def span_cost_us(n: int = 20000) -> dict:
    """Microseconds per `with TraceAnnotation(...)` with no arguments and
    with four, outside a profiler session and inside one."""
    import jax
    from jax.profiler import TraceAnnotation

    def per(args):
        t0 = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("moebius.cost", **args):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    four = {"B": 16, "Sq": 64, "dec": 12, "pre": 52}
    out = {"idle_0": per({}), "idle_4": per(four)}
    logdir = tempfile.mkdtemp(prefix="bench_cost_")
    jax.profiler.start_trace(logdir)
    out["active_0"], out["active_4"] = per({}), per(four)
    jax.profiler.stop_trace()
    shutil.rmtree(logdir, ignore_errors=True)
    return out


def traced_window(cell, seed: int, seconds: float, *, chip: bool = True,
                  backend=None):
    """(window, trace, program spans) of one traced window of `cell`."""
    import jax
    from repro.launch.serve import use_compile_cache
    if chip:
        device.require_tpu(cell.chips)
    use_compile_cache(bench_run.ROOT)
    counter = device.CompileCounter()
    eng = serve.build(cell.arch, cell.conf, seed % 2**31, backend)
    eng.warmup()
    reqs = traffic.generate(cell.mix, seconds, seed, cell.conf["vocab_size"])
    logdir = tempfile.mkdtemp(prefix="bench_spans_")
    jax.profiler.start_trace(logdir)
    win = serve.run_window(eng, reqs, seconds, cell.mix["drain"],
                           counter=counter, trace=True)
    jax.profiler.stop_trace()
    trace = tr.load(logdir)
    program = spans.inside(spans.load_program(logdir), *trace.window)
    shutil.rmtree(logdir, ignore_errors=True)
    return win, trace, program


def report(win, trace, program, data_groups: int = 1) -> dict:
    steps = spans.per_step(program)
    per = [1 + len(kids) for _, kids in steps]
    gaps = spans.idle_by_span(trace, program)
    step_idle, share = spans.step_idle_labelled(gaps)
    useful = sum(n for st in win.steps for _, _, rows in st.dispatches
                 for _, _, n, _ in rows)
    slots = data_groups * sum(B * Sq for st in win.steps
                              for B, Sq, _ in st.dispatches)
    sw = []
    for s in spans.named(program, "switch"):
        kids = spans.inside(program, s[1], s[2])
        sw.append({"direction": s[3].get("direction"),
                   "plan_commit_s": sum(
                       e[2] - e[1] for e in kids
                       if e[0] in ("moebius.switch.plan",
                                   "moebius.switch.commit")) * 1e-9,
                   "chunks": len(spans.named(kids, "switch.chunk"))})
    for rec, rep in zip(win.switches, sw):
        rep["pause_s"] = rec["pause_s"]
    n_steps = len(steps)
    return {
        "steps": n_steps, "harness_steps": len(win.steps),
        "requests": len(win.sent), "compiles_in_window": win.compiles,
        "window_s": trace.window_s, "busy_s": tr.busy_s(trace),
        "step_ms": (sum(s.t1 - s.t0 for s in win.steps) / len(win.steps)
                    * 1e3 if win.steps else None),
        "host_ms_per_step": spans.host_ms_per_step(program),
        "decode_step_ms": spans.step_ms(program, prefill=False),
        "mixed_step_ms": spans.step_ms(program, prefill=True),
        "decode_steps": sum(1 for st, _ in steps
                            if st[3].get("pre", 0) == 0),
        "token_fill_pct": spans.token_fill_pct(program),
        "token_fill_pct_dispatch_log": (useful / slots * 100.0
                                        if slots else None),
        "spans_per_step_max": max(per, default=0),
        "spans_per_step_mean": sum(per) / len(per) if per else 0,
        "idle_in_step_s": step_idle, "idle_in_step_labelled": share,
        "idle_in_step_ms_per_step": (step_idle / n_steps * 1e3
                                     if n_steps else None),
        "idle_by_span_s": gaps,
        "self_ms_per_step": spans.self_ms_per_step(program),
        "switches": sw,
    }


def save(trace, program, path: str, ms: float = SAVE_MS) -> None:
    lo = trace.window[0]
    hi = lo + int(ms * 1e6)
    cut = tr.Trace(
        ops={d: [e for e in v if lo <= e[1] and e[2] <= hi]
             for d, v in trace.ops.items()},
        spans=[s for s in trace.spans if lo <= s[1] and s[2] <= hi])
    d = cut.to_json()
    d["program"] = [list(e) for e in spans.inside(program, lo, hi)]
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(d, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save")
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload, True)
    cost = span_cost_us()
    win, trace, program = traced_window(cell, args.seed, args.seconds)
    out = {"workload": cell.name, "seed": args.seed,
           "device": device.device_info(cell.chips),
           "span_cost_us": cost}
    out.update(report(win, trace, program,
                      int(cell.conf["mesh"].split("x")[0])))
    if args.save:
        save(trace, program, args.save)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
