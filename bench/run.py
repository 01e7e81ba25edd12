#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of BENCHMARK.json's `workloads`) names a configuration
(its file under bench/configs), a traffic mix (bench/traffic/<mix>.json) and
the chips it needs; its correctness limit is bench/limits/<cell>.json and
each metric is read by bench/metrics/<metric>.py. The configuration file's
`"arch"` names its architecture module, bench/arch/<arch>.py: the program's
model configuration, the work counts and the plain reference of that block
(bench/arch/topk_moe.py states the contract). Adding a cell, a metric or an
architecture adds files and entries and edits none.

Set-up builds the engine through the launcher (weights made on the device
from the seed), warms the cell's own ladder and chunk shapes, and keeps
compiled programs in the checkout's fixed compile cache. The window then
sends the mix open loop (or, for a batch mix, all at once) for `--seconds`.
With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from the harness's own timestamps,
the engine's counters and a profiler trace of the window.

After the window the program's state is freed and the served tokens are
compared with the plain reference (benchlib/correct.py). The compared
numbers and their limits are the last lines on standard error and the last
key of the result, which is the last line on standard output.

Exits non-zero without a result when JAX finds no TPU, fewer chips than the
cell asks for, or a device kind that bench/peaks.json does not list.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def err(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict            # configuration file
    mix: dict             # traffic file
    limits: dict          # correctness limits file
    metrics: list         # BENCHMARK.json entries this run reports
    arch: object          # the configuration's architecture module


def load_cell(name: str, trace: bool, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if trace:
        names = {m["name"] for m in e2e}
        metrics = [m for m in spec["per_layer"]
                   if (name in m["workloads"] if "workloads" in m
                       else m["moves"] in names)]
    else:
        metrics = e2e
    bench = root / "bench"
    conf = json.loads((root / entry["file"]).read_text())
    return Cell(
        name=name, chips=cell["chips"], conf=conf,
        mix=json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                       .read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        metrics=metrics, arch=load_arch(conf["arch"]))


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_arch(name: str):
    """The architecture module bench/arch/<name>.py."""
    return importlib.import_module(f"arch.{name}")


@dataclass
class Run:
    """What a metric reader sees."""
    conf: dict
    arch: object          # the configuration's architecture module
    dims: object          # arch.dims(conf)
    peaks: dict
    chips: int
    setup_s: float
    window: object        # serve.Window
    trace: object         # trace.Trace or None


def free_device_memory() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def served_sample(window, vocab: int):
    """[(prompt, served)] of the requests the window served, and the exact
    checks: tokens outside the vocabulary, finished requests short of
    their forced length."""
    from repro.serving.request import State
    served, oov, short = [], 0, 0
    for r in window.sent:
        toks = list(r.req.prompt[:r.prompt_len]) + list(r.req.output)
        out = toks[r.prompt_len:]
        oov += sum(1 for t in out if not 0 <= t < vocab)
        if r.req.state is State.FINISHED and len(out) != r.target:
            short += 1
        if out:
            served.append((toks[:r.prompt_len], out))
    return served, oov, short


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             chip: bool = True, backend: str | None = None,
             peaks: dict | None = None, t_start: float = T_START,
             before_window=None, keep: dict | None = None,
             control: bool = False) -> dict:
    """One run of `cell`. `chip=False` skips the look for a TPU (tests on
    the CPU pass `backend` and `peaks` themselves); `before_window(eng)`
    lets a test break the timed path underneath; `keep` receives the
    run that the metric readers saw. `control` also reads the fp8 control
    on the same sample (bench/control.py), into the result's `control`,
    and judges it by the cell's limits into `control_correct`."""
    import jax

    from benchlib import correct, device, serve, traffic
    from benchlib import trace as tr
    from repro.launch.serve import use_compile_cache

    dev = (device.require_tpu(cell.chips) if chip
           else device.device_info(cell.chips))
    peaks = peaks or device.peaks_for(dev["kind"])
    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = device.CompileCounter()
    conf = cell.conf
    wseed = seed % 2**31
    t0 = time.perf_counter()
    eng = serve.build(cell.arch, conf, wseed, backend)
    t1 = time.perf_counter()
    eng.warmup()
    t2 = time.perf_counter()
    err(f"set-up: build {t1 - t0:.3f}s, warmup {t2 - t1:.3f}s, "
        f"{counter.n} compiles taking {counter.secs:.3f}s")
    if before_window is not None:
        before_window(eng)
    reqs = traffic.generate(cell.mix, seconds, seed, conf["vocab_size"])
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
    win = serve.run_window(eng, reqs, seconds, cell.mix["drain"],
                           counter=counter, trace=trace)
    tr_ = None
    if trace:
        jax.profiler.stop_trace()
        tr_ = tr.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    devices = jax.devices()[:cell.chips]
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    if tr_ is not None:
        dev["busy_s"] = tr.busy_s(tr_)
        dev["window_s"] = tr_.window_s
    backends = None
    if chip:
        from repro.kernels import dispatch
        backends = {f"{op}[{b}]": n
                    for (op, b), n in sorted(dispatch.COUNTS.items())}
    run = Run(conf=conf, arch=cell.arch, dims=cell.arch.dims(conf),
              peaks=peaks, chips=cell.chips, setup_s=win.t_open - t_start,
              window=win, trace=tr_)
    if keep is not None:
        keep["run"] = run
    metrics = {}
    for m in cell.metrics:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if tr_ is not None:
        breakdown = {"device_ops": tr.top_ops(tr_),
                     "idle_gaps": tr.idle_gaps(tr_)}

    # --- correctness, after the program's state is freed ---
    served, oov, short = served_sample(win, conf["vocab_size"])
    del eng
    free_device_memory()
    lim = cell.limits
    sample = correct.pick(served, seed, lim["pack_tokens"])
    weights = cell.arch.make_weights(conf, wseed, devices)
    res = correct.check(cell.arch, conf, weights, sample, lim["pack_tokens"])
    low = (correct.check(cell.arch, conf, weights, sample,
                         lim["pack_tokens"], control=True)
           if control else None)
    del weights
    free_device_memory()
    compared = {k: {"value": res[k], "limit": v}
                for k, v in lim["compare"].items()}
    compared.update({
        "tokens_compared": {"value": res["tokens"],
                            "at_least": lim["min_tokens"]},
        "out_of_vocab": {"value": oov, "limit": 0},
        "short_finished": {"value": short, "limit": 0},
    })
    ok = all(c["value"] <= c["limit"] if "limit" in c
             else c["value"] >= c["at_least"] for c in compared.values())
    low_ok = (None if low is None else
              all(low[k] <= v for k, v in lim["compare"].items()))

    late = sorted(win.late_s)
    err(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)}"
        f" device {dev}")
    err(f"requests sent {len(win.sent)}, steps {len(win.steps)}, window "
        f"{win.t_close - win.t_open:.3f}s, drain "
        f"{win.t_stop - win.t_close:.3f}s, compiles in window "
        f"{win.compiles}")
    if late:
        err(f"generator late: max {late[-1] * 1e3:.3f} ms, p95 "
            f"{late[int(0.95 * (len(late) - 1))] * 1e3:.3f} ms")
    err(f"counters {win.counters}")
    for s in win.switches:
        err(f"switch {s}")
    if backends is not None:
        err(f"kernel backends {backends}")
    err(f"compared {res['requests']} requests, {res['tokens']} tokens, "
        f"{res['mismatches']} not the reference's best; widest gap "
        f"{res['max_logit_gap']}, mean gap {res['mean_logit_gap']}")
    for k, c in compared.items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['at_least']}")
        err(f"{k} {c['value']} {bound}")
    out = {"correct": ok, "attempted": len(win.sent), "failed": short,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if low is not None:
        out["control"] = low
        out["control_correct"] = low_ok
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, bool(args.trace))
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
