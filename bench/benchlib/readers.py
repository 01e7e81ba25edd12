"""Quantities the metric files report. Each takes the run (see run.py's
`Run`) and returns a number, or None when the run has nothing to read."""
from __future__ import annotations

import numpy as np

from benchlib import trace as tr


def p95(values) -> float | None:
    return float(np.percentile(values, 95)) if len(values) else None


def ttft_p95_ms(run):
    return p95([(r.times[0] - r.due) * 1e3 for r in run.window.sent
                if r.times])


def tpot_p95_ms(run):
    return p95([(r.times[-1] - r.times[0]) / (len(r.times) - 1) * 1e3
                for r in run.window.sent if len(r.times) >= 2])


def output_tok_s(run):
    w = run.window
    n = sum(1 for r in w.sent for t in r.times if t <= w.t_close)
    return n / (w.t_close - w.t_open)


def queue_wait_p95_ms(run):
    return p95([(r.left_wait - r.due) * 1e3 for r in run.window.sent
                if r.left_wait is not None])


def decode_rows_per_step(run):
    c = run.window.counters
    return c["decode_tokens"] / c["dispatches"] if c["dispatches"] else None


def step_ms(run):
    s = run.window.steps
    return sum(x.t1 - x.t0 for x in s) / len(s) * 1e3 if s else None


def dispatches(run):
    for st in run.window.steps:
        yield from st.dispatches


def mfu_pct(run):
    if run.trace is None or not run.trace.ops:
        return None
    work = sum(run.arch.forward(run.dims, rows)
               for _, _, rows in dispatches(run))
    peak = run.chips * run.peaks["bf16_flops"]
    return work / (run.trace.window_s * peak) * 100.0


def roofline_pct(run, kernel: str):
    """Least time the chips could take for the kernel's useful work,
    step by step (the larger of operations over peak and bytes over
    bandwidth), over the kernel's device time. The architecture's `costs`
    count the work of the kernel by its trace name; an architecture that
    has no such kernel, or a trace without it, gives None."""
    cost = run.arch.costs.get(kernel)
    if run.trace is None or cost is None:
        return None
    t = tr.kernel_s(run.trace, kernel)
    if t <= 0:
        return None
    pk = run.peaks
    bound = 0.0
    for _, _, rows in dispatches(run):
        f, b = cost(run.dims, rows)
        bound += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return bound / run.chips / t * 100.0


def idle_pct(run):
    if run.trace is None or not run.trace.ops:
        return None
    return (1.0 - tr.busy_s(run.trace) / run.trace.window_s) * 100.0

