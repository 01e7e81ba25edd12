"""The program's own spans and request stamps, reduced to what the engine
step's metrics read.

The serving program opens a `moebius.*` host span at each layer boundary
of `MoebiusEngine.step()` (src/repro/tracing.py), with its numbers as
arguments: `moebius.step {step, B, Sq, dec, pre}`, `moebius.exec.stage
{B, Sq, dec, pre, slots}`, `moebius.exec.fetch`, ... They share the
profiler's clock with the device ops and the harness's `bench.*` spans.
`load_program` reads them from the `.xplane.pb`; everything after it works
on a plain list of (name, start_ns, end_ns, args), so the tests check it
on made-up lists. A program without these spans gives an empty list, and
every reader here then returns None.
"""
from __future__ import annotations

import glob
import re

import numpy as np

from benchlib import trace as tr

# the program's `repro.tracing.PREFIX`, spelled out so that the harness
# also runs on a program that has no spans
PREFIX = "moebius."
DEVICE_PLANE = re.compile(r"^/device:")


def load_program(logdir: str) -> list:
    """[(name, start_ns, end_ns, args)] of the program's host spans in the
    one `.xplane.pb` under `logdir`, parents before their children."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def inside(program: list, lo: int, hi: int) -> list:
    return [e for e in program if lo <= e[1] and e[2] <= hi]


def named(program: list, name: str) -> list:
    return [e for e in program if e[0] == PREFIX + name]


def per_step(program: list) -> list:
    """[(step span, [spans nested in it])] in time order."""
    out, i = [], 0
    for st in named(program, "step"):
        while i < len(program) and program[i][1] < st[1]:
            i += 1
        kids, j = [], i
        while j < len(program) and program[j][1] < st[2]:
            if program[j] is not st and program[j][2] <= st[2]:
                kids.append(program[j])
            j += 1
        out.append((st, kids))
    return out


def host_ms_per_step(program: list) -> float | None:
    """Mean over steps of the step's duration less its `exec.fetch`
    children: host time in which the chip waits for the program."""
    steps = per_step(program)
    if not steps:
        return None
    tot = sum((st[2] - st[1]) - sum(k[2] - k[1] for k in kids
                                    if k[0] == PREFIX + "exec.fetch")
              for st, kids in steps)
    return tot / len(steps) * 1e-6


def step_ms(program: list, prefill: bool) -> float | None:
    """Mean duration of the steps that carried prefill tokens (`pre` above
    0), or of those that did not."""
    d = [e[2] - e[1] for e in named(program, "step")
         if (e[3].get("pre", 0) > 0) == prefill]
    return sum(d) / len(d) * 1e-6 if d else None


def token_fill_pct(program: list) -> float | None:
    """Useful token rows (decode rows + prefill tokens) over the rows the
    dispatches computed (data groups x rung x chunk width), in %."""
    st = named(program, "exec.stage")
    slots = sum(e[3].get("slots", 0) for e in st)
    if not slots:
        return None
    return sum(e[3].get("dec", 0) + e[3].get("pre", 0)
               for e in st) / slots * 100.0


def self_ms_per_step(program: list) -> dict:
    """{span name: mean ms per step of its self time}: its duration less
    what its direct children cover."""
    steps = per_step(program)
    if not steps:
        return {}
    acc: dict = {}
    for a, b, name in _timeline(program, 0, 2**63 - 1):
        if name != "outside":
            acc[name] = acc.get(name, 0) + b - a
    return {k: v / len(steps) * 1e-6
            for k, v in sorted(acc.items(), key=lambda kv: -kv[1])}


def _timeline(spans: list, lo: int, hi: int) -> list:
    """[(start, end, innermost span's name)] covering [lo, hi) with no
    overlap; "outside" where no span is open."""
    evs = sorted((e[:3] for e in spans), key=lambda e: (e[1], -e[2]))
    out, stack, t = [], [], lo

    def emit(end, name):
        nonlocal t
        a, b = max(t, lo), min(end, hi)
        if b > a:
            out.append((a, b, name))
        t = max(t, end)

    def unwind(until):
        while stack and stack[-1][2] <= until:
            top = stack.pop()
            emit(top[2], top[0])

    for name, s, e in evs:
        unwind(s)
        emit(s, stack[-1][0] if stack else "outside")
        stack.append((name, s, e))
    unwind(2**63 - 1)
    emit(hi, "outside")
    return out


def idle_by_span(trace, program: list) -> dict:
    """{span: seconds}: each device idle interval of the traced window
    split by the innermost span, of the harness's or the program's, that
    the host was in over each part of it; averaged over the traced chips.
    With no program spans this is the harness's own split."""
    lo, hi = trace.window
    line = _timeline(list(trace.spans) + [e[:3] for e in program], lo, hi)
    acc: dict = {}
    for d in trace.ops:
        idle, prev = [], lo
        for s, e in tr.busy_intervals(trace, d) + [(hi, hi)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        j = 0
        for a, b in idle:
            while j < len(line) and line[j][1] <= a:
                j += 1
            k = j
            while k < len(line) and line[k][0] < b:
                x, y, name = line[k]
                acc[name] = acc.get(name, 0) + min(b, y) - max(a, x)
                k += 1
    n = max(1, len(trace.ops))
    return {k: v / n * 1e-9
            for k, v in sorted(acc.items(), key=lambda kv: -kv[1])}


def step_idle_labelled(gaps: dict) -> tuple[float, float]:
    """(idle seconds inside `bench.step`, the share of it that a program
    span names)."""
    prog = sum(v for k, v in gaps.items() if k.startswith(PREFIX))
    total = prog + gaps.get("bench.step", 0.0)
    return total, (prog / total if total else 0.0)


def prefill_ms_p95(run) -> float | None:
    """95th percentile over the window's requests of first token -
    first left `waiting`, both on the engine's clock (ms)."""
    v = []
    for r in run.window.sent:
        a = getattr(r.req, "prefill_start_s", None)
        b = r.req.first_token_s
        if a is not None and b is not None:
            v.append((b - a) * 1e3)
    return float(np.percentile(v, 95)) if v else None
