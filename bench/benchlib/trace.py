"""Reduce a profiler trace to what the per-layer metrics read.

`load` reads the `.xplane.pb` that `jax.profiler` writes: the device ops of
each chip (the "XLA Ops" line of every `/device:TPU:n` plane) and the
harness's host spans (`bench.*` events on the host plane). Everything after
`load` works on plain lists, so the tests check it on a small recorded
trace kept in `bench/tests/data`.

The traced window runs from the first harness span's start to the last
one's end. Busy time is the union of a chip's op intervals inside it.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HLO_NAME = re.compile(r"^%?([^\s=%]+) = ")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    ops: dict            # device id -> [(name, start_ns, end_ns)], sorted
    spans: list          # [(name, start_ns, end_ns)], host, sorted

    @property
    def window(self) -> tuple[int, int]:
        return (min(s for _, s, _ in self.spans),
                max(e for _, _, e in self.spans))

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * 1e-9

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops={int(k): [tuple(e) for e in v]
                        for k, v in d["ops"].items()},
                   spans=[tuple(e) for e in d["spans"]])


def op_name(event_name: str) -> str:
    """The op's own name: a TPU trace names each op by its whole HLO line
    ("%fusion.12 = bf16[...] fusion(...)"), whose operands may name other
    ops."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {logdir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), int(e.start_ns),
                     int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif not m:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for v in ops.values():
        v.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    if not spans:
        raise RuntimeError("no harness spans in the trace")
    return Trace(ops=ops, spans=spans)


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def read(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def _clip(events, lo: int, hi: int):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def busy_intervals(trace: Trace, dev: int) -> list[tuple[int, int]]:
    """Union of the chip's op intervals inside the window, merged."""
    lo, hi = trace.window
    out: list[list[int]] = []
    for _, s, e in _clip(trace.ops.get(dev, []), lo, hi):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds with an op running, averaged over the traced chips."""
    if not trace.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(trace, d))
              for d in trace.ops)
    return tot / len(trace.ops) * 1e-9


def op_family(name: str) -> str:
    """Op name without its numeric suffixes (fusion.12 -> fusion)."""
    return re.sub(r"(\.\d+)+$", "", name)


def kernel_s(trace: Trace, name: str) -> float:
    """Device seconds of the ops of family `name` (a kernel's name),
    summed over the window and averaged over the traced chips."""
    if not trace.ops:
        return 0.0
    lo, hi = trace.window
    tot = sum(e - s for d in trace.ops
              for n, s, e in _clip(trace.ops[d], lo, hi)
              if op_family(n) == name)
    return tot / len(trace.ops) * 1e-9


def leaves(events):
    """The events that contain no later event: a loop op (the layer scan's
    `while`) spans the ops of its body on the same line."""
    out = []
    for i, (name, s, e) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][1] < e \
                and events[i + 1][2] <= e:
            continue
        out.append((name, s, e))
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[op family, seconds]] of the leaf ops that took most device time,
    averaged over the traced chips."""
    lo, hi = trace.window
    acc: dict[str, float] = {}
    for d in trace.ops:
        for name, s, e in _clip(leaves(trace.ops[d]), lo, hi):
            k = op_family(name)
            acc[k] = acc.get(k, 0.0) + (e - s)
    k = max(1, len(trace.ops))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in top]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[host span, seconds]]: device idle time summed by the harness span
    the host was in at each gap's midpoint ("outside" when none), averaged
    over the traced chips, largest first."""
    lo, hi = trace.window
    spans = trace.spans
    acc: dict[str, float] = {}
    for d in trace.ops:
        prev = lo
        for s, e in busy_intervals(trace, d) + [(hi, hi)]:
            if s > prev:
                mid = (prev + s) // 2
                label = "outside"
                for name, a, b in spans:
                    if a > mid:
                        break
                    if a <= mid < b:
                        label = name
                acc[label] = acc.get(label, 0.0) + (s - prev)
            prev = max(prev, e)
    k = max(1, len(trace.ops))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in top]
