"""Device facts the benchmark needs: which chip it runs on, its peaks, the
peak memory a run reached, and a count of backend compiles."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised when the run must not report: no TPU, too few chips, or a
    device kind with no published peaks."""


def device_info(n: int | None = None) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs) if n is None else n}


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} has no entry in {PEAKS.name}")
    return table[kind]


def require_tpu(chips: int) -> dict:
    """The device the cell runs on: a TPU with at least `chips` chips and a
    row in peaks.json. Anything else ends the run without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips wanted, JAX sees {len(devs)}")
    peaks_for(devs[0].device_kind)
    return device_info(chips)


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of `devices`, where the backend
    reports it."""
    vals = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            vals.append(int(st["peak_bytes_in_use"]))
    return max(vals) if vals else None


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    their seconds, through jax.monitoring."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.secs += duration
