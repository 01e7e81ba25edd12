"""Dual-runtime residency: the TPU analogue of keeping both modes' CUDA
graphs resident (paper §4.4).

Each layout's step functions are AOT-compiled once at startup against fixed
aval/sharding signatures (a ladder of batch-slot sizes, like the paper's
36-graph capture set). A switch *selects* the other layout's executables —
a host pointer swap — instead of recompiling. Executables are keyed on
(layout, kind, batch_slots) — `kind` covers prefill, single-step decode,
AND the fused decode loop, whose key carries the fused step count:
(layout, "decode_loop", bs, steps).
"""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class ResidentRuntime:
    # key tuple (layout, kind, *geometry) -> compiled/jitted step fn
    executables: dict = field(default_factory=dict)
    ladder: tuple = (4, 8, 16, 32, 64, 128, 256)

    def get_or_build(self, key: tuple, builder):
        """Resident lookup by full key tuple; builds on first use. The
        engine routes every step-fn cache through here so warmup, switch,
        and steady state share one registry."""
        if key not in self.executables:
            self.executables[key] = builder()
        return self.executables[key]
