"""Shared backend resolution for the four kernel packages.

Every ``ops.py`` dispatcher funnels through :func:`resolve_backend`, so the
policy lives in exactly one place:

  "ref"          -> pure-jnp oracle
  "pallas"       -> the compiled Pallas kernel; only on a TPU (anywhere else
                    it is an error, never a silent substitute)
  "interpret"    -> Pallas interpret mode (CPU parity tests)
  None (auto)    -> pallas on a TPU, ref everywhere else

Interpret mode is orders of magnitude slower than the jnp oracle and is
only ever wanted explicitly. A run that must prove it used the kernels
(``chip_smoke.py``) reads the trace-time counters below and fails on any
op that resolved to ``ref`` or ``interpret``.

Counters tick once per *trace*, not per execution — sufficient to prove
routing.
"""
from __future__ import annotations

from collections import Counter

import jax

BACKENDS = ("ref", "pallas", "interpret")

#: (op_name, resolved_backend) -> number of traces since last reset_counts().
COUNTS: Counter[tuple[str, str]] = Counter()


def reset_counts() -> None:
    COUNTS.clear()


def record(op: str, resolved: str) -> None:
    """Called by ops.py at trace time, once per dispatcher invocation."""
    COUNTS[(op, resolved)] += 1


def calls(op: str, resolved: str | None = None) -> int:
    """Total recorded traces for `op` (optionally for one backend)."""
    if resolved is not None:
        return COUNTS[(op, resolved)]
    return sum(n for (o, _), n in COUNTS.items() if o == op)


def resolve_backend(explicit: str | None = None, *,
                    platform: str | None = None) -> str:
    """Collapse (explicit request, platform) to one of BACKENDS.

    `platform` defaults to JAX's default backend; tests inject it to pin a
    branch without touching the process.
    """
    if explicit is not None and explicit not in BACKENDS:
        raise ValueError(f"unknown kernel backend {explicit!r}; expected "
                         f"one of {BACKENDS} or None (auto)")
    if explicit in ("ref", "interpret"):
        return explicit
    if platform is None:
        platform = jax.default_backend()
    if explicit == "pallas" and platform != "tpu":
        raise ValueError(f"backend 'pallas' compiles for a TPU, not "
                         f"{platform!r}; use 'interpret' off the chip")
    return "pallas" if platform == "tpu" else "ref"
