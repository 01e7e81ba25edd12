"""Run multi-device checks in subprocesses so the main pytest process keeps
a single device (the dry-run-only-512 rule)."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Default host-device count for multidevice tests. Overridable via env so
# CI / developers can scale it without touching test code.
DEFAULT_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# Preamble of the multidevice subprocesses: a (2, 4) mesh and a tiny
# float32 cut of mixtral-8x7b.
COMMON = """
import jax, jax.numpy as jnp, numpy as np
import jax.random as jr
from repro.configs import get_config
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mixtral-8x7b").reduced(
    num_heads=8, num_kv_heads=2, head_dim=8, d_model=32, num_layers=2,
    num_experts=8, top_k=2, d_expert=32, vocab_size=256, capacity_factor=8.0,
    param_dtype=jnp.float32, compute_dtype=jnp.float32)
"""


def default_devices() -> int:
    """REPRO_TEST_DEVICES read at CALL time, not import time, so a test
    (or the elastic world sweep) can adjust it per subprocess."""
    return int(os.environ.get("REPRO_TEST_DEVICES", str(DEFAULT_DEVICES)))


def device_flags(devices: int, base: str = "") -> str:
    """Merge the host-device-count flag into an existing XLA_FLAGS string,
    preserving any unrelated flags the caller's environment already set."""
    kept = [f for f in base.split() if not f.startswith(_COUNT_FLAG + "=")]
    kept.append(f"{_COUNT_FLAG}={devices}")
    return " ".join(kept)


def run_multidevice(code: str, devices: int | None = None,
                    timeout: int = 900) -> str:
    devices = default_devices() if devices is None else devices
    env = dict(os.environ)
    env["XLA_FLAGS"] = device_flags(devices, env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed:\nSTDOUT:\n{proc.stdout[-4000:]}"
            f"\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout
