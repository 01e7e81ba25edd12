"""The four-chip switch cell, run whole at tiny widths on four CPU devices
in a child process: a sound run switches tp->ep and back with nothing
compiled in the window and is correct; with the exchange between chips
left out, or a token altered where it is produced, it is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def _run(fault: str, tmp_path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(HERE / "switch_run.py"), fault],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_switches_both_ways(tmp_path):
    out = _run("none", tmp_path)
    assert out["correct"], out["compared"]
    assert out["switches"] == ["tp_to_ep", "ep_to_tp"]
    assert out["compiles_in_window"] == 0
    assert out["device"]["count"] == 4
    assert out["metrics"]["switch_pause_ms"]["value"] > 0
    assert (out["metrics"]["switch_total_ms"]["value"]
            >= out["metrics"]["switch_pause_ms"]["value"])


@pytest.mark.parametrize("fault", ["no_exchange", "token_altered"])
def test_fault_is_not_correct(fault, tmp_path):
    out = _run(fault, tmp_path)
    assert not out["correct"], out["compared"]
