"""The switch's byte count, on four CPU devices at tiny widths: the bytes
the program's movers send in each live switch of the window, tp->ep and
ep->tp (`SwitchRecord.bytes_sent`, counted from the plan), are what the
benchmark's own count in bench/metrics/switch_link_pct.py gives for the
same switch, and the share of the link roofline a sound run reads lies in
(0, 100]."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def link_metric():
    spec = importlib.util.spec_from_file_location(
        "switch_link_pct", HERE.parent / "metrics" / "switch_link_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bytes_sent_is_the_benchmarks_count(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(HERE / "link_run.py")],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["compared"]
    recs = out["records"]
    assert [r["direction"] for r in recs] == ["tp_to_ep", "ep_to_tp"], recs
    count = link_metric().link_bytes
    for r in recs:
        # the commit re-copies the pages decode dirtied; the program counts
        # them, and so does the benchmark's count given the same pages
        assert r["bytes_sent"] == count(
            out["conf"], out["chips"], r["direction"],
            r["kv_pages"] + r["delta_pages"]), r
        assert r["bytes_sent"] > count(out["conf"], out["chips"],
                                       r["direction"], 0)
    assert 0 < out["metrics"]["switch_link_pct"]["value"] <= 100
