"""A new architecture enters the benchmark by new files alone.

In a copy of the benchmark (bench/ and BENCHMARK.json), add an
architecture module with shared experts beside the routed ones
(tests/data/shared_moe.py), a tiny configuration that names it, a traffic
mix, a limits file and a cell, and run the cell on the CPU from the copy:
it comes out correct, and with the shared experts' output zeroed in the
timed path it does not. No file that was in the copy changed, and
BENCHMARK.json only gained entries."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import tiny

BENCH = tiny.BENCH
CELL = "tiny-shared-moe.chat"


def _hashes(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root)
    before = _hashes(root / "bench")
    spec_before = json.loads((root / "BENCHMARK.json").read_text())

    b = root / "bench"
    shutil.copy(BENCH / "tests" / "data" / "shared_moe.py",
                b / "arch" / "shared_moe.py")
    conf = tiny.conf("mixtral-8x7b-l4", torch_dtype="float32",
                     arch="shared_moe", moe_intermediate_size=32,
                     shared_expert_intermediate_size=64)
    conf["name"] = "tiny-shared-moe"
    (b / "configs" / "tiny-shared-moe.json").write_text(json.dumps(conf))
    (b / "traffic" / "tiny-chat.json").write_text(
        json.dumps(tiny.mix("chat")))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"compare": {"mismatch_pct": 1.0, "mean_logit_gap": 0.001},
         "min_tokens": 20, "pack_tokens": 512}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-shared-moe", "source": "test",
        "file": "bench/configs/tiny-shared-moe.json", "reduced": [],
        "why": "routed and shared experts"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-shared-moe", "traffic": "tiny-chat",
        "chips": 1, "why": "the new architecture under chat"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before, spec_before


def _run(root: Path, fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"),
               PYTHONPATH=str(BENCH.parent / "src"))
    p = subprocess.run(
        [sys.executable, str(root / "bench" / "tests" / "cpu_run.py"), CELL,
         str(2**31 + 29), "2.0", fault],
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_new_architecture_is_correct(checkout):
    root, _, _ = checkout
    out = _run(root, "none")
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_shared_experts_left_out_is_not_correct(checkout):
    root, _, _ = checkout
    out = _run(root, "shared_zeroed")
    assert not out["correct"], out["compared"]


def test_no_file_of_the_benchmark_changed(checkout):
    root, before, spec_before = checkout
    after = _hashes(root / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "arch/shared_moe.py", "configs/tiny-shared-moe.json",
        "traffic/tiny-chat.json", f"limits/{CELL}.json"}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for k, v in spec_before.items():
        if isinstance(v, list):
            assert spec[k][:len(v)] == v
        else:
            assert spec[k] == v
