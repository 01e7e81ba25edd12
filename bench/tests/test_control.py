"""The control at a size a test run can hold: the reference computed in
fp8 and put in the program's place must not pass the comparison that a
sound run passes."""
import time

import pytest

import run as bench_run
import test_harness as th


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.parametrize("name", sorted(th.CELLS))
def test_control_fails(name):
    out = bench_run.run_cell(th._cell(name), 2**31 + 23, 2.0, False,
                             chip=False, peaks=th.tiny.PEAKS,
                             t_start=time.perf_counter(), control=True)
    assert out["correct"]
    assert out["control_correct"] is False, out["control"]
