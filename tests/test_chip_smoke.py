"""CPU rehearsal of `chip_smoke.py`: its serving-and-check and kernel-parity
functions on a tiny reduced config, with the Pallas kernels in interpret
mode. The script's own `main` must still refuse any device that is not a
TPU, printing no summary line."""
import importlib.util
import pathlib

import pytest

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.smoke_config(0, cfg=get_config("mixtral-8x7b").reduced())


def test_smoke_serving_phase_interpret(smoke, tiny):
    from repro.kernels import dispatch
    dispatch.reset_counts()
    counter = smoke.CompileCounter()
    res = smoke.serving_phase(tiny, counter, backend="interpret",
                              n_requests=3, new_tokens=4,
                              prompt_lens=(5, 40), ladder=(4,),
                              prefill_chunk=16)
    assert sorted(res["outputs"]) == [0, 1, 2]
    assert res["serve_compiles"] == 0
    assert res["warm_compiles"] > 0
    counts = smoke.check_backends("interpret")
    assert any(k.startswith("paged_attention") for k in counts)
    with pytest.raises(AssertionError):
        smoke.check_backends("pallas")


def test_smoke_kernel_parity_interpret(smoke, tiny):
    errs = smoke.kernel_parity(tiny, backend="interpret", pages=32)
    assert set(errs) >= {"gather_pages_rows", "interleave_shards",
                         "grouped_matmul_w13", "paged_attention_B8_Sq1"}


@pytest.mark.parametrize("reduced", [False, True])
def test_model_config_cuts_depth_only(reduced):
    """The launcher's `layers` cut keeps every width and dtype of the
    chosen config; only `reduced` swaps in the tiny CPU config."""
    from repro.launch.serve import model_config
    full = get_config("mixtral-8x7b")
    base = full.reduced() if reduced else full
    cfg = model_config("mixtral-8x7b", reduced=reduced, layers=2)
    assert cfg.num_layers == 2
    assert cfg.replace(num_layers=base.num_layers) == base
    assert model_config("mixtral-8x7b").num_layers == full.num_layers


CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.launch.serve import use_compile_cache
print(use_compile_cache(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """Compiled programs land in JAX_COMPILATION_CACHE_DIR when it is set,
    else in the fixed `<root>/.jax_cache`, and nowhere else."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    root, fixed = tmp_path / "root", tmp_path / "root" / ".jax_cache"
    want = tmp_path / "env_cache" if from_env else fixed
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE, str(root)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == str(want)
    assert any(p.name.endswith("-cache") for p in want.iterdir())
    assert from_env != fixed.exists()


def test_smoke_main_refuses_cpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
