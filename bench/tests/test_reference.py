"""The reference draws the weights the program draws: the same recipe from
the same seed gives the same bits (the comparison that decides `correct`
rests on it)."""
import jax
import numpy as np
import pytest

import run as bench_run
import tiny


@pytest.mark.parametrize("name", ["mixtral-8x7b-l4", "qwen3-235b-a22b-l1"])
def test_weights_match_the_program(name):
    from repro.models.registry import init_params
    conf = tiny.conf(name)
    arch = bench_run.load_arch(conf["arch"])
    key = jax.random.PRNGKey(2**31 - 5)
    ours = arch.init(conf, key)
    theirs = init_params(arch.model_config(conf), key)
    pairs = [(ours["embed"], theirs["embed"]),
             (ours["lm_head"], theirs["lm_head"]),
             (ours["final_norm"], theirs["final_norm"]["scale"])]
    lay, tl = ours["layers"], theirs["layers"]
    pairs += [(lay["attn_norm"], tl["attn_norm"]["scale"]),
              (lay["mlp_norm"], tl["mlp_norm"]["scale"])]
    pairs += [(lay[k], tl["attn"][k]) for k in ("wq", "wk", "wv", "wo")]
    pairs += [(lay[k], tl["moe"][k]) for k in ("router", "w13", "w2")]
    if conf["qk_norm"]:
        pairs += [(lay[k], tl["attn"][k]) for k in ("q_norm", "k_norm")]
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
