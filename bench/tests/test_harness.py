"""A whole run of each one-chip cell on the CPU at tiny widths: the look
for a chip is skipped, everything else runs as on the chip. Then the same
run with the timed path broken underneath, once per fault the cells can
have, must come out not correct."""
import time

import jax.numpy as jnp
import pytest

import run as bench_run
import tiny

CELLS = {"mixtral-l4.chat": ("mixtral-8x7b-l4", "chat"),
         "qwen3-l1.rollout": ("qwen3-235b-a22b-l1", "rollout")}
# float32 at tiny widths: the program agrees with the reference to
# rounding (gaps under 1e-4 on the seeds tried), so any fault stands out
LIMITS = {"compare": {"mismatch_pct": 1.0, "mean_logit_gap": 0.001},
          "min_tokens": 20, "pack_tokens": 512}


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch, tmp_path):
    # the launcher then leaves JAX's persistent cache off in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _cell(name, trace=False):
    c = bench_run.load_cell(name, trace)
    conf, mix = CELLS[name]
    c.conf = tiny.conf(conf, torch_dtype="float32")
    c.mix = tiny.mix(mix)
    c.limits = dict(LIMITS)
    return c


def _run(name, trace=False, **kw):
    keep = {}
    out = bench_run.run_cell(_cell(name, trace), 2**31 + 17, 2.0, trace,
                             chip=False, peaks=tiny.PEAKS,
                             t_start=time.perf_counter(), keep=keep, **kw)
    return out, keep["run"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    out, run = _run(name)
    assert out["correct"], out["compared"]
    assert out["attempted"] == len(run.window.sent) > 0
    assert list(out)[-1] == "compared"
    want = {"mixtral-l4.chat": {"ttft_p95_ms", "tpot_p95_ms", "setup_s"},
            "qwen3-l1.rollout": {"tpot_p95_ms", "output_tok_s", "setup_s"}}
    assert set(out["metrics"]) == want[name]
    assert run.window.compiles == 0


def test_traced_run_reads_per_layer_metrics():
    out, run = _run("qwen3-l1.rollout", trace=True)
    assert out["correct"]
    # the CPU trace has no TPU planes: device metrics stay silent
    assert set(out["metrics"]) == {"decode_rows_per_step.rollout",
                                   "step_ms.rollout"}
    assert out["device"]["window_s"] > 0


def _token_altered(eng):
    orig = eng.ex.run_mixed

    def run_mixed(plan, step_i):
        return (orig(plan, step_i) + 1) % eng.cfg.vocab_size
    eng.ex.run_mixed = run_mixed


def _kv_not_written(monkeypatch):
    from repro.serving import steps
    monkeypatch.setattr(steps, "_write_pages",
                        lambda pool_l, k, v, page_ids, slots: pool_l)


def _half_batch(monkeypatch):
    """The expert layer leaves out every other token row of the step
    (their FFN output stays 0)."""
    from repro.serving import steps
    orig = steps.moe_decode_tp

    def moe_tp(cfg, p, x, axis, **kw):
        y = orig(cfg, p, x, axis, **kw)
        return y.at[1::2].set(jnp.zeros_like(y[1::2]))
    monkeypatch.setattr(steps, "moe_decode_tp", moe_tp)


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_fault_is_not_correct(name, fault, monkeypatch):
    kw = {}
    if fault == "token_altered":
        kw["before_window"] = _token_altered
    elif fault == "state_unchanged":
        _kv_not_written(monkeypatch)
    else:
        _half_batch(monkeypatch)
    out, _ = _run(name, **kw)
    assert not out["correct"], out["compared"]
    assert out["compared"]["mismatch_pct"]["value"] > \
        LIMITS["compare"]["mismatch_pct"]
