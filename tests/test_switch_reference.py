"""A live switch does not change what the model computes: on four CPU
devices at tiny widths, requests prefill and decode in TP, the engine
switches to EP (chunked, decode running beside the chunks), decodes on,
and switches back; the logits behind every token it sampled match the
float32 reference's full forward pass over the same tokens. With one
expert slice that the switch moved zeroed, the same check fails."""
from tests.helpers import run_multidevice

CODE = """
import jax, jax.numpy as jnp, numpy as np
import jax.random as jr
from repro.configs import get_config
from repro.compat import make_mesh
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.models.registry import init_params
from repro.models.transformer import lm_forward
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request

# the tolerances of the benchmark's `correct` for float32 runs at tiny
# widths (bench/tests/test_harness.py): the program computes in float32
# like the reference, summing heads and experts over four devices in
# another order, so it agrees to rounding and any fault stands out
MISMATCH_PCT = 1.0      # share of sampled tokens not the reference's best
MEAN_GAP = 1e-3         # mean of reference best logit - logit of the token
# rounding of float32 logits of size about 1 summed in another order;
# readings on the seeds tried are under 1e-5
MAX_ABS = 1e-3

cfg = get_config("mixtral-8x7b").reduced(
    num_heads=8, num_kv_heads=4, head_dim=8, d_model=32, num_layers=2,
    num_experts=8, top_k=2, d_expert=32, vocab_size=256,
    capacity_factor=4.0, param_dtype=jnp.float32,
    compute_dtype=jnp.float32)
SEED = 3
pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
eng = MoebiusEngine(cfg, make_mesh((1, 4), ("data", "model")),
    CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16),
    ecfg=EngineConfig(start_layout=TP, ladder=(4,), prefill_chunk=8,
                      temperature=0.0, policy=pol, seed=SEED,
                      chunk_layers=1, warm_switches=True,
                      record_logits=True, prefix_cache=False))
eng.warmup()
params = init_params(cfg, jr.PRNGKey(SEED))
ref_fwd = jax.jit(lambda t: lm_forward(cfg, params, t, remat=False))
rng = np.random.default_rng(SEED)
PAD = 48                # longest prompt (16) + new tokens (16), rounded up


def serve(rids, fault=None):
    reqs = [Request(rid=i, prompt=list(rng.integers(5, 250, 11 + i)),
                    max_new_tokens=16) for i in rids]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):              # prefill and decode in TP
        eng.step()
    eng.execute_switch(EP)          # chunked: decode beside the chunks
    if fault is not None:
        fault()
    for _ in range(4):              # decode in EP
        eng.step()
    eng.execute_switch(TP)
    eng.run()
    assert [r.direction for r in eng.switch_records[-2:]] == [
        "tp_to_ep", "ep_to_tp"]
    return reqs


def check(reqs):
    gaps, diffs = [], []
    for r in reqs:
        toks = list(r.prompt) + list(r.output)
        # causal: padding after the sequence leaves its logits as they are,
        # and one padded length compiles the reference once
        toks += [0] * (PAD - len(toks))
        ref = np.asarray(ref_fwd(jnp.asarray([toks], jnp.int32))[0])
        keys = sorted(k for k in eng.ex.logits if k[0] == r.rid)
        assert len(keys) >= len(r.output), (r.rid, len(keys))
        for _, pos in keys:
            got = eng.ex.logits[(r.rid, pos)]
            want = ref[pos - 1]          # logits that sample position pos
            diffs.append(float(np.abs(got - want).max()))
            gaps.append(float(want.max() - want[int(got.argmax())]))
    g = np.asarray(gaps)
    return dict(mismatch_pct=100.0 * float((g > 0).mean()),
                mean_gap=float(g.mean()), max_abs=max(diffs), n=len(g))


sound = check(serve([0, 1, 2]))
print("sound", sound)
assert sound["n"] >= 48
assert sound["mismatch_pct"] <= MISMATCH_PCT, sound
assert sound["mean_gap"] <= MEAN_GAP, sound
assert sound["max_abs"] <= MAX_ABS, sound


def zero_moved_slice():
    # rank 1's layer-0 down projections in EP: its experts, whose other
    # three quarters the switch has just brought from the other chips
    ex = eng.ex
    w2 = ex._experts["w2"]
    ex._experts["w2"] = jax.jit(lambda a: a.at[0, 1].set(0),
                                out_shardings=w2.sharding)(w2)
    ex._pack_cache.clear()


eng.ex.logits.clear()
broken = check(serve([3, 4, 5], fault=zero_moved_slice))
print("broken", broken)
assert (broken["mismatch_pct"] > MISMATCH_PCT
        or broken["mean_gap"] > MEAN_GAP or broken["max_abs"] > MAX_ABS)
print("OK")
"""


def test_switch_matches_reference_and_zeroed_slice_does_not():
    out = run_multidevice(CODE, devices=4, timeout=300)
    assert "OK" in out
