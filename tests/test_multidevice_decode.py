"""The serve step and the decode paths on a real SPMD mesh (subprocesses
with 8 host devices): each layout against the single-device reference,
the fused decode loop against single steps, token budgets and the prefix
cache across live switches.
"""
import pytest

from tests.helpers import COMMON, run_multidevice

pytestmark = pytest.mark.multidevice


def test_layouts_match_single_device_reference():
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP, TPEP, pack_params
from repro.models.registry import init_params
from repro.models.transformer import lm_forward
from repro.serving.kvcache import CacheConfig
from repro.serving.steps import build_mixed_step, build_decode_pack
params = init_params(cfg, jr.PRNGKey(0))
cc = CacheConfig(page_size=4, pages_ep=16, max_pages_per_req=8)
prompt = [5, 9, 17, 3, 101, 42]; P0 = len(prompt); n = 4
toks = list(prompt)
for _ in range(n):
    lg = lm_forward(cfg, params, jnp.array([toks]), remat=False)
    toks.append(int(jnp.argmax(lg[0, -1])))
ref = toks[P0:]
key = jr.key_data(jr.PRNGKey(1))
for layout in (TP, EP, TPEP):
    sp = pack_params(cfg, params, layout, 4,
                     expert_G=8 if layout == TPEP else None)
    pack = build_decode_pack(cfg, sp, layout, 4)
    kv = jnp.zeros((2, 4, cc.nelems(cfg, 4)), jnp.float32)
    bt = np.zeros((2, 4, 8), np.int32); bt[:, 0, :3] = [1, 2, 3]
    pre = build_mixed_step(cfg, mesh, layout, cc, 4, Sq=8, donate=False)
    ti = np.zeros((2, 4, 8), np.int32); ti[:, 0, :P0] = prompt
    pos = np.zeros((2, 4), np.int32)
    vl = np.zeros((2, 4), np.int32); vl[:, 0] = P0
    carry = jnp.zeros((2, 4), jnp.int32)
    src = np.full((2, 4), -1, np.int32)
    nxt, kv, _, carry = pre(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                            jnp.asarray(vl), jnp.asarray(bt), key, carry,
                            jnp.asarray(src))
    out = [int(nxt[0, 0])]
    dec = build_mixed_step(cfg, mesh, layout, cc, 4, Sq=1, donate=False)
    kvlen = P0
    for i in range(n - 1):
        # odd steps feed the token from the host, even ones from the carry
        ti = np.zeros((2, 4, 1), np.int32)
        src = np.full((2, 4), -1, np.int32)
        if i % 2:
            ti[:, 0, 0] = np.array(nxt)[:, 0]
        else:
            src[:, 0] = 0
        pos = np.zeros((2, 4), np.int32); pos[:, 0] = kvlen
        vl = np.zeros((2, 4), np.int32); vl[:, 0] = 1
        nxt, kv, _, carry = dec(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                                jnp.asarray(vl), jnp.asarray(bt), key, carry,
                                jnp.asarray(src))
        out.append(int(nxt[0, 0])); kvlen += 1
    assert out == ref, (layout, out, ref)
print("OK")
""")


def test_fused_decode_loop_matches_single_steps_per_layout():
    """Satellite acceptance: N fused decode steps must be byte-identical —
    sampled tokens AND KV bytes — to N single-step calls, for EVERY
    registered layout (tp / ep / tpep)."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP, TPEP, pack_params
from repro.models.registry import init_params
from repro.serving.kvcache import CacheConfig
from repro.serving.steps import (build_mixed_step, build_decode_pack,
                                 build_decode_loop)
params = init_params(cfg, jr.PRNGKey(0))
cc = CacheConfig(page_size=4, pages_ep=16, max_pages_per_req=8)
key = jr.key_data(jr.PRNGKey(1))
N = 4
prompts = {0: [5, 9, 17, 3, 101], 1: [42, 7, 88]}
for layout in (TP, EP, TPEP):
    G = 4
    sp = pack_params(cfg, params, layout, G,
                     expert_G=8 if layout == TPEP else None)
    pack = build_decode_pack(cfg, sp, layout, G)
    B = 4
    # prefill two requests into separate slots/pages
    kv = jnp.zeros((2, G, cc.nelems(cfg, G)), jnp.float32)
    pre = build_mixed_step(cfg, mesh, layout, cc, B, Sq=8, donate=False)
    ti = np.zeros((2, B, 8), np.int32); pos = np.zeros((2, B), np.int32)
    vl = np.zeros((2, B), np.int32); bt = np.zeros((2, B, 8), np.int32)
    pages = {0: [1, 2, 3], 1: [4, 5, 6]}
    # slot-sharded layouts: rows 0 and 1 live on model ranks 0 and 1, with
    # per-rank page pools; pooled layouts share one pool
    for i, p in prompts.items():
        ti[:, i, :len(p)] = p; vl[:, i] = len(p)
        bt[:, i, :3] = pages[i]
    carry = jnp.zeros((2, B), jnp.int32)
    src = jnp.full((2, B), -1, jnp.int32)
    nxt, kv, _, _ = pre(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                     jnp.asarray(vl), jnp.asarray(bt), key, carry, src)
    nxt = np.asarray(nxt)
    first = {i: int(nxt[0, i]) for i in prompts}
    # path A: N single steps with host feedback
    dec = build_mixed_step(cfg, mesh, layout, cc, B, Sq=1, donate=False)
    kv_a = kv; cur = dict(first); kl = {i: len(p) for i, p in prompts.items()}
    outs_a = {i: [] for i in prompts}
    for s in range(N):
        ti = np.zeros((2, B, 1), np.int32); pos = np.zeros((2, B), np.int32)
        vl = np.zeros((2, B), np.int32)
        for i in prompts:
            ti[:, i, 0] = cur[i]; pos[:, i] = kl[i]; vl[:, i] = 1
        nx, kv_a, _, _ = dec(pack, kv_a, jnp.asarray(ti), jnp.asarray(pos),
                          jnp.asarray(vl), jnp.asarray(bt), key, carry, src)
        nx = np.asarray(nx)
        for i in prompts:
            cur[i] = int(nx[0, i]); kl[i] += 1; outs_a[i].append(cur[i])
    # path B: one fused dispatch, tokens fed back on device
    loop = build_decode_loop(cfg, mesh, layout, cc, B, N, donate=False)
    tok = np.zeros((2, B), np.int32); pos = np.zeros((2, B), np.int32)
    bud = np.zeros((2, B), np.int32)
    for i, p in prompts.items():
        tok[:, i] = first[i]; pos[:, i] = len(p); bud[:, i] = 100
    out, kv_b, t2, p2, b2, _ = loop(pack, kv, jnp.asarray(tok),
                                 jnp.asarray(pos), jnp.asarray(bud),
                                 jnp.asarray(bt), key)
    out = np.asarray(out)
    outs_b = {i: [int(x) for x in out[0, i, :N]] for i in prompts}
    assert outs_a == outs_b, (layout, outs_a, outs_b)
    assert np.array_equal(np.asarray(kv_a), np.asarray(kv_b)), layout
    assert np.asarray(p2)[0, 0] == len(prompts[0]) + N
    assert np.asarray(b2)[0, 0] == 100 - N
print("OK")
""", timeout=1200)


def test_fused_live_switch_matches_baseline():
    """Satellite acceptance: a live switch mid-stream with decode_steps > 1
    (pipeline drained to a step boundary before the plan) must match a
    never-switched single-step baseline byte-for-byte — monolithic and
    chunked/overlapped, across layout pairs including tpep."""
    run_multidevice(COMMON + """
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def make_reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200,
            int(rng.integers(3, 10)))), max_new_tokens=int(rng.integers(4, 12)),
            arrival_s=0.0) for i in range(6)]
def run(start, n, switch_at=None, target=None, chunk=0):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        decode_steps=n, chunk_layers=chunk))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(target)
        eng.step(); i += 1
        assert i < 500
    assert eng._pending is None
    return {r.rid: r.output for r in eng.finished}
base = run("tp", 1)
for src, dst in (("tp", "ep"), ("ep", "tp"), ("tp", "tpep"), ("ep", "tpep")):
    assert run(src, 4, 4, dst) == base, f"{src}->{dst} fused diverged"
out = run("tp", 4, 5, "ep", chunk=1)   # overlapped switch, fused overlap decode
assert out == base, "chunked switch under fused decode diverged"
print("OK")
""", timeout=1200)


def test_mixed_batch_matches_two_phase_across_switches():
    """Tentpole acceptance: the token-budgeted mixed dispatch must keep
    outputs byte-identical to a never-switched run with single-token decode
    on a real SPMD mesh — on a prefill-storm-shaped batch (long prompts
    landing while short ones decode), across live tp -> ep -> tpep
    switches, under a budget of four chunks, and with the fused decode loop
    (decode_steps=4) suspending for the storm and resuming."""
    run_multidevice(COMMON + """
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def make_reqs():
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=list(rng.integers(5, 200, 4)),
            max_new_tokens=14, forced_len=14, arrival_s=0.0)
            for i in range(3)]                       # live decoders
    reqs += [Request(rid=3 + j, prompt=list(rng.integers(5, 200, 20)),
             max_new_tokens=3, forced_len=3, arrival_s=0.0)
             for j in range(3)]                      # the storm
    return reqs
def run(n=1, switches=(), budget=0):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout="tp", layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        decode_steps=n, token_budget=budget))
    for r in make_reqs(): eng.submit(r)
    sw = dict(switches); i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if i in sw:
            eng.execute_switch(sw[i])
        eng.step(); i += 1
        assert i < 500
    assert eng.metrics.mixed_dispatches > 0, "storm never mixed"
    assert len(eng.switch_records) == len(sw)
    return {r.rid: r.output for r in eng.finished}
base = run()                                # never switched, decode_steps=1
sw = ((3, "ep"), (8, "tpep"))
assert run(switches=sw) == base, "mixed tp->ep->tpep diverged"
assert run(switches=sw, budget=32) == base, "wide budget + switches diverged"
assert run(n=4) == base, "mixed fused suspend/resume diverged"
assert run(n=4, switches=sw) == base, "mixed fused + switches diverged"
print("OK")
""", timeout=1200)


def test_prefix_cache_rollout_switches_match_baseline():
    """Tentpole acceptance: a rollout group with shared prefixes
    (samples_per_prompt), prefix cache ON, live tp -> ep -> tpep switches
    mid-group, must produce greedy outputs byte-identical to a cache-off,
    never-switched baseline — and must actually share (hits > 0, fewer
    prefill tokens), with the allocator's conservation invariant intact
    across every view change."""
    run_multidevice(COMMON + """
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.workloads import RolloutSpec, rollout_batch
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
spec = RolloutSpec(num_prompts=8, samples_per_prompt=4, prompt_median=10,
                   prompt_max=14, output_median=6, output_p99=12,
                   output_cap=12, token_range=(5, 200))
def run(prefix, switches=()):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout="tp", layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        prefix_cache=prefix))
    for r in rollout_batch(spec, seed=2):
        eng.submit(r)
    i = 0
    plan = dict(switches)
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if i in plan:
            eng.execute_switch(plan[i])
        eng.step(); i += 1
        assert i < 800
    for al in eng.alloc:
        al.check()
    return eng
base = run(False)
ref = {r.rid: r.output for r in base.finished}
cached = run(True)
assert {r.rid: r.output for r in cached.finished} == ref, "cache-on diverged"
assert cached.metrics.prefix_hits > 0, "no prefix hits"
assert cached.metrics.prefill_tokens < base.metrics.prefill_tokens
switched = run(True, switches=((3, "ep"), (6, "tpep"), (9, "tp")))
assert {r.rid: r.output for r in switched.finished} == ref, \
    "cache + live tp->ep->tpep switches diverged"
assert switched.metrics.prefix_hits > 0
assert len(switched.switch_records) == 3
for eng in (cached, switched):
    eng.clear_prefix_cache()
    for al in eng.alloc:
        al.check()
        assert al.total_free() == al.capacity * al.npools()
print("OK")
""", timeout=1200)
