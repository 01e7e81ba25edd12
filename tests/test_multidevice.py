"""Multi-device integration tests (subprocesses with 8 host devices).

Each test asserts the paper's core invariants on a real SPMD mesh:
layout equivalence, exact output preservation across live switches,
reshard-path equivalence, KV-migration byte fidelity, training parity.
"""
import pytest

from tests.helpers import run_multidevice

pytestmark = pytest.mark.multidevice


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


def test_layouts_match_single_device_reference():
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP, TPEP, pack_params
from repro.models.registry import init_params
from repro.models.transformer import lm_forward
from repro.serving.kvcache import CacheConfig
from repro.serving.steps import build_serve_step, build_decode_pack
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
    pre = build_serve_step(cfg, mesh, layout, cc, 4, Sq=8, donate=False)
    ti = np.zeros((2, 4, 8), np.int32); ti[:, 0, :P0] = prompt
    pos = np.zeros((2, 4), np.int32)
    vl = np.zeros((2, 4), np.int32); vl[:, 0] = P0
    nxt, kv, _ = pre(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                  jnp.asarray(vl), jnp.asarray(bt), key)
    out = [int(nxt[0, 0])]
    dec = build_serve_step(cfg, mesh, layout, cc, 4, Sq=1, donate=False)
    kvlen = P0
    for i in range(n - 1):
        ti = np.zeros((2, 4, 1), np.int32); ti[:, 0, 0] = np.array(nxt)[:, 0]
        pos = np.zeros((2, 4), np.int32); pos[:, 0] = kvlen
        vl = np.zeros((2, 4), np.int32); vl[:, 0] = 1
        nxt, kv, _ = dec(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                      jnp.asarray(vl), jnp.asarray(bt), key)
        out.append(int(nxt[0, 0])); kvlen += 1
    assert out == ref, (layout, out, ref)
print("OK")
""")


def test_live_switch_preserves_outputs():
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
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
def run(switch_at=None, start=TP):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, ladder=(4, 8), prefill_chunk=8,
        temperature=0.0, policy=pol, seed=0))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP if eng.active == TP else TP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}
base = run(None, TP)
assert run(None, EP) == base, "static EP != static TP"
for at in (2, 5, 9):
    assert run(at, TP) == base, f"TP->EP@{at}"
    assert run(at, EP) == base, f"EP->TP@{at}"
print("OK")
""", timeout=1200)


def test_chunked_switch_preserves_outputs_and_shrinks_pause():
    """Overlapped layer-chunked switch (EngineConfig.chunk_layers > 0):
    outputs must match the static baseline exactly, pause_s must be
    recorded strictly below total_s once the movers are warm."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
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
def run(switch_at=None, start=TP, chunk=0):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, ladder=(4, 8), prefill_chunk=8,
        temperature=0.0, policy=pol, seed=0, chunk_layers=chunk))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP if eng.active == TP else TP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}, eng
base, _ = run(None, TP)
for at in (2, 5, 9):
    for start in (TP, EP):
        out, eng = run(at, start, chunk=1)
        assert out == base, (at, start)
        r = eng.switch_records[-1]
        assert r.chunks == 2 and r.pause_s <= r.total_s, vars(r)
        assert eng.metrics.switch_events, "switch not recorded in metrics"
# warm movers inside one engine: pause strictly below total
pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
    start_layout=TP, ladder=(4, 8), prefill_chunk=8, temperature=0.0,
    policy=pol, seed=0, chunk_layers=1))
for r in make_reqs(): eng.submit(r)
for i in range(6): eng.step()
for target in (EP, TP, EP, TP):
    eng.execute_switch(target)
    eng.step()
warm = eng.switch_records[-2:]
assert all(r.pause_s < r.total_s for r in warm), \
    [(r.pause_s, r.total_s) for r in warm]
print("OK")
""", timeout=1200)


LAYOUT_NAMES = ("tp", "ep", "tpep")
ORDERED_PAIRS = [(a, b) for a in LAYOUT_NAMES for b in LAYOUT_NAMES
                 if a != b]


@pytest.mark.parametrize("src,dst", ORDERED_PAIRS,
                         ids=[f"{a}_to_{b}" for a, b in ORDERED_PAIRS])
def test_pairwise_switch_preserves_outputs(src, dst):
    """N-layout acceptance: for EVERY ordered pair of registered layouts
    (including the hybrid tpep), serving statically on the source and
    live-switching source -> destination mid-flight must both be
    byte-identical to a never-switched baseline."""
    run_multidevice(COMMON + f"""
src, dst = {src!r}, {dst!r}
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
def run(start, switch_at=None, target=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(target)
        eng.step(); i += 1
        assert i < 500
    return {{r.rid: r.output for r in eng.finished}}
base = run("tp")                          # never-switched baseline
assert run(src) == base, f"static {{src}} != baseline"
assert run(src, 4, dst) == base, f"{{src}}->{{dst}} diverged"
print("OK")
""", timeout=1200)


def test_fused_decode_loop_matches_single_steps_per_layout():
    """Satellite acceptance: N fused decode steps must be byte-identical —
    sampled tokens AND KV bytes — to N single-step calls, for EVERY
    registered layout (tp / ep / tpep)."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP, TPEP, pack_params
from repro.models.registry import init_params
from repro.serving.kvcache import CacheConfig
from repro.serving.steps import (build_serve_step, build_decode_pack,
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
    pre = build_serve_step(cfg, mesh, layout, cc, B, Sq=8, donate=False)
    ti = np.zeros((2, B, 8), np.int32); pos = np.zeros((2, B), np.int32)
    vl = np.zeros((2, B), np.int32); bt = np.zeros((2, B, 8), np.int32)
    pages = {0: [1, 2, 3], 1: [4, 5, 6]}
    # slot-sharded layouts: rows 0 and 1 live on model ranks 0 and 1, with
    # per-rank page pools; pooled layouts share one pool
    for i, p in prompts.items():
        ti[:, i, :len(p)] = p; vl[:, i] = len(p)
        bt[:, i, :3] = pages[i]
    nxt, kv, _ = pre(pack, kv, jnp.asarray(ti), jnp.asarray(pos),
                  jnp.asarray(vl), jnp.asarray(bt), key)
    nxt = np.asarray(nxt)
    first = {i: int(nxt[0, i]) for i in prompts}
    # path A: N single steps with host feedback
    dec = build_serve_step(cfg, mesh, layout, cc, B, Sq=1, donate=False)
    kv_a = kv; cur = dict(first); kl = {i: len(p) for i, p in prompts.items()}
    outs_a = {i: [] for i in prompts}
    for s in range(N):
        ti = np.zeros((2, B, 1), np.int32); pos = np.zeros((2, B), np.int32)
        vl = np.zeros((2, B), np.int32)
        for i in prompts:
            ti[:, i, 0] = cur[i]; pos[:, i] = kl[i]; vl[:, i] = 1
        nx, kv_a, _ = dec(pack, kv_a, jnp.asarray(ti), jnp.asarray(pos),
                       jnp.asarray(vl), jnp.asarray(bt), key)
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
    """Tentpole acceptance: the token-budgeted mixed dispatch must be
    byte-identical to the legacy two-phase loop on a real SPMD mesh — on a
    prefill-storm-shaped batch (long prompts landing while short ones
    decode), across live tp -> ep -> tpep switches, and with the fused
    decode loop (decode_steps=4) suspending for the storm and resuming."""
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
def run(mixed, n=1, switches=()):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout="tp", layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        decode_steps=n, mixed_batch=mixed))
    for r in make_reqs(): eng.submit(r)
    sw = dict(switches); i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if i in sw:
            eng.execute_switch(sw[i])
        eng.step(); i += 1
        assert i < 500
    if mixed:
        assert eng.metrics.mixed_dispatches > 0, "storm never mixed"
    return {r.rid: r.output for r in eng.finished}
base = run(False)                           # legacy two-phase reference
assert run(True) == base, "mixed != two-phase (static tp)"
sw = ((3, "ep"), (8, "tpep"))
assert run(False, switches=sw) == base, "two-phase switched diverged"
assert run(True, switches=sw) == base, "mixed tp->ep->tpep diverged"
assert run(True, n=4) == base, "mixed fused suspend/resume diverged"
assert run(True, n=4, switches=sw) == base, "mixed fused + switches diverged"
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


def test_reshard_paths_agree():
    run_multidevice(COMMON + """
from repro.core.switch import (make_reshard_experts,
                               make_reshard_experts_direct)
from repro.models.moe import make_expert_layout, pack_w13, pack_experts
E, I, D, L, G = 8, 32, 32, 2, 4
key = jr.PRNGKey(0)
w13 = jr.normal(key, (L, E, 2*I, D), jnp.float32)
w2 = jr.normal(jr.fold_in(key, 1), (L, E, D, I), jnp.float32)
lay_tp = make_expert_layout(E, G, "tp"); lay_ep = make_expert_layout(E, G, "ep")
pk13 = lambda w, lay: jax.vmap(lambda x: pack_w13(x, lay))(w)
pk2 = lambda w, lay: jax.vmap(lambda x: pack_experts(x, lay, 2))(w)
w13_ep, w2_ep = pk13(w13, lay_ep), pk2(w2, lay_ep)
w13_tp, w2_tp = pk13(w13, lay_tp), pk2(w2, lay_tp)
moe = {"w13": w13_ep, "w2": w2_ep}
sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), moe)
xla = make_reshard_experts(cfg, mesh, "ep", "tp", donate=False)(sds)(moe)
d13, d2 = make_reshard_experts_direct(cfg, mesh, "ep_to_tp")(w13_ep, w2_ep)
assert np.array_equal(np.asarray(xla["w13"]), np.asarray(w13_tp))
assert np.array_equal(np.asarray(d13), np.asarray(w13_tp))
assert np.array_equal(np.asarray(d2), np.asarray(w2_tp))
b13, b2 = make_reshard_experts_direct(cfg, mesh, "tp_to_ep")(d13, d2)
assert np.array_equal(np.asarray(b13), np.asarray(w13_ep))
print("OK")
""")


def test_train_layout_parity_and_checkpoint_restart():
    run_multidevice(COMMON + """
from repro.training.train_loop import build_train_step
from repro.training.optimizer import AdamWConfig
from repro.training.data import MarkovData
from repro.distributed.checkpoint import save_checkpoint, restore_checkpoint
import tempfile, os
data = MarkovData(cfg.vocab_size, 16, 8, seed=1)
losses = {}
finals = {}
for layout in ("tp", "ep"):
    step, init_fn, (psh, osh, bsh) = build_train_step(
        cfg, mesh, layout, opt=AdamWConfig(lr=1e-2, warmup_steps=2,
                                           total_steps=20))
    params, opt = init_fn(jr.PRNGKey(0))
    ls = []
    for i in range(6):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, m = step(params, opt, b)
        ls.append(float(m["loss"]))
    losses[layout] = ls
    finals[layout] = params
assert losses["tp"][-1] < losses["tp"][0]
assert all(abs(a - b) < 1e-3 for a, b in zip(losses["tp"], losses["ep"])), \
    (losses)
# checkpoint from EP, restore into TP, losses must continue identically
with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, cfg, finals["ep"], "ep", 4, step=6)
    restored, _, st = restore_checkpoint(td, cfg, "tp", 4)
    la = jax.tree.leaves(restored); lb = jax.tree.leaves(finals["tp"])
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-4)
print("OK")
""", timeout=1200)


def test_compressed_allreduce_and_fault_recovery():
    run_multidevice(COMMON + """
# int8 error-feedback allreduce vs exact mean
from repro.distributed.compression import make_compressed_allreduce
G = 2
g = jr.normal(jr.PRNGKey(0), (2, 64))     # per-data-rank grads
res = jnp.zeros((2, 64))
fn = make_compressed_allreduce(mesh, "data")
exact = jnp.mean(g, axis=0)
acc = jnp.zeros(64)
out, res = fn(g, res)
err1 = float(jnp.abs(out[0] - exact).max())
out2, res = fn(g, res)      # error feedback improves the running average
assert err1 < 0.1, err1

# serving fault recovery: kill a rank, re-prefill, outputs preserved
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.distributed.elastic import fail_rank
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                    max_new_tokens=8, arrival_s=0.0) for i in range(4)]
def run(fail_at=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=EP, ladder=(4, 8), prefill_chunk=8, temperature=0.0,
        policy=pol, seed=0))
    for r in reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if fail_at is not None and i == fail_at:
            fail_rank(eng, data_group=0, rank=1)
        eng.step(); i += 1
        assert i < 800
    # generated text = tokens teacher-forced into the prompt at recovery
    # (everything past the original 6-token prompt) + post-recovery output
    return {r.rid: list(r.prompt[6:]) + list(r.output)
            for r in eng.finished}
base = run(None)
rec = run(fail_at=6)
# full generated text survives the failure + re-prefill, every request
assert base == rec, (base, rec)
print("OK")
""", timeout=1200)


FAULT_PHASES = ("before", "chunk0", "chunk1", "after")


@pytest.mark.parametrize("phase", FAULT_PHASES)
def test_rank_failure_at_every_switch_phase(phase):
    """Robustness acceptance (DESIGN.md §12): a rank failure BEFORE a
    chunked tp->ep switch, AT each chunk boundary DURING it (the switch
    must abort, source layout stays live), and AFTER it commits (per-rank
    EP failure -> degraded-mode placement + recovery) — in every phase the
    full generated text of every request is byte-identical to a
    never-faulted, never-switched baseline."""
    run_multidevice(COMMON + f"""
phase = {phase!r}
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.faults import Fault, FaultPlan
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
P = 6                                    # original prompt length
def reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200, P)),
                    max_new_tokens=10, arrival_s=0.0) for i in range(6)]
PLANS = {{
    # TP failure while no switch is staged; the later switch commits
    "before": (Fault("rank_fail", at_step=3, data_group=0, rank=1),
               Fault("switch", at_step=8, target="ep")),
    # failure at a chunk boundary of the in-flight switch: abort first
    # (SwitchExecutor.abort), then the normal re-prefill recovery
    "chunk0": (Fault("switch", at_step=4, target="ep"),
               Fault("rank_fail", switch_chunk=0, switch_index=0,
                     data_group=0, rank=1)),
    "chunk1": (Fault("switch", at_step=4, target="ep"),
               Fault("rank_fail", switch_chunk=1, switch_index=0,
                     data_group=0, rank=1)),
    # per-rank EP failure after the commit: degraded-mode placement
    "after": (Fault("switch", at_step=4, target="ep"),
              Fault("rank_fail", at_step=12, data_group=0, rank=1)),
}}
def run(plan=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout="tp", ladder=(4, 8), prefill_chunk=8, temperature=0.0,
        policy=pol, seed=0, chunk_layers=1,
        faults=None if plan is None else FaultPlan(plan)))
    for r in reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        eng.step(); i += 1
        assert i < 800
    # generated text = tokens teacher-forced back into the prompt at
    # recovery (everything past the original prompt) + remaining output
    return eng, {{r.rid: list(r.prompt[P:]) + list(r.output)
                  for r in eng.finished}}
_, base = run(None)                      # never-faulted, never-switched
eng, out = run(PLANS[phase])
assert out == base, (phase, out, base)
s = eng.metrics.summary()
assert s["rank_failures"] == 1 and eng._faults.done
if phase in ("chunk0", "chunk1"):
    # the in-flight switch aborted; the source layout never moved
    assert str(eng.active) == "tp" and s["switches"] == 0
    assert s["switch_aborts"] == 1 and eng.coord.backoff_mult > 1.0
else:
    assert str(eng.active) == "ep" and s["switches"] == 1
    assert s["switch_aborts"] == 0
if phase == "after":
    # EP is per-rank: the failure degrades one pool, recovery revives it
    assert s["degraded_recoveries"] >= 1
    assert not eng.sched.dead_pools
for al in eng.alloc:
    al.check()
print("OK")
""", timeout=1200)


def test_ssm_serve_step_matches_reference():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
import jax.random as jr
from repro.configs import get_config
from repro.core.layouts import EP, TP, pack_params
from repro.models.registry import init_params
from repro.models.ssm_lm import ssm_lm_forward
from repro.serving.steps_extra import build_ssm_serve_step, ssm_state_shapes
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
G, Dd, Bslot = 4, 2, 4
cfg = get_config("mamba2-780m").reduced(
    num_layers=2, d_model=32, vocab_size=256, ssm_state=8, ssm_head_dim=8,
    ssm_chunk=4, param_dtype=jnp.float32, compute_dtype=jnp.float32)
params = init_params(cfg, jr.PRNGKey(0))
prompt = [5, 9, 17, 3, 101]
n = 5
toks = list(prompt)
for _ in range(n):
    lg = ssm_lm_forward(cfg, params, jnp.array([toks]), remat=False)
    toks.append(int(jnp.argmax(lg[0, -1])))
ref = toks[len(prompt):]
for layout in (TP, EP):
    sp = pack_params(cfg, params, "tp", G)   # vocab pad only (no experts)
    pack = {"embed": sp["embed"], "lm_head": sp["lm_head"],
            "final_norm": sp["final_norm"], "layers": sp["layers"]}
    step = build_ssm_serve_step(cfg, mesh, layout, Bslot, donate=False)
    shp = ssm_state_shapes(cfg, Dd, Bslot)
    cx = jnp.zeros(shp["conv_x"], jnp.float32)
    cB = jnp.zeros(shp["conv_B"], jnp.float32)
    cC = jnp.zeros(shp["conv_C"], jnp.float32)
    st = jnp.zeros(shp["ssm"], jnp.float32)
    key = jr.key_data(jr.PRNGKey(1))
    out = []
    seq = list(prompt)
    for i in range(len(prompt) + n - 1):
        tok = np.zeros((Dd, Bslot, 1), np.int32)
        tok[:, 0, 0] = seq[i] if i < len(seq) else out[-1]
        vl = np.zeros((Dd, Bslot), np.int32); vl[:, 0] = 1
        nxt, cx, cB, cC, st = step(pack, cx, cB, cC, st,
                                   jnp.asarray(tok), jnp.asarray(vl), key)
        if i >= len(prompt) - 1:
            t = int(np.asarray(nxt)[0, 0])
            out.append(t)
            if i >= len(seq) - 1:
                seq.append(t)
    assert out == ref, (layout, out, ref)
print("OK")
""", timeout=900)


def test_moe_backend_parity_across_live_switch():
    """moe_backend="interpret" must reproduce the einsum
    decode path token-for-token on the real (2, 4) mesh, including across
    a live tp->ep chunked switch (DESIGN.md §14 acceptance)."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
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
def run(backend, switch_at=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=TP, ladder=(4, 8), prefill_chunk=8, temperature=0.0,
        policy=pol, seed=0, chunk_layers=1, moe_backend=backend))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}
for at in (None, 4):
    ref = run("ref", at)
    ker = run("interpret", at)
    assert ker == ref, f"kernel MoE diverged on mesh (switch_at={at})"
print("OK")
""", timeout=1200)
