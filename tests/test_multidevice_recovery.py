"""Faults and training on a real SPMD mesh (subprocesses with forced host
devices): rank failures around a chunked switch, a scripted chaos
replay, training layout parity with checkpoint restart, compressed
all-reduce, and the SSM serve step.
"""
import pytest

from tests.helpers import COMMON, run_multidevice

pytestmark = pytest.mark.multidevice


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


def test_chaos_fault_plan_replay():
    """One scripted fault plan on a VirtualClock (DESIGN.md §12): the
    pool seized (decode growth preempts), a rank lost at chunk 0 of a
    tp->ep switch (the switch aborts, the group re-prefills), a client
    disconnect, a retried switch that commits, then a rank lost under EP
    (degraded-mode placement and recovery). Against a run of the same
    switches without faults: survivors byte-identical, the disconnected
    request's output a strict prefix, an abort and a degraded recovery
    recorded, every recovery within 120 iterations, pages conserved."""
    run_multidevice(COMMON + """
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.faults import Fault, FaultPlan
from repro.serving.frontend import AsyncEngine, VirtualClock
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
from repro.serving.workloads import replay
mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
CUT = 2                                  # the request the client drops
def trace():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=[int(x) for x in rng.integers(
                5, 200, int(rng.integers(8, 15)))],
                    max_new_tokens=n, arrival_s=0.02 * i, slo_class="batch")
            for i, n in enumerate((40, 48, 56, 64, 40, 56, 48, 64))]
def chaos():
    # a Fault records that it fired: a fresh plan for every run
    return (Fault("pool_exhaust", at_step=10, data_group=0, pool=0,
                  duration_steps=6),
            Fault("switch", at_step=14, target="ep"),
            Fault("rank_fail", switch_chunk=0, switch_index=0,
                  data_group=0, rank=1),
            Fault("client_disconnect", at_step=30, rid=CUT),
            Fault("switch", at_step=44, target="ep"),   # the retry
            Fault("rank_fail", at_step=52, data_group=0, rank=2))
def run(faults):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, CacheConfig(
        page_size=8, pages_ep=64, max_pages_per_req=16), ecfg=EngineConfig(
        start_layout="tp", ladder=(4, 8), prefill_chunk=16,
        temperature=0.0, policy=pol, seed=0, chunk_layers=1,
        clock=VirtualClock(), faults=FaultPlan(faults)))
    fe = AsyncEngine(eng, step_dt=0.05)
    streams = replay(fe, trace())
    summary = fe.run_until_complete()
    assert all(st.finished for st in streams.values())
    for a in eng.sched.alloc:
        a.check()
    return eng, {rid: st.drain_available() for rid, st in streams.items()}, \
        summary
_, base, calm = run([f for f in chaos() if f.kind == "switch"])
eng, out, s = run(chaos())
assert calm["switches"] == 1 and eng._faults.done
assert all(out[rid] == base[rid] for rid in base if rid != CUT)
assert len(out[CUT]) < len(base[CUT]) and out[CUT] == base[CUT][:len(out[CUT])]
assert s["switch_aborts"] >= 1 and s["switches"] == 1
assert s["degraded_recoveries"] >= 1 and s["rank_failures"] == 2
assert s["recoveries"] >= 1 and s["recovery_steps_max"] <= 120
assert s["client_disconnects"] == 1 and s["pool_exhaust_events"] == 1
print("OK")
""", timeout=1200)
