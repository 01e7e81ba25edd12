"""Token-budgeted mixed-batch dispatch (DESIGN.md §10), single device.

Batch composition is an execution-shape choice, not a semantic one: at
temperature 0 every request's output depends only on its own prompt and
KV, so a token budget of one prefill chunk per step and one of four
chunks (`WIDE`) must give byte-identical outputs — on a prefill storm,
across a live layout switch, under the fused decode loop, and with
shared-prefix reuse in play — and so must a run whose layout never
switched.
"""
import copy

import numpy as np
import pytest

from repro.core.policy import PolicyConfig
from repro.launch.mesh import make_mesh
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.frontend import AsyncEngine, VirtualClock
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
from repro.serving.workloads import StormSpec, replay, storm_trace


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


# token budget of four prefill chunks (the default budget is one chunk)
WIDE = 32

SPEC = StormSpec(n_decoders=2, decoder_prompt=6, decoder_output=10,
                 n_storm=3, storm_prompt=24, storm_output=2,
                 storm_start_s=0.2, storm_interval_s=0.1)


def _mk(cfg, mesh, **kw):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    return MoebiusEngine(cfg, mesh,
                         CacheConfig(page_size=4, pages_ep=64,
                                     max_pages_per_req=16),
                         ecfg=EngineConfig(start_layout="tp", ladder=(4, 8),
                                           prefill_chunk=8, temperature=0.0,
                                           policy=pol, **kw))


def _outputs(eng, reqs0):
    """Full generated sequence per rid (robust to a preemption fold)."""
    plen0 = {r.rid: r.prompt_len for r in reqs0}
    return {r.rid: list(r.prompt[plen0[r.rid]:]) + list(r.output)
            for r in eng.finished}


def _run_trace(cfg, mesh, reqs0, **kw):
    eng = _mk(cfg, mesh, clock=VirtualClock(), **kw)
    fe = AsyncEngine(eng, step_dt=0.05)
    streams = replay(fe, copy.deepcopy(reqs0))
    fe.run_until_complete()
    assert all(s.finished for s in streams.values())
    return _outputs(eng, reqs0), eng


def test_mixed_matches_two_phase_on_storm_trace(tiny_moe, mesh11):
    """The flagship identity: a prefill storm over live decoders produces
    byte-identical outputs whether each dispatch packs one prefill chunk
    beside the decode rows or four."""
    reqs0 = storm_trace(SPEC, seed=0)
    out_n, eng_n = _run_trace(tiny_moe, mesh11, reqs0)
    out_w, eng_w = _run_trace(tiny_moe, mesh11, reqs0, token_budget=WIDE)
    assert out_n == out_w
    # the storm really did share dispatches with live decode rows, and
    # the wide budget packed its prompts into fewer dispatches
    assert eng_n.metrics.mixed_dispatches > 0
    assert eng_w.metrics.mixed_dispatches > 0
    assert eng_w.metrics.dispatches < eng_n.metrics.dispatches


def test_mixed_matches_two_phase_across_live_switch(tiny_moe, mesh11):
    """Same identity with a live tp->ep switch mid-run under both budgets,
    against a never-switched run (the switch drains in-flight work, then
    the new layout resumes the same plan shapes)."""
    rng = np.random.default_rng(1)
    reqs0 = [Request(rid=i, prompt=list(rng.integers(5, 200, 6 + 8 * (i % 2))),
                     max_new_tokens=6, forced_len=6, arrival_s=0.0)
             for i in range(5)]

    def run(switch, **kw):
        eng = _mk(tiny_moe, mesh11, **kw)
        for r in copy.deepcopy(reqs0):
            eng.submit(r)
        switched, i = False, 0
        while eng.pending or eng.waiting or eng.prefilling or eng.running:
            if switch and not switched and eng.running:
                eng.execute_switch("ep")
                switched = True
            eng.step()
            i += 1
            assert i < 1000
        assert switched == switch
        return _outputs(eng, reqs0)

    base = run(False)
    assert run(True) == base
    assert run(True, token_budget=WIDE) == base


def test_mixed_with_fused_decode_suspends_and_resumes(tiny_moe, mesh11):
    """decode_steps > 1: a storm forces the fused pipeline to drain to a
    step boundary (suspend), serve single-token mixed steps, then re-join
    the fused loop — outputs still byte-identical to single-token decode
    under either budget."""
    rng = np.random.default_rng(2)
    reqs0 = [Request(rid=i, prompt=list(rng.integers(5, 200, 5 + 10 * (i % 2))),
                     max_new_tokens=9, forced_len=9, arrival_s=0.0)
             for i in range(5)]

    def run(steps, **kw):
        eng = _mk(tiny_moe, mesh11, decode_steps=steps, **kw)
        for r in copy.deepcopy(reqs0):
            eng.submit(r)
        eng.run(max_steps=2000)
        return _outputs(eng, reqs0)

    ref = run(1)
    assert run(4) == ref
    assert run(1, token_budget=WIDE) == ref
    assert run(4, token_budget=WIDE) == ref


def test_mixed_budget_cap_and_min_grant_invariant(tiny_moe, mesh11):
    """Every planned iteration respects the budget: decode + prefill
    tokens <= budget, except the 1-token min-grant when decode alone
    saturates it."""
    eng = _mk(tiny_moe, mesh11, token_budget=6)
    plans = []
    orig = eng.sched.plan_mixed

    def spy(*a, **k):
        p = orig(*a, **k)
        plans.append(p)
        return p

    eng.sched.plan_mixed = spy
    rng = np.random.default_rng(3)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 12)),
                           max_new_tokens=8, forced_len=8, arrival_s=0.0))
    eng.run(max_steps=2000)
    assert len(eng.finished) == 6
    assert any(p.prefill_tokens for p in plans)
    for p in plans:
        total = p.decode_tokens + p.prefill_tokens
        assert total <= max(6, p.decode_tokens + 1), p


def test_mixed_matches_two_phase_with_shared_prefixes(tiny_moe, mesh11):
    """Prefix-cache forks + CoW under the mixed planner: groups of
    requests sharing one prompt reuse cached pages, and the outputs under
    either budget match byte-for-byte."""
    rng = np.random.default_rng(4)
    base = list(rng.integers(5, 200, 10))
    reqs0 = [Request(rid=i, prompt=list(base) + [int(i) + 7],
                     max_new_tokens=6, forced_len=6, arrival_s=0.0)
             for i in range(4)]

    def run(**kw):
        eng = _mk(tiny_moe, mesh11, **kw)
        for r in copy.deepcopy(reqs0):
            eng.submit(r)
        eng.run(max_steps=2000)
        return _outputs(eng, reqs0), eng

    out_n, eng_n = run()
    out_w, eng_w = run(token_budget=WIDE)
    assert out_n == out_w
    assert eng_n.metrics.prefix_hits > 0 and eng_w.metrics.prefix_hits > 0


def test_switch_overlap_steps_dispatch_decode_only_plans(tiny_moe, mesh11):
    """Between the layer chunks of a chunked switch the engine keeps
    decoding on the source layout: every overlap step is a mixed plan with
    decode rows only (Sq 1, the rung `pick_B` gives the running rows)
    dispatched through run_mixed; prefill waits for the commit, and the
    outputs match a never-switched run."""
    rng = np.random.default_rng(5)
    reqs0 = [Request(rid=i, prompt=list(rng.integers(5, 200, 5)),
                     max_new_tokens=12, forced_len=12, arrival_s=0.0)
             for i in range(3)]
    # a long prompt still prefilling when the first switch starts
    reqs0.append(Request(rid=3, prompt=list(rng.integers(5, 200, 40)),
                         max_new_tokens=4, forced_len=4, arrival_s=0.0))

    def run(switches):
        eng = _mk(tiny_moe, mesh11, chunk_layers=1)
        seen = []                  # [plan, rung expected, in switch, ran]
        plan_mixed, run_mixed = eng.sched.plan_mixed, eng.ex.run_mixed

        def spy_plan(*a, **k):
            B = eng.sched.pick_B(max(1, len(eng.sched.running)))
            plan = plan_mixed(*a, **k)
            seen.append([plan, B, eng.switch_in_progress(), False])
            return plan

        def spy_run(plan, step_i):
            assert seen[-1][0] is plan
            seen[-1][3] = True
            return run_mixed(plan, step_i)

        eng.sched.plan_mixed, eng.ex.run_mixed = spy_plan, spy_run
        for r in copy.deepcopy(reqs0):
            eng.submit(r)
        sw, i = dict(switches), 0
        while eng.sched.has_work():
            if i in sw:
                assert eng.sched.prefilling or sw[i] == "tp"
                assert eng.execute_switch(sw[i])
            eng.step()
            i += 1
            assert i < 1000
        return _outputs(eng, reqs0), seen, eng

    base, _, _ = run(())
    out, seen, eng = run(((2, "ep"), (9, "tp")))
    assert out == base
    assert len(eng.switch_records) == 2
    overlap = [s for s in seen if s[2]]
    # two layers, one per chunk: two overlap steps a switch
    assert len(overlap) == 4
    for plan, B, _, ran in overlap:
        assert ran and plan.rows
        assert plan.Sq == 1 and plan.prefill_tokens == 0
        assert {row.kind for row in plan.rows} == {"decode"}
        assert plan.B == B


# ---------------------------------------------------------------------------
# the one-deep pipeline: step n+1 is planned and staged while n is in flight
# ---------------------------------------------------------------------------

def _pipeline_trace():
    """Two short decoders (each prompt ends in the dispatch before its
    first decode), a three-chunk prompt beside them, its prefix-cache
    follower, a request that finishes at its first token, and a wave that
    takes the rung from 4 to 8 and back."""
    rng = np.random.default_rng(7)
    shared = list(rng.integers(5, 200, 20))
    reqs = [Request(rid=0, prompt=list(rng.integers(5, 200, 5)),
                    max_new_tokens=14, arrival_s=0.0),
            Request(rid=1, prompt=list(rng.integers(5, 200, 6)),
                    max_new_tokens=9, arrival_s=0.0),
            Request(rid=2, prompt=shared, max_new_tokens=4, arrival_s=1.0),
            Request(rid=3, prompt=list(shared), max_new_tokens=5,
                    arrival_s=2.0),
            Request(rid=4, prompt=list(rng.integers(5, 200, 7)),
                    max_new_tokens=1, arrival_s=2.0)]
    reqs += [Request(rid=5 + i, prompt=list(rng.integers(5, 200, 4 + i)),
                     max_new_tokens=6 + i, arrival_s=6.0)
             for i in range(5)]
    return reqs


CANCEL = (9, 6)                    # (before step, rid)


def _pipelined(eng, reqs):
    """`MoebiusEngine.step` on a clock that ticks once a step; checks the
    pipeline's invariants on the way."""
    for r in reqs:
        eng.submit(r)
    i = 0
    while eng.sched.has_work():
        if i + 1 == CANCEL[0]:
            assert eng.cancel(CANCEL[1])
        before = eng._in_flight
        n0 = eng.metrics.dispatches
        eng.step()
        eng._clock.advance(1.0)
        i += 1
        assert i < 500
        if eng._in_flight is not None:
            assert eng.sched.has_work()
        if eng.metrics.dispatches == n0 and before is not None:
            # nothing launched: what was in flight landed in this step
            assert eng._in_flight is None
            assert all(row.req.inflight == 0 for row in before[0].rows)
    return i


def _synchronous(eng, reqs):
    """One dispatch at a time through the same API: plan, launch, fetch
    and commit in series, with admission as the engine's step does it."""
    s, ex = eng.sched, eng.ex
    for r in reqs:
        s.submit(r)
    i = 0
    while s.has_work():
        i += 1
        if i == CANCEL[0]:
            assert s.cancel_request(CANCEL[1]) is not None
        s.admit(eng.now())
        s.start_prefills()
        plan = s.plan_mixed(i, budget=eng.token_budget,
                            chunk=ex.prefill_chunk)
        ex.run_copies(s.drain_copies())
        if plan.rows:
            nxt = ex.run_mixed(plan, i)
            s.commit_mixed(plan, ex.fetch_mixed(nxt), eng.now())
        eng._clock.advance(1.0)
        assert i < 500


@pytest.mark.parametrize("pages_ep", [64, 12])
def test_pipelined_engine_matches_synchronous_driver(tiny_moe, mesh11,
                                                     pages_ep):
    """The pipelined engine gives every request the tokens a driver that
    lands each dispatch before planning the next gives it, and drains to
    a step boundary for a cancel and, in the tight pool, a preemption (the
    ample pool serves the prefix-cache follower from the cache)."""
    reqs0 = _pipeline_trace()
    outs, engs = [], []
    for drive in (_pipelined, _synchronous):
        eng = MoebiusEngine(
            tiny_moe, mesh11,
            CacheConfig(page_size=4, pages_ep=pages_ep, max_pages_per_req=8),
            ecfg=EngineConfig(start_layout="tp", ladder=(4, 8),
                              prefill_chunk=8, temperature=0.0,
                              clock=VirtualClock(),
                              policy=PolicyConfig(t_high=10**9, t_low=-1,
                                                  cooldown_s=10**9)))
        drive(eng, copy.deepcopy(reqs0))
        outs.append(_outputs(eng, reqs0))
        engs.append(eng)
    assert outs[0] == outs[1]
    assert sorted(outs[0]) == list(range(len(reqs0)))
    m = engs[0].metrics
    assert m.overlapped_dispatches > 0 and m.mixed_dispatches > 0
    assert m.pipeline_drains["cancel"] == 1
    if pages_ep < 64:
        assert m.preemptions > 0 and m.pipeline_drains["starved"] > 0
    else:
        assert m.prefix_hits > 0
    assert engs[1].metrics.overlapped_dispatches == 0
    for a in engs[0].alloc:
        a.check()


def test_pipeline_compiles_nothing_after_warmup(tiny_moe, mesh11):
    """Warm-up builds every (rung, chunk width) step once; a window whose
    rung changes between dispatches and whose prefill chunks ride beside
    decode rows, with decode tokens taken from the carried buffer, then
    compiles nothing: the buffer has one shape at every rung."""
    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    eng = MoebiusEngine(
        tiny_moe, mesh11,
        CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=8),
        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8), prefill_chunk=8,
                          temperature=0.0, clock=VirtualClock(),
                          policy=PolicyConfig(t_high=10**9, t_low=-1,
                                              cooldown_s=10**9)))
    eng.warmup()
    shapes = []
    orig = eng.ex.run_mixed

    def run_mixed(plan, step_i):
        shapes.append((plan.B, plan.Sq,
                       any(r.src >= 0 for r in plan.rows)))
        return orig(plan, step_i)
    eng.ex.run_mixed = run_mixed
    n0 = len(compiles)
    for r in _pipeline_trace():
        eng.submit(r)
    while eng.sched.has_work():
        eng.step()
        eng._clock.advance(1.0)
    assert len(compiles) == n0, compiles[n0:]
    carried = {(B, Sq) for B, Sq, fed in shapes if fed}
    assert {B for B, _ in carried} == {4, 8} and {Sq for _, Sq in carried} \
        == {1, 8}
