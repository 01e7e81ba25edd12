"""AsyncEngine streaming frontend (DESIGN.md §7), single device.

Streaming is an observation layer over the same engine execution: the
tokens a `TokenStream` yields must equal the batch-mode outputs
byte-for-byte — across a live layout switch included — and the virtual
clock + idle fast-forward make trace replay deterministic and independent
of quiet-period length.
"""
import copy

import numpy as np
import pytest

from repro.core.policy import PolicyConfig
from repro.launch.mesh import make_mesh
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.frontend import AsyncEngine, VirtualClock
from repro.serving.kvcache import CacheConfig
from repro.serving.qos import slo_targets
from repro.serving.request import Request
from repro.serving.workloads import QosMixSpec, qos_mixed_trace, replay


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _mk(cfg, mesh, **kw):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    return MoebiusEngine(cfg, mesh,
                         CacheConfig(page_size=4, pages_ep=64,
                                     max_pages_per_req=16),
                         ecfg=EngineConfig(start_layout="tp", ladder=(4, 8),
                                           prefill_chunk=8, temperature=0.0,
                                           policy=pol, **kw))


def _reqs(n=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                    max_new_tokens=int(rng.integers(4, 10)), arrival_s=0.0)
            for i in range(n)]


def test_stream_matches_batch_across_live_switch(tiny_moe, mesh11):
    """Streamed tokens == batch-mode outputs byte-for-byte, with a live
    tp->ep switch in both runs (greedy outputs are switch-invariant, so
    the reference is well-defined regardless of switch timing)."""
    # batch reference, switched once mid-run
    eng = _mk(tiny_moe, mesh11)
    for r in _reqs():
        eng.submit(r)
    switched, i = False, 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if not switched and eng.running:
            eng.execute_switch("ep")
            switched = True
        eng.step()
        i += 1
        assert i < 1000
    assert switched
    ref = {r.rid: list(r.output) for r in eng.finished}

    # streamed run under a virtual clock, switch after the first token
    eng2 = _mk(tiny_moe, mesh11, clock=VirtualClock())
    fe = AsyncEngine(eng2, step_dt=0.01)
    streams = [fe.submit(r) for r in _reqs()]
    got = {s.rid: [] for s in streams}
    got[streams[0].rid].append(next(streams[0]))   # pump until first token
    eng2.execute_switch("ep")
    # interleaved pulls: one token from each stream round-robin, then drain
    alive = list(streams)
    while alive:
        nxt = []
        for s in alive:
            try:
                got[s.rid].append(next(s))
                nxt.append(s)
            except StopIteration:
                pass
        alive = nxt
    assert got == ref
    assert len(eng2.switch_records) == 1
    # per-request latency percentiles recorded (virtual clock: exact)
    summ = fe.run_until_complete()
    for k in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s"):
        assert np.isfinite(summ[k]), k


def test_generate_streams_and_records_latency(tiny_dense, mesh11):
    eng = _mk(tiny_dense, mesh11, clock=VirtualClock())
    fe = AsyncEngine(eng, step_dt=0.5)
    s1 = fe.generate(list(range(1, 8)), max_new_tokens=5)
    s2 = fe.generate(list(range(3, 9)), max_new_tokens=7)
    toks1 = s1.tokens()
    toks2 = s2.tokens()
    assert len(toks1) == 5 and len(toks2) == 7
    summ = fe.run_until_complete()
    assert summ["n"] == 2
    # TTFT/TPOT are deterministic step counts under the virtual clock
    assert summ["ttft_p50_s"] > 0 and summ["tpot_p50_s"] > 0


def test_idle_skip_jumps_quiet_period_virtual_clock(tiny_dense, mesh11):
    """A pending request 1000 virtual seconds out costs ONE iteration, not
    a thousand: the idle fast-forward advances the injected clock straight
    to the next arrival."""
    clk = VirtualClock()
    eng = _mk(tiny_dense, mesh11, clock=clk)
    fe = AsyncEngine(eng, step_dt=0.01)
    st = fe.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=3,
                           arrival_s=1000.0))
    toks = st.tokens()
    assert len(toks) == 3
    assert clk.t >= 1000.0
    # the whole run took a handful of iterations, not 100k empty spins
    assert eng._step_i < 50
    (rid, arr, first, fin, n), = eng.metrics.records
    assert first >= 1000.0 and fin >= first


def test_idle_skip_wall_clock(tiny_dense, mesh11):
    """Same fast-forward on the default wall clock: a far-future arrival
    must not burn empty step() iterations (or wall time) waiting."""
    eng = _mk(tiny_dense, mesh11)
    eng.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=3,
                       arrival_s=3600.0))
    eng.run(max_steps=100)
    assert len(eng.finished) == 1
    assert eng._step_i < 50
    assert eng.metrics.records[0][2] >= 3600.0   # first token after arrival


def test_stall_guard_raises_on_unservable_request(tiny_dense, mesh11):
    """A prompt that can never acquire its prefill pages must raise from
    the event loop instead of spinning forever."""
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(tiny_dense, mesh11,
                        CacheConfig(page_size=4, pages_ep=8,
                                    max_pages_per_req=16),
                        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8),
                                          prefill_chunk=8, temperature=0.0,
                                          policy=pol,
                                          clock=VirtualClock()))
    fe = AsyncEngine(eng, step_dt=0.01, stall_limit=50)
    st = fe.generate(list(range(1, 41)), max_new_tokens=4)  # 11 pages > 7
    with pytest.raises(RuntimeError, match="no scheduling progress"):
        st.tokens()


def test_stream_survives_preemption(tiny_dense, mesh11):
    """A teacher-force-requeued request folds generated tokens into its
    prompt; the stream must keep yielding the same byte sequence."""
    eng = _mk(tiny_dense, mesh11, clock=VirtualClock())
    fe = AsyncEngine(eng, step_dt=0.01)
    st = fe.generate(list(range(1, 6)), max_new_tokens=6)
    first_two = [next(st), next(st)]
    r = st.req
    # force a mid-stream requeue (what pool-exhaustion preemption does),
    # its in-flight token landed first as rank-failure recovery lands it
    eng._drain_decode()
    eng.sched.requeue_for_reprefill(r)
    rest = st.tokens()
    assert len(first_two) + len(rest) == 6
    # the folded tokens are byte-stable through the requeue
    assert r.prompt[5:7] == first_two


def test_qos_beats_class_blind_on_mixed_tenant_trace(tiny_moe, mesh11):
    """Multi-tenant QoS (DESIGN.md §11) on a VirtualClock, so every latency
    is an iteration count: a floor of prompt-heavy batch requests
    oversubscribes prefill while interactive bursts arrive. With QoS the
    interactive class attains its SLO strictly more often than under
    class-blind FIFO, and the batch tenant keeps at least 0.8x its
    class-blind throughput."""
    spec = QosMixSpec(duration_s=12.0, batch_interval_s=0.25,
                      batch_prompt=192, batch_output=4,
                      burst_windows=((1.0, 4.0), (7.0, 10.0)),
                      burst_interval_s=0.25, inter_prompt=16,
                      inter_output=12)
    reqs = qos_mixed_trace(spec, seed=0)
    res = {}
    for qos in (False, True):
        eng = MoebiusEngine(
            tiny_moe, mesh11,
            CacheConfig(page_size=16, pages_ep=256, max_pages_per_req=48),
            ecfg=EngineConfig(
                start_layout="tp", ladder=(4, 8, 16), prefill_chunk=64,
                temperature=0.0, clock=VirtualClock(), qos=qos,
                policy=PolicyConfig(t_high=10**9, t_low=-1,
                                    cooldown_s=10**9)))
        fe = AsyncEngine(eng, step_dt=0.1)
        streams = replay(fe, copy.deepcopy(reqs))
        fe.run_until_complete()
        assert all(s.finished for s in streams.values())
        m = eng.metrics
        m.slo_targets = slo_targets()   # class-blind installs none
        # batch output tokens per virtual second of the batch tenant's span
        recs = m._recs("batch")
        span = max(fin for *_, fin, _ in recs)
        res[qos] = (m.attainment("interactive"),
                    sum(n for *_, n in recs) / span)
    (att_blind, tps_blind), (att_qos, tps_qos) = res[False], res[True]
    assert att_qos > att_blind
    assert tps_qos >= 0.8 * tps_blind
