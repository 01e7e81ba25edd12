"""Refcounted page allocator + prefix cache (DESIGN.md §6).

Property tests for the allocator's conservation invariants (hypothesis),
unit tests for the hash-chain index, and single-device engine tests for
the lifecycle bugfixes: cap-hit truncation, pool-exhaustion preemption,
and cross-switch release to the recorded pool.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # offline fallback (tests/_hypothesis_compat.py)
    from tests._hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.launch.mesh import make_mesh
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import (CacheConfig, PageAllocator, PrefixCache,
                                   full_prompt_hash, token_page_hashes)
from repro.serving.request import Request
from repro.serving.workloads import RolloutSpec, rollout_batch

HYP = dict(deadline=None, max_examples=30)


def _alloc(pages_ep=10, G=2, layout=EP):
    cfg = get_config("internlm2-1.8b").reduced(num_kv_heads=2, num_heads=4)
    cc = CacheConfig(page_size=4, pages_ep=pages_ep)
    return PageAllocator(cc, cfg, G, layout)


# ---------------------------------------------------------------------------
# allocator properties
# ---------------------------------------------------------------------------

@settings(**HYP)
@given(ops=st.lists(st.tuples(st.integers(0, 2), st.integers(0, 1),
                              st.integers(1, 4)),
                    min_size=1, max_size=60))
def test_allocator_interleavings_conserve(ops):
    """Arbitrary alloc/fork/release interleavings: pages are conserved
    (free + held == capacity per pool), a fresh alloc never returns a page
    with refcount > 0, and releases never double-free."""
    al = _alloc()
    held = {0: [], 1: []}            # our model: one entry per reference
    for kind, rank, n in ops:
        if kind == 0:                # alloc
            got = al.try_alloc(rank, n)
            if got is None:
                assert al.free_pages(rank) < n
            else:
                for p in got:
                    # freshly handed-out pages carry exactly one reference
                    assert al.refcount(rank, p) == 1
                    held[rank].append(p)
        elif kind == 1 and held[rank]:   # fork the n-th most recent ref
            p = held[rank][-(1 + (n - 1) % len(held[rank]))]
            before = al.refcount(rank, p)
            al.fork(rank, [p])
            assert al.refcount(rank, p) == before + 1
            held[rank].append(p)
        elif kind == 2 and held[rank]:   # release one reference
            p = held[rank].pop(n % len(held[rank]) - 1)
            al.release(rank, [p])
        al.check()
        for r in (0, 1):
            # ledger matches the model exactly
            assert sorted(al.refs[r].keys()) == sorted(set(held[r]))
            assert sum(al.refs[r].values()) == len(held[r])
            assert al.free_pages(r) + al.held_pages(r) == al.capacity


def test_allocator_double_free_and_bad_fork_raise():
    al = _alloc()
    got = al.alloc(0, 2)
    al.release(0, [got[0]])
    with pytest.raises(ValueError):
        al.release(0, [got[0]])          # double free
    with pytest.raises(ValueError):
        al.fork(0, [got[0]])             # fork of a freed page
    al.fork(0, [got[1]])
    al.release(0, [got[1]])
    al.release(0, [got[1]])              # second ref
    with pytest.raises(ValueError):
        al.release(0, [got[1]])          # third is one too many
    al.check()


def test_fresh_alloc_never_reuses_held_pages():
    al = _alloc(pages_ep=6, G=1)
    a = al.alloc(0, 3)
    al.fork(0, a)                        # refcount 2 on each
    b = al.alloc(0, 2)
    assert not (set(a) & set(b))
    al.release(0, a)                     # still held once
    c = al.try_alloc(0, 3)               # only 0 free left
    assert c is None
    al.release(0, a)
    assert sorted(al.alloc(0, 3)) == sorted(a)


# ---------------------------------------------------------------------------
# hashing + index
# ---------------------------------------------------------------------------

def test_page_hash_chain_prefix_property():
    a = list(range(1, 20))
    b = a[:12] + [999] * 7
    ha, hb = token_page_hashes(a, 4), token_page_hashes(b, 4)
    assert len(ha) == len(a) // 4
    assert ha[:3] == hb[:3]              # identical first 12 tokens
    assert ha[3] != hb[3]                # diverge at page 4
    assert full_prompt_hash(a, 4) != full_prompt_hash(b, 4)
    # length is part of the full digest (no prefix collision)
    assert full_prompt_hash(a, 4) != full_prompt_hash(a[:-1], 4)
    # resuming from the page chain is identical to hashing from scratch
    for toks in (a, b, a[:3], a[:4]):
        assert (full_prompt_hash(toks, 4,
                                 page_hashes=token_page_hashes(toks, 4))
                == full_prompt_hash(toks, 4))


def test_prefix_cache_insert_match_evict():
    al = _alloc(pages_ep=10, G=1)
    pc = PrefixCache(al)
    toks = list(range(1, 13))            # 3 full pages @ page_size 4
    hs = token_page_hashes(toks, 4)
    pages = al.alloc(0, 3)
    pc.insert_chain(0, hs, pages)
    assert all(al.refcount(0, p) == 2 for p in pages)
    assert pc.match(0, hs) == pages
    assert pc.match(0, token_page_hashes([7] * 12, 4)) == []
    fh = full_prompt_hash(toks + [50, 51], 4)
    tail = al.alloc(0, 1)
    pc.insert_full(0, fh, pages + tail, 14)
    assert pc.lookup_full(0, fh) == (tuple(pages + tail), 14)
    # while a live request still shares every cached page, eviction can
    # free nothing — it must refuse WITHOUT wiping the index
    assert not pc.evict(0, al.capacity)
    assert pc.match(0, hs) == pages and pc.lookup_full(0, fh) is not None
    # requests release; cache keeps everything resident
    al.release(0, pages)
    al.release(0, tail)
    al.check()
    assert al.held_pages(0) == 4
    # eviction frees cache-only pages until the demand fits
    assert pc.evict(0, al.capacity)
    al.check()
    assert al.free_pages(0) == al.capacity


# ---------------------------------------------------------------------------
# engine-level lifecycle regressions (single device)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _engine(cfg, mesh, cc, **kw):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    return MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=TP, ladder=(4,), prefill_chunk=8, temperature=0.0,
        policy=pol, seed=0, **kw))


def _drive(eng, max_iter=2000):
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        eng.step()
        i += 1
        assert i < max_iter, "engine made no progress (livelock)"
    return eng


def test_engine_prefix_hits_and_byte_identity(tiny_moe, mesh11):
    """Shared prompts: cache-on run must produce byte-identical outputs to
    cache-off while computing strictly fewer prefill tokens."""
    rng = np.random.default_rng(0)
    shared = list(rng.integers(5, 200, 9))
    other = list(rng.integers(5, 200, 5))

    def mk():
        return ([Request(rid=i, prompt=list(shared), max_new_tokens=6)
                 for i in range(3)]
                + [Request(rid=3, prompt=list(other), max_new_tokens=5)])

    cc = CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=16)
    eng_off = _engine(tiny_moe, mesh11, cc, prefix_cache=False)
    for r in mk():
        eng_off.submit(r)
    _drive(eng_off)
    ref = {r.rid: r.output for r in eng_off.finished}

    on = _engine(tiny_moe, mesh11, cc, prefix_cache=True)
    for r in mk():
        on.submit(r)
    _drive(on)
    assert {r.rid: r.output for r in on.finished} == ref
    assert on.metrics.prefix_hits == 2
    assert on.metrics.prefill_tokens < eng_off.metrics.prefill_tokens
    assert on.metrics.cow_forks >= 2     # shared tails forked before append
    for al in on.alloc:
        al.check()
    on.clear_prefix_cache()
    assert on.alloc[0].total_free() == 63


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_cap_hit_finishes_with_truncation(tiny_moe, mesh11, decode_steps):
    """A request at max_pages_per_req must finish (truncated), not spin
    forever holding its slot and pages."""
    cc = CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=2)
    eng = _engine(tiny_moe, mesh11, cc, decode_steps=decode_steps)
    r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3)
    eng.submit(r)
    eng.step()                       # admit + start prefill
    r.max_new_tokens = 50            # blow past the cap (bypasses _admit)
    _drive(eng, max_iter=300)
    assert r.truncated
    assert 0 < len(r.output) < 50
    assert eng.metrics.truncations == 1
    for al in eng.alloc:
        al.check()
    eng.clear_prefix_cache()
    assert eng.alloc[0].total_free() == cc.pages_tp(tiny_moe, 1) - 1


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_pool_exhaustion_preempts_youngest(tiny_moe, mesh11, decode_steps):
    """A dry pool preempts the youngest request (pages released, requeued)
    instead of livelocking; every request's generated text matches the
    ample-pool reference exactly."""
    prompts = [list(p) for p in
               np.random.default_rng(5).integers(5, 200, (2, 5))]

    def run(cc, n):
        eng = _engine(tiny_moe, mesh11, cc, decode_steps=n)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=10))
        _drive(eng)
        # preempted requests carry earlier output teacher-forced into the
        # prompt: compare the full generated text
        return eng, {r.rid: list(r.prompt[5:]) + list(r.output)
                     for r in eng.finished}

    _, ref = run(CacheConfig(page_size=4, pages_ep=64,
                             max_pages_per_req=16), 1)
    tight = CacheConfig(page_size=4, pages_ep=7, max_pages_per_req=6)
    eng, got = run(tight, decode_steps)
    assert got == ref
    assert not any(r.truncated for r in eng.finished)
    if decode_steps == 1:
        assert eng.metrics.preemptions >= 1
    for al in eng.alloc:
        al.check()


def test_starving_runner_not_truncated_while_prefill_holds_pages(
        tiny_moe, mesh11):
    """Review regression: pool holders include PREFILLING requests — a
    runner starved by a big in-flight prefill must preempt it (or wait),
    never conclude it is the pool's sole holder and self-truncate."""
    def run(pages_ep):
        cc = CacheConfig(page_size=4, pages_ep=pages_ep,
                         max_pages_per_req=16)
        eng = _engine(tiny_moe, mesh11, cc)
        rng = np.random.default_rng(4)
        short = list(rng.integers(5, 200, 4))
        long_ = list(rng.integers(5, 200, 40))
        eng.submit(Request(rid=0, prompt=short, max_new_tokens=8))
        eng.submit(Request(rid=1, prompt=long_, max_new_tokens=2,
                           arrival_s=0.0))
        _drive(eng)
        for al in eng.alloc:
            al.check()
        return eng

    ample = run(64)
    ref = {r.rid: r.output for r in ample.finished}
    tight = run(14)     # rid0 starves while rid1 is still mid-prefill
    assert not any(r.truncated for r in tight.finished)
    got = {}
    for r in tight.finished:
        base = 4 if r.rid == 0 else 40
        got[r.rid] = list(r.prompt[base:]) + list(r.output)
    assert got == ref


def test_hit_survives_eviction_pressure(tiny_moe, mesh11):
    """Review regression: a cache hit under pool pressure pins its matched
    pages BEFORE evicting — eviction may drop the very entry just matched,
    and an unpinned cache-only page would return to the free list out from
    under the fork (ValueError crash) or get re-allocated as the CoW
    destination."""
    cc = CacheConfig(page_size=4, pages_ep=16, max_pages_per_req=8)
    eng = _engine(tiny_moe, mesh11, cc, prefix_cache=True)
    prompt = list(np.random.default_rng(9).integers(5, 200, 9))
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=4))
    _drive(eng)
    ref = eng.finished[0].output
    # squat on every free page: the next hit can only proceed by evicting,
    # and the only evictable entries are the ones it just matched
    al = eng.alloc[0]
    squat = al.alloc(0, al.free_pages(0))
    eng.submit(Request(rid=1, prompt=list(prompt), max_new_tokens=4))
    eng.step()                       # hit under pressure must not crash
    al.release(0, squat)
    _drive(eng)
    assert eng.finished[-1].output == ref
    assert not eng.finished[-1].truncated
    for a in eng.alloc:
        a.check()


def test_fail_rank_under_fused_decode(tiny_moe, mesh11):
    """Review regression: rank-failure recovery must vacate fused-decode
    device slots (drain + shared requeue path) — a stale slot budget would
    keep writing KV through the old block table into released pages."""
    from repro.distributed.elastic import fail_rank

    def run(fail_at):
        cc = CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=16)
        eng = _engine(tiny_moe, mesh11, cc, decode_steps=4)
        rng = np.random.default_rng(2)
        for i in range(3):
            eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                               max_new_tokens=8))
        i = 0
        while eng.pending or eng.waiting or eng.prefilling or eng.running:
            if fail_at is not None and i == fail_at:
                fail_rank(eng, data_group=0, rank=0)
            eng.step()
            i += 1
            assert i < 500
        for al in eng.alloc:
            al.check()
        return {r.rid: list(r.prompt[6:]) + list(r.output)
                for r in eng.finished}

    base = run(None)
    assert run(6) == base


def test_finish_after_view_switch_releases_recorded_pool(tiny_moe, mesh11):
    """Satellite regression: a request that prefilled under one KV view and
    finishes after a view-changing switch must release to the pool its
    pages actually live in (recorded at alloc / switch-apply time) — the
    old code recomputed the pool from the ACTIVE layout and leaked."""
    cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
    eng = _engine(tiny_moe, mesh11, cc, prefix_cache=True)
    rng = np.random.default_rng(1)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                           max_new_tokens=8))
    for _ in range(4):
        eng.step()                   # prefill + a little decode under TP
    assert eng.running
    eng.execute_switch(EP)           # tp view -> ep view mid-flight
    for _ in range(2):
        eng.step()
    eng.execute_switch(TP)           # and back, still mid-flight
    _drive(eng)
    assert len(eng.finished) == 3
    for al in eng.alloc:
        al.check()                   # no leak, no double-free
    eng.clear_prefix_cache()
    assert eng.alloc[0].total_free() == cc.pages_tp(tiny_moe, 1) - 1


def test_rollout_prefix_cache_cuts_prefill_tokens(tiny_moe, mesh11):
    """An RL rollout group draws 4 samples of each prompt: with the cache
    on, the shared prompts fork cached pages, so the engine computes at
    least 30% fewer prefill tokens, with outputs byte-identical to the
    cache-off run and every page back in its pool."""
    spec = RolloutSpec(num_prompts=16, samples_per_prompt=4,
                       prompt_median=40, prompt_max=64, output_median=6,
                       output_p99=16, output_cap=16, token_range=(5, 200))
    cc = CacheConfig(page_size=8, pages_ep=256, max_pages_per_req=16)
    runs = {}
    for on in (False, True):
        eng = _engine(tiny_moe, mesh11, cc, prefix_cache=on)
        for r in rollout_batch(spec, seed=0):
            eng.submit(r)
        _drive(eng)
        assert len(eng.finished) == 16
        for al in eng.alloc:
            al.check()
        runs[on] = eng
    off, on = runs[False], runs[True]
    assert ({r.rid: r.output for r in on.finished}
            == {r.rid: r.output for r in off.finished})
    assert on.metrics.prefix_hits > 0
    assert on.metrics.prefill_tokens <= 0.7 * off.metrics.prefill_tokens
    on.clear_prefix_cache()
    assert on.alloc[0].total_free() == on.alloc[0].capacity
