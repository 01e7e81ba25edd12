"""Device-free Scheduler unit tests (DESIGN.md §7).

The Scheduler is pure host logic by contract: these tests drive it with a
fake layout spec + the pure `paging.PagePoolAllocator`, no mesh, no
devices, no jax — and the first test enforces the no-jax import contract
in a subprocess.
"""
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

import pytest

from repro.serving.metrics import ServeMetrics
from repro.serving.paging import PagePoolAllocator, PrefixCache
from repro.serving.request import Request, State
from repro.serving.scheduler import (Admit, CopyPages, Grow, Preempt,
                                     Scheduler, StartPrefill, Truncate)

REPO = Path(__file__).resolve().parent.parent


@dataclass
class FakeSpec:
    """Duck-typed stand-in for a LayoutSpec: only the pure attributes the
    Scheduler reads."""
    kv_per_rank: bool = False
    slots_sharded: bool = False

    def decode_ladder(self, ladder, G):
        return tuple(ladder)


@dataclass
class CC:
    page_size: int = 4
    max_pages_per_req: int = 8


def make_sched(Dd=1, G=1, npages=17, per_rank=False, prefix=False,
               ladder=(4, 8), cc=None, clock=None, qos=None):
    cc = cc or CC()
    spec = FakeSpec(kv_per_rank=per_rank, slots_sharded=per_rank)
    npools = G if per_rank else 1
    alloc = [PagePoolAllocator(npools, npages, per_rank=per_rank)
             for _ in range(Dd)]
    pre = [PrefixCache(a) for a in alloc] if prefix else None
    t = {"v": 0.0}
    return Scheduler(cc, Dd, G, ladder, alloc=alloc, prefix=pre, spec=spec,
                     clock=clock or (lambda: t["v"]),
                     metrics=ServeMetrics(), qos=qos)


def req(rid, plen=5, out=8, arrival=0.0, **kw):
    return Request(rid=rid, prompt=list(range(1, plen + 1)),
                   max_new_tokens=out, arrival_s=arrival, **kw)


def test_scheduler_imports_no_jax():
    """The module contract: `import repro.serving.scheduler` must not pull
    in jax, directly or transitively."""
    code = ("import sys; import repro.serving.scheduler; "
            "import repro.serving.paging; import repro.serving.request; "
            "import repro.serving.qos; import repro.serving.faults; "
            "assert 'jax' not in sys.modules, 'scheduler imported jax'; "
            "print('ok')")
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env)
    assert out.returncode == 0, out.stderr
    assert "ok" in out.stdout


# ---------------------------------------------------------------------------
# admission ordering under load skew
# ---------------------------------------------------------------------------

def test_admission_balances_on_total_group_load():
    """A burst admitted in ONE iteration must spread across data groups —
    the balance counts running + prefilling + waiting, so the whole burst
    doesn't pile onto whichever group momentarily runs the least."""
    s = make_sched(Dd=2)
    # group 0 already owes 3 requests (they are waiting, not yet running)
    for i in range(3):
        r = req(i)
        r.data_group = 0
        r.state = State.WAITING
        s.waiting.append(r)
    for i in range(3, 7):
        s.submit(req(i))
    decs = s.admit(t=0.0)
    assert [type(d) for d in decs] == [Admit] * 4
    groups = [d.data_group for d in decs]
    # all four go to the emptier group 1 until it catches up, then alternate
    assert groups.count(1) == 3 and groups.count(0) == 1, groups
    loads = [0, 0]
    for r in s.waiting:
        loads[r.data_group] += 1
    assert loads == [4, 3]


def test_admission_respects_arrival_clock():
    s = make_sched()
    s.submit(req(0, arrival=0.0))
    s.submit(req(1, arrival=5.0))
    assert [d.req.rid for d in s.admit(t=1.0)] == [0]
    assert s.next_arrival() == 5.0
    assert [d.req.rid for d in s.admit(t=5.0)] == [1]
    assert s.next_arrival() is None


def test_admission_clamps_to_page_cap():
    """max_new_tokens gets clamped so prompt + output + 1 fits the per-
    request block table."""
    cc = CC(page_size=4, max_pages_per_req=4)   # 16-token block table
    s = make_sched(cc=cc)
    s.submit(req(0, plen=10, out=1000))
    s.admit(t=0.0)
    r = s.waiting[0]
    assert r.max_new_tokens == 16 - 10 - 1


# ---------------------------------------------------------------------------
# prefill start: watermark + page acquisition
# ---------------------------------------------------------------------------

def test_prefill_start_watermark_reserves_for_growing_runners():
    """Starting a prefill must leave one free page per growth-capable
    runner; otherwise prefill and a starved decoder thrash forever."""
    s = make_sched(npages=9)          # 8 usable pages
    # a runner holding 1 page that still needs to grow (reserve = 1)
    runner = req(100, plen=3, out=20)
    runner.pages = s.alloc[0].alloc(0, 1)
    runner.state = State.RUNNING
    runner.output = [7]
    s.running[runner.rid] = runner
    # prefill wants ceil((25+1)/4) but capped by max_pages_per_req=8 ->
    # 7 fresh pages; free = 7, reserve = 1 -> refused
    s.submit(req(0, plen=25, out=8))
    s.admit(t=0.0)
    assert s.start_prefills() == []
    assert len(s.waiting) == 1
    # the runner finishing releases its page; now 8 free >= 7 + 0 reserve
    s.finish_request(runner)
    decs = s.start_prefills()
    assert len(decs) == 1 and isinstance(decs[0], StartPrefill)
    assert len(decs[0].pages) == 7
    assert s.waiting == [] and len(s.prefilling) == 1
    s.alloc[0].check()


def test_prefill_start_per_rank_pool_choice_prefers_least_loaded():
    """Per-rank KV views place a prefill on the least-loaded rank that has
    pages (no prefix cache -> pure load order)."""
    s = make_sched(G=4, per_rank=True, npages=9, ladder=(8, 16))
    # rank 0 busy with 2 running requests, rank 1 with 1
    for i, g in enumerate((0, 0, 1)):
        q = req(50 + i)
        q.owner_rank = q.pool_rank = g
        q.state = State.RUNNING
        q.pages = s.alloc[0].alloc(g, 1)
        s.running[q.rid] = q
    s.submit(req(0))
    s.admit(t=0.0)
    dec = s.start_prefills()[0]
    assert dec.pool == 2                      # ranks 2/3 empty; lowest wins
    assert dec.req.owner_rank == 2


# ---------------------------------------------------------------------------
# preemption victim choice
# ---------------------------------------------------------------------------

def _running(s, rid, pool=0, npages=1, arrival=0.0, out_len=1):
    q = req(rid, arrival=arrival)
    q.owner_rank = q.pool_rank = pool
    q.state = State.RUNNING
    q.pages = s.alloc[0].alloc(pool, npages)
    q.output = list(range(out_len))
    s.running[q.rid] = q
    return q


def test_preemption_picks_youngest_holder():
    """Pool-exhaustion starvation preempts the YOUNGEST page-holder of the
    starved pool (latest arrival, ties by rid) — teacher-force-requeued,
    pages released, prompt extended by its generated tokens."""
    s = make_sched(npages=7)                  # 6 usable pages
    old = _running(s, 1, npages=2, arrival=1.0, out_len=2)
    mid = _running(s, 2, npages=2, arrival=2.0, out_len=2)
    young = _running(s, 3, npages=2, arrival=3.0, out_len=2)
    free_before = s.alloc[0].free_pages(0)
    decs = s.handle_starvation([old], exclude=[])
    assert [type(d) for d in decs] == [Preempt]
    assert decs[0].req is young
    assert young.rid not in s.running and young in s.waiting
    assert young.state is State.WAITING and young.pages == []
    # teacher-forced: the 2 generated tokens folded into the prompt
    assert young.prompt[-2:] == [0, 1] and young.output == []
    assert young.max_new_tokens == 6          # 8 - 2 already generated
    assert s.alloc[0].free_pages(0) == free_before + 2
    assert old.rid in s.running and mid.rid in s.running
    s.alloc[0].check()


def test_preemption_never_picks_excluded_or_inflight():
    s = make_sched(npages=7)
    a = _running(s, 1, npages=2, arrival=1.0)
    b = _running(s, 2, npages=2, arrival=2.0)
    c = _running(s, 3, npages=2, arrival=3.0)
    c.inflight = 2                            # mid-flight: never requeued
    decs = s.handle_starvation([a], exclude=[b])
    # youngest settled-and-unscheduled is a itself? no — a is the starved
    # one but also eligible; victim = max eligible arrival = a(1.0) only
    assert [d.req.rid for d in decs] == [1]
    s.alloc[0].check()


def test_sole_holder_truncates_instead_of_preempting():
    """A request starving ALONE in its pool can never be saved by waiting:
    it finishes truncated (with its pages released)."""
    s = make_sched(npages=3)                  # 2 usable pages
    solo = _running(s, 1, npages=2, out_len=3)
    decs = s.handle_starvation([solo], exclude=[])
    assert [type(d) for d in decs] == [Truncate]
    assert solo.truncated and solo.state is State.FINISHED
    assert solo in s.finished and solo.pages == []
    assert s.alloc[0].free_pages(0) == 2
    assert s.metrics.truncations == 1
    s.alloc[0].check()


# ---------------------------------------------------------------------------
# page-budget accounting (ensure_pages / CoW / conservation)
# ---------------------------------------------------------------------------

def test_ensure_pages_grows_on_page_boundary():
    s = make_sched()
    q = _running(s, 1, npages=1)              # page holds 4 tokens
    q.prefill_pos = 3
    q.output = [5]                            # kv_len = 4: next write -> page 2
    assert s.ensure_pages(q) is True
    assert len(q.pages) == 2
    # the growth is recorded as a typed Grow decision
    grows = [d for d in s.last_decisions if isinstance(d, Grow)]
    assert grows and grows[-1].req is q and grows[-1].pages == (q.pages[1],)
    q.output = [5, 6]                         # still fits page 2
    held = s.alloc[0].total_held()
    assert s.ensure_pages(q) is True and s.alloc[0].total_held() == held


def test_plan_decode_records_grow_decisions():
    s = make_sched()
    q = _running(s, 1, npages=1)
    q.prefill_pos = 3
    q.output = [5]                            # next decode write needs page 2
    plan = s.plan_mixed(0, budget=8, chunk=0)      # decode rows only
    assert [row.req for row in plan.rows] == [q] and plan.Sq == 1
    assert [d for d in s.last_decisions if isinstance(d, Grow)]
    # next pass clears the log; a no-growth step records nothing
    s.plan_mixed(1, budget=8, chunk=0)
    assert not s.last_decisions


def test_ensure_pages_cap_and_dry():
    cc = CC(page_size=4, max_pages_per_req=2)
    s = make_sched(cc=cc, npages=17)
    q = _running(s, 1, npages=2)
    q.prefill_pos = 6
    q.output = [1, 2]                         # kv_len 8 = cap; next write over
    assert s.ensure_pages(q) == "cap"
    s2 = make_sched(npages=3)                 # 2 usable pages
    w = _running(s2, 1, npages=2)
    w.prefill_pos = 7
    w.output = [1]                            # kv_len 8 -> needs page 3; dry
    assert s2.ensure_pages(w) == "dry"


def test_cow_emits_copy_decision_for_shared_page():
    """Appending into a page the prefix cache (or a sibling) still holds
    must emit a CopyPages decision and swap the writer onto the copy."""
    s = make_sched(prefix=True)
    q = _running(s, 1, npages=1)
    q.prefill_pos = 2
    q.output = [9]                            # writing inside page 0 of req
    shared = q.pages[0]
    s.alloc[0].fork(0, [shared])              # someone else holds it too
    assert s.cow_if_shared(q) is True
    copies = s.drain_copies()
    assert len(copies) == 1 and isinstance(copies[0], CopyPages)
    (src, dst), = copies[0].pairs
    assert src == shared and q.pages[0] == dst != shared
    assert s.alloc[0].refcount(0, shared) == 1   # our ref moved to the copy
    assert s.metrics.cow_forks == 1
    s.alloc[0].release(0, [shared])
    s.alloc[0].check()


def test_finish_releases_to_recorded_pool():
    s = make_sched(G=2, per_rank=True, npages=5)
    q = _running(s, 1, pool=1, npages=2)
    s.finish_request(q)
    assert s.alloc[0].free_pages(1) == 4 and q.pages == []
    assert s.metrics.records and s.metrics.records[0][0] == 1
    s.alloc[0].check()


# ---------------------------------------------------------------------------
# token-budgeted mixed-batch planning (plan_mixed / commit_mixed)
# ---------------------------------------------------------------------------

def _decoding(s, rid, pool=0, arrival=0.0):
    """A runner past prefill: prompt in KV, one generated token."""
    q = _running(s, rid, pool=pool, npages=2, arrival=arrival, out_len=1)
    q.prefill_pos = q.prompt_len
    q.max_new_tokens = 64
    return q


def test_plan_mixed_decode_first_then_prefill_remainder():
    """Every eligible decode token ships first; the prefill chunk is
    clamped to what the budget still holds."""
    s = make_sched(npages=33)
    runners = [_decoding(s, 10 + i) for i in range(3)]
    s.submit(req(0, plen=20, out=4))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 1
    plan = s.plan_mixed(0, budget=8, chunk=16)
    dec = [r for r in plan.rows if r.kind == "decode"]
    pre = [r for r in plan.rows if r.kind == "prefill"]
    assert plan.decode_tokens == 3 and len(dec) == 3
    assert all(r.n_tokens == 1 and r.start_pos == r.req.kv_len - 1
               for r in dec)
    # remainder = 8 - 3 = 5: the 20-token prompt gets a 5-token chunk
    assert plan.prefill_tokens == 5 and len(pre) == 1
    assert pre[0].start_pos == 0 and pre[0].n_tokens == 5
    assert plan.Sq == 16 and plan.B == 4
    # prefill takes the slot after the group's decode rows; no collisions
    assert len({r.row for r in plan.rows}) == len(plan.rows)
    # commit: decode rows append, the prefill row advances its cursor
    s.commit_mixed(plan, [[7] * plan.B], t=0.0)
    assert all(q.output[-1] == 7 for q in runners)
    assert s.prefilling[0].prefill_pos == 5


def test_plan_mixed_pure_decode_keeps_decode_step_shape():
    """No prefill rows -> Sq == 1: pure-decode iterations reuse the exact
    compiled decode executable, not a widened chunk."""
    s = make_sched(npages=33)
    _decoding(s, 1)
    plan = s.plan_mixed(0, budget=8, chunk=16)
    assert plan.Sq == 1 and plan.prefill_tokens == 0
    assert [r.kind for r in plan.rows] == ["decode"]


@pytest.mark.parametrize("qos", [False, True])
def test_plan_mixed_chunk_zero_plans_decode_rows_only(qos):
    """chunk 0 (the decode steps between a switch's chunks): prefill waits,
    even past the min-grant, and the decode rows, rung and slots are the
    ones a scheduler with no prefill waiting plans."""
    plans = []
    for prefill in (True, False):
        s = make_sched(npages=65, qos=_qos() if qos else None)
        for i in range(5):
            _decoding(s, 10 + i)
        if prefill:
            s.submit(req(0, plen=20, out=4))
            s.admit(t=0.0)
            assert len(s.start_prefills()) == 1
        plan = s.plan_mixed(3, budget=4, chunk=0 if prefill else 16)
        plans.append((plan.B, plan.Sq, plan.prefill_tokens,
                      [(r.req.rid, r.kind, r.row, r.start_pos, r.n_tokens)
                       for r in plan.rows]))
    assert plans[0] == plans[1]
    assert plans[0][:3] == (8, 1, 0)


def test_plan_mixed_prefill_fifo_and_chunk_clamp():
    """Remainder packs prefilling FIFO: head gets a full chunk, the next
    gets what's left."""
    s = make_sched(npages=65, ladder=(8, 16))
    for i in range(2):
        _decoding(s, 10 + i)
    s.submit(req(0, plen=20, out=4))
    s.submit(req(1, plen=20, out=4))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 2
    plan = s.plan_mixed(0, budget=30, chunk=16)
    pre = [r for r in plan.rows if r.kind == "prefill"]
    assert [(r.req.rid, r.n_tokens) for r in pre] == [(0, 16), (1, 12)]
    assert plan.decode_tokens + plan.prefill_tokens <= 30
    assert len({r.row for r in plan.rows}) == len(plan.rows)


def test_plan_mixed_min_grant_defeats_decode_saturation():
    """A decode set that alone fills the budget must not starve prefill:
    the head-of-line prefill gets a 1-token grant every iteration, so a
    sustained storm still drains — and the decoders never lose a token."""
    s = make_sched(npages=65, ladder=(4, 8))
    runners = [_decoding(s, 10 + i) for i in range(4)]
    s.submit(req(0, plen=20, out=4))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 1
    storm = s.prefilling[0]
    for i in range(20):
        plan = s.plan_mixed(i, budget=4, chunk=16)   # budget == n_dec
        assert plan.decode_tokens == 4               # never displaced
        assert plan.prefill_tokens == 1              # min-grant
        s.commit_mixed(plan, [[5] * plan.B], t=float(i))
    # 20 one-token grants completed the 20-token prompt
    assert storm.rid in s.running and not s.prefilling
    assert storm.prefill_pos == 20 and storm.output == [5]
    assert all(len(q.output) == 21 for q in runners)


def test_plan_mixed_sharded_rows_land_in_owner_rank_range():
    """Sharded slots: prefill rows take the slot after their owner rank's
    decode rows (slot = owner_rank * bs_loc + local), never colliding."""
    s = make_sched(G=2, per_rank=True, npages=17, ladder=(4, 8))
    _decoding(s, 1, pool=0)
    _decoding(s, 2, pool=0)
    _decoding(s, 3, pool=1)
    s.submit(req(0, plen=6, out=4))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 1
    r0 = s.prefilling[0]
    assert r0.owner_rank == 1                        # least-loaded rank
    plan = s.plan_mixed(0, budget=10, chunk=8)
    assert plan.B == 4 and plan.decode_tokens == 3
    pre = [r for r in plan.rows if r.kind == "prefill"]
    bs_loc = plan.B // 2
    assert pre[0].row == 1 * bs_loc + 1              # after rank 1's decoder
    assert len({r.row for r in plan.rows}) == len(plan.rows)
    for a in s.alloc:
        a.check()


def test_queue_snapshot_counts_inflight_tokens():
    s = make_sched()
    q = _running(s, 1, npages=1)
    q.prefill_pos = 3
    q.output = [5]
    q.inflight = 2
    s.submit(req(7, arrival=99.0))
    snap = s.snapshot()
    assert snap.in_flight == 1 and snap.pending == 1
    assert snap.live_tokens == q.kv_len + 2 + 1


# ---------------------------------------------------------------------------
# multi-tenant QoS (DESIGN.md §11): class-aware victim / admission / shares
# ---------------------------------------------------------------------------

def _qos():
    from repro.serving.qos import QosPolicy
    return QosPolicy()


def test_qos_victim_evicts_batch_before_interactive():
    """Pool-exhaustion victim choice under QoS: the LIGHTEST class loses
    first (batch before interactive) even when the interactive request is
    the youngest; youngest-first within the class, as ever."""
    s = make_sched(npages=9, qos=_qos())
    b_old = _running(s, 1, npages=2, arrival=1.0, out_len=2)
    b_young = _running(s, 2, npages=2, arrival=2.0, out_len=2)
    inter = _running(s, 3, npages=2, arrival=3.0, out_len=2)
    b_old.slo_class = b_young.slo_class = "batch"
    inter.slo_class = "interactive"
    decs = s.handle_starvation([b_old], exclude=[])
    assert [type(d) for d in decs] == [Preempt]
    assert decs[0].req is b_young                 # youngest BATCH, not the
    assert inter.rid in s.running                 # youngest overall
    s.alloc[0].check()


def test_qos_victim_uniform_class_matches_class_blind():
    """Degeneracy: with every holder in one class the QoS victim rule is
    exactly the class-blind youngest-first rule."""
    for qos in (None, _qos()):
        s = make_sched(npages=9, qos=qos)
        _running(s, 1, npages=2, arrival=1.0, out_len=2)
        young = _running(s, 2, npages=2, arrival=3.0, out_len=2)
        _running(s, 3, npages=2, arrival=2.0, out_len=2)
        decs = s.handle_starvation([s.running[1]], exclude=[])
        assert decs[0].req is young, f"qos={qos}"


def test_qos_prefill_starts_interactive_first_fifo_within_class():
    """start_prefills walks heavier classes first, FIFO within a class;
    whoever can't start stays in `waiting` in ADMISSION order."""
    s = make_sched(npages=65, qos=_qos())
    s.submit(req(0, plen=5, slo_class="batch"))
    s.submit(req(1, plen=5, slo_class="batch"))
    s.submit(req(2, plen=5, slo_class="interactive"))
    s.submit(req(3, plen=5, slo_class="interactive"))
    s.admit(t=0.0)
    decs = s.start_prefills()
    assert [d.req.rid for d in decs] == [2, 3, 0, 1]
    assert not s.waiting


def test_qos_snapshot_reports_per_class_depths():
    s = make_sched(qos=_qos())
    q = _running(s, 1, npages=1)
    q.slo_class = "interactive"
    s.submit(req(7, arrival=99.0, slo_class="batch"))
    s.submit(req(8, arrival=99.0, slo_class="interactive"))
    snap = s.snapshot()
    assert snap.per_class == (("batch", 0, 1), ("interactive", 1, 1))
    assert snap.class_in_flight("interactive") == 1
    assert snap.class_in_flight("batch") == 0
    assert snap.class_in_flight("nope") == 0


def test_qos_plan_mixed_weight_proportional_shares():
    """The prefill remainder splits 4:1 (interactive:batch weights) with
    interactive packing first; the batch share is still granted."""
    s = make_sched(npages=65, ladder=(8, 16), qos=_qos())
    s.submit(req(0, plen=30, slo_class="batch"))
    s.submit(req(1, plen=30, slo_class="interactive"))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 2
    plan = s.plan_mixed(0, budget=20, chunk=32)     # no decode: rem = 20
    pre = [(r.req.rid, r.n_tokens) for r in plan.rows
           if r.kind == "prefill"]
    # shares: interactive 20*4//5 = 16, batch max(1, 20*1//5) = 4
    assert pre == [(1, 16), (0, 4)]
    assert plan.prefill_tokens == 20


def test_qos_plan_mixed_single_class_consumes_full_remainder():
    """Degeneracy: one class present -> its share is the whole remainder
    and packing is FIFO — byte-identical to the class-blind plan."""
    s_blind = make_sched(npages=65, ladder=(8, 16))
    s_qos = make_sched(npages=65, ladder=(8, 16), qos=_qos())
    for s in (s_blind, s_qos):
        s.submit(req(0, plen=20, slo_class="batch"))
        s.submit(req(1, plen=20, slo_class="batch"))
        s.admit(t=0.0)
        assert len(s.start_prefills()) == 2
    p_blind = s_blind.plan_mixed(0, budget=30, chunk=16)
    p_qos = s_qos.plan_mixed(0, budget=30, chunk=16)
    pick = lambda p: [(r.req.rid, r.n_tokens, r.kind) for r in p.rows]
    assert pick(p_blind) == pick(p_qos) == [(0, 16, "prefill"),
                                            (1, 14, "prefill")]


def test_qos_batch_min_grant_survives_interactive_saturation():
    """A sustained interactive prefill that alone absorbs the remainder
    must not starve batch: every present class keeps a >= 1-token grant,
    so the batch prompt still completes."""
    s = make_sched(npages=65, ladder=(8, 16), qos=_qos())
    s.submit(req(0, plen=12, slo_class="batch"))
    # a stream of big interactive prompts saturating every remainder
    for i in range(1, 5):
        s.submit(req(i, plen=40, slo_class="interactive"))
    s.admit(t=0.0)
    assert len(s.start_prefills()) == 5
    batch = next(r for r in s.prefilling if r.slo_class == "batch")
    for i in range(24):
        if batch.rid not in [r.rid for r in s.prefilling]:
            break
        plan = s.plan_mixed(i, budget=8, chunk=16)
        mine = [r.n_tokens for r in plan.rows
                if r.kind == "prefill" and r.req is batch]
        assert mine and mine[0] >= 1          # the per-class min-grant
        s.commit_mixed(plan, [[5] * plan.B], t=float(i))
    assert batch.rid in s.running             # finished its prefill
