"""Moebius serving engine: the thin facade over Scheduler + Executor.

The engine is decomposed into three layers (DESIGN.md §7):

  * `serving/scheduler.py` — pure-host Scheduler (imports no jax): queues,
    admission, continuous-batching plans, page budgets, preemption, prefix
    policy — emitting typed decisions;
  * `serving/executor.py`  — Executor/ModelRunner: packs, KV buffer, step
    fns, fused dispatch pipeline, page copies, switch execution;
  * `serving/frontend.py`  — AsyncEngine: streaming `generate()` on an
    arrival-driven event loop with per-request TTFT/TPOT.

`MoebiusEngine` wires the first two and keeps the classic synchronous
`step()`/`run()` API: admission -> policy -> (switch?) -> ONE
token-budgeted mixed dispatch per iteration (decode rows first, prefill
chunks into the remaining budget; DESIGN.md §10), pipelined one deep: an
iteration launches its dispatch, then lands the one before it. The
switch is executed between steps without draining requests (only the
dispatch in flight lands): request metadata is rewritten on host, expert
weights are resharded and the paged KV migrated by the jitted movers, and
the target layout's pre-warmed step functions are *selected*, not
rebuilt. The `SwitchCoordinator` observes the
Scheduler's queue snapshot — never engine internals.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from repro.core.layouts import EP, TP, LayoutSpec, get_layout, world_of
from repro.core.policy import PolicyConfig, SwitchCoordinator
from repro.models.common import ModelConfig
from repro.serving.executor import Executor
from repro.serving.faults import FaultInjector
from repro.serving.kvcache import CacheConfig, PageAllocator, PrefixCache
from repro.serving.metrics import ServeMetrics
from repro.serving.qos import QosPolicy, slo_targets
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler
from repro.tracing import span


@dataclass
class EngineConfig:
    start_layout: str = TP
    # layouts the engine keeps resident and the policy may switch between
    # (any registered LayoutSpec names, e.g. ("tp", "ep", "tpep"))
    layouts: tuple = (TP, EP)
    ladder: tuple = (4, 8, 16, 32)
    prefill_chunk: int = 32
    # per-iteration mixed-batch token budget (DESIGN.md §10); 0 = auto:
    # the executor's prefill chunk, which is already rounded up to a
    # multiple of every resident layout's prefill_quantum, so full-mesh
    # layouts keep their 1/G-per-rank prefill split
    token_budget: int = 0
    temperature: float = 0.0
    time_scale: float = 1.0            # virtual seconds per wall second
    direct_reshard: bool = True        # paper's fused path when pure-EP
    # 0 = monolithic switch (decode paused for the whole migration);
    # k > 0 = overlapped switch migrating k layers per chunk, decode
    # interleaved between chunks (DESIGN.md §4.3)
    chunk_layers: int = 0
    # N > 1 fuses N decode steps under one dispatch (lax.fori_loop feeding
    # sampled tokens back on device, DESIGN.md §5): decode state lives on
    # device, outputs are fetched once per N steps and consumed one engine
    # iteration late, and the engine drains to a step boundary before any
    # switch. N == 1 keeps the classic per-token host loop.
    decode_steps: int = 1
    # kernel backends for the step fns (kernels/dispatch.resolve_backend):
    # None = auto (pallas on TPU, ref elsewhere), "ref" = pure-jnp oracle,
    # "pallas" = the compiled kernel (TPU only), "interpret" = Pallas
    # interpret mode. attn_backend picks paged attention; moe_backend picks
    # the grouped expert GEMM inside _ffn (DESIGN.md §14).
    attn_backend: str | None = None
    moe_backend: str | None = None
    # backend for the fused switch-staging movers (kv_pack page
    # gather/scatter + expert_reshard permutes inside the jitted movers
    # and the cross-world staged gathers); same resolution rules
    switch_backend: str | None = None
    # opt-in: warmup() also makes an idle chunked round trip to every
    # other same-world resident layout, compiling and running its step fns
    # and the movers both ways, so no live switch — nor the serving after
    # it — compiles anything (paper §4.4). Off by default — tests and
    # non-switching servers shouldn't pay the mover compiles.
    warm_switches: bool = False
    # share page-aligned prompt prefixes across requests (refcounted pages
    # + CoW; DESIGN.md §6). Greedy outputs are byte-identical with the
    # cache on or off — it only removes redundant prefill compute/bytes.
    prefix_cache: bool = True
    # keep the fp32 logits behind every sampled token in Executor.logits,
    # keyed (rid, position): lets a parity check compare two runs at the
    # first step where their greedy tokens part (one host fetch per step)
    record_logits: bool = False
    # trace-replay idle fast-forward: when every pending request is still
    # in the future and nothing is live, jump the engine clock to the next
    # arrival instead of burning empty step() iterations (quiet-period
    # wall time becomes O(1) under the virtual clock)
    idle_skip: bool = True
    # injectable clock (callable -> seconds). None = wall clock scaled by
    # time_scale. A VirtualClock (serving/frontend.py) makes the event
    # loop fully deterministic; `idle_skip` then advances it directly.
    clock: object = None
    # multi-tenant QoS (DESIGN.md §11): class-aware admission / victim /
    # budget-share scheduling plus the interactive-attainment switch gate.
    # Safe to leave on: with a single-class trace every QoS hook
    # degenerates to the class-blind rule (byte-identical outputs).
    qos: bool = True
    # deterministic fault injection (DESIGN.md §12): a FaultPlan /
    # FaultInjector / iterable of Faults scripted against the virtual
    # clock. None = no chaos. The engine polls it at the top of every
    # iteration and at every chunk boundary of a chunked switch.
    faults: object = None
    policy: PolicyConfig = field(default_factory=PolicyConfig)
    seed: int = 0


@dataclass
class SwitchRecord:
    t: float
    direction: str
    total_s: float
    weights_s: float
    kv_s: float
    plan_s: float
    kv_pages: int
    live_requests: int
    pause_s: float = 0.0               # decode-blocked time (== total_s
                                       # for a monolithic switch)
    chunks: int = 1
    delta_pages: int = 0
    bytes_sent: int = 0                # owner-changed bytes a chip's
                                       # movers send: the busiest chip's
                                       # expert slices + an even share of
                                       # the pages (plan + delta; 0 across
                                       # worlds, via the host)


class MoebiusEngine:
    """Facade: owns the clock, the policy coordinator, and the step loop;
    delegates every scheduling decision to `Scheduler` and every device
    action to `Executor`. Existing call sites keep working through the
    delegating properties below."""

    def __init__(self, cfg: ModelConfig, mesh, cc: CacheConfig,
                 ecfg: EngineConfig | None = None,
                 data_axis: str = "data", model_axis: str = "model"):
        self.cfg, self.mesh, self.cc = cfg, mesh, cc
        self.ecfg = ecfg or EngineConfig()
        self.m, self.da = model_axis, data_axis
        self.G = mesh.shape[model_axis]
        self.Dd = mesh.shape[data_axis]
        self.chips = self.Dd * self.G
        self.layouts: tuple[LayoutSpec, ...] = tuple(
            get_layout(l) for l in self.ecfg.layouts)
        start = get_layout(self.ecfg.start_layout)
        if start not in self.layouts:
            self.layouts = self.layouts + (start,)
        self.metrics = ServeMetrics()
        self.switch_records: list[SwitchRecord] = []
        self._step_i = 0
        self._t0 = time.monotonic()
        self._clock = self.ecfg.clock
        self._clock_skip = 0.0
        # fault tolerance (DESIGN.md §12)
        self._faults = (None if self.ecfg.faults is None
                        else FaultInjector(self.ecfg.faults))
        self._holds: list = []         # live pool_exhaust page seizures
        self._recoveries: list = []    # in-progress rank-failure recoveries

        # --- the three layers ---
        self.ex = Executor(cfg, mesh, cc, self.ecfg, self.layouts, start,
                           metrics=self.metrics,
                           data_axis=data_axis, model_axis=model_axis)
        # allocators live at the START layout's world (a sized start like
        # "tp@4" begins life on the sub-mesh)
        alloc = [PageAllocator(cc, cfg, world_of(start, self.G), start)
                 for _ in range(self.Dd)]
        # prefix cache: one index per data group over that group's allocator
        prefix = ([PrefixCache(alloc[d]) for d in range(self.Dd)]
                  if self.ecfg.prefix_cache else None)
        qos = QosPolicy() if self.ecfg.qos else None
        if qos is not None:
            # per-class attainment needs the class targets installed
            self.metrics.slo_targets = slo_targets()
        self.sched = Scheduler(cc, self.Dd, self.G, self.ex.rt.ladder,
                               alloc=alloc, prefix=prefix, spec=start,
                               clock=self.now, metrics=self.metrics,
                               qos=qos)
        self.sched.set_layout(start)   # syncs sched.G with start's world
        self.sched.clear_slot = self.ex.clear_slot
        self.sched.drain = self._drain
        self.ex.on_finish = self.sched.finish_request
        # the mixed-step pipeline, one deep: (plan, device next tokens) of
        # the dispatch launched and not yet landed, or None
        self._in_flight: tuple | None = None
        # the policy runs on the engine's virtual clock (time_scale-aware),
        # never wall time: cooldowns stay correct under scaled replay; it
        # observes the SCHEDULER's queue snapshot, not engine internals
        self.coord = SwitchCoordinator(cfg, self.G, self.ecfg.policy,
                                       active=start, clock=self.now,
                                       layouts=self.layouts,
                                       chips=self.chips)

    # ------------------------------------------------------------------
    # time
    # ------------------------------------------------------------------
    def now(self) -> float:
        if self._clock is not None:
            return self._clock()
        return ((time.monotonic() - self._t0) * self.ecfg.time_scale
                + self._clock_skip)

    def _skip_idle(self) -> None:
        """Trace-replay fast-forward: with nothing live and every pending
        request in the future, advance the clock straight to the next
        arrival — quiet periods cost one iteration, not wall time."""
        if (self.sched.waiting or self.sched.prefilling or self.sched.running
                or self.ex._pending is not None):
            return
        nxt = self.sched.next_arrival()
        if nxt is None:
            return
        t = self.now()
        if nxt <= t:
            return
        if self._clock is not None:
            adv = getattr(self._clock, "advance_to", None)
            if adv is not None:
                adv(nxt)
            return
        self._clock_skip += nxt - t

    # ------------------------------------------------------------------
    # delegating surface (compat: tests/benches/elastic reach these)
    # ------------------------------------------------------------------
    @property
    def active(self) -> LayoutSpec:
        return self.ex.active

    @property
    def pending(self):
        return self.sched.pending

    @property
    def waiting(self):
        return self.sched.waiting

    @property
    def prefilling(self):
        return self.sched.prefilling

    @property
    def running(self):
        return self.sched.running

    @property
    def finished(self):
        return self.sched.finished

    @property
    def alloc(self):
        return self.sched.alloc

    @property
    def prefix(self):
        return self.sched.prefix

    @property
    def kv_flat(self):
        return self.ex.kv_flat

    @property
    def packs(self):
        return self.ex.packs

    @property
    def _experts(self):
        return self.ex._experts

    @property
    def _pending(self):
        return self.ex._pending

    @property
    def prefill_chunk(self) -> int:
        return self.ex.prefill_chunk

    @property
    def token_budget(self) -> int:
        """Per-iteration mixed-batch token budget (0 in the config = auto:
        the executor's quantum-rounded prefill chunk)."""
        return self.ecfg.token_budget or self.ex.prefill_chunk

    def submit(self, req: Request) -> None:
        self.sched.submit(req)

    def warmup(self, layouts=None) -> None:
        """Compile (and run on zeros) the active layout's step fns. With
        warm_switches, also switch idle to each other same-world layout and
        back: its step fns then run once in their own expert layout, and
        the movers of both directions are compiled before traffic."""
        self.ex.warmup(layouts)
        if not (self.ecfg.warm_switches and self.ecfg.chunk_layers > 0):
            return
        home = self.active
        for lo in (self.layouts if layouts is None else layouts):
            if lo is home or self.ex._is_cross_world(lo):
                continue
            self._idle_switch(lo)
            self.ex.warmup((lo, home))    # lo's steps + lo->home movers
            self._idle_switch(home)

    def _idle_switch(self, target: LayoutSpec) -> None:
        """A chunked switch with no live request, recorded nowhere."""
        assert not self.sched.live(), "idle switch with live requests"
        sess = self.ex.switch_start(target, [], self.ecfg.chunk_layers,
                                    self.sched.alloc, self.sched.prefix)
        while not sess.done:
            self.ex.switch_advance()
        alloc, caches, _ = self.ex.switch_commit(target, [])
        self.sched.alloc, self.sched.prefix = alloc, caches
        self.sched.set_layout(target)
        self.coord.switch_completed(self.active)

    def requeue_for_reprefill(self, r: Request) -> None:
        self.sched.requeue_for_reprefill(r)

    def clear_prefix_cache(self) -> None:
        self.sched.clear_prefix_cache()

    def _drain_decode(self) -> None:
        self._drain("fault")
        self.ex.drain_decode()

    # ------------------------------------------------------------------
    # the serve step (Scheduler plans, Executor dispatches)
    # ------------------------------------------------------------------
    def _decode_step(self) -> None:
        """The decode step between the chunks of a chunked switch: prefill
        does not advance while a switch session is staging, so it is the
        serve step planned with no prefill chunk (or the fused loop). It
        lands before the next chunk, unpipelined, so the commit's
        dirty-page delta sees every write of the overlap window."""
        if self.ecfg.decode_steps > 1:
            self.ex.decode_fused(self.sched, self._step_i)
        elif self.sched.running:
            self._dispatch(chunk=0)
            self._drain("switch")

    def _mixed_step(self) -> tuple[int, int]:
        """ONE token-budgeted dispatch per iteration (DESIGN.md §10): all
        eligible decode tokens first, prefill chunks packed into the
        remaining budget, through a single step function. Returns the
        dispatched (B, Sq), (0, 0) when no mixed step ran."""
        if self.ecfg.decode_steps > 1:
            if not self.sched.prefilling:
                # pure decode: the fused N-step pipeline serves it (copies
                # from admission land inside decode_fused's drain)
                self._drain("fused")
                self.ex.decode_fused(self.sched, self._step_i)
                return 0, 0
            # a prefill chunk joins: drain the one-deep pipeline to a step
            # boundary and run single-token mixed dispatches until the
            # storm passes (runners re-join the fused loop afterwards)
            self.ex.suspend_fused(self.sched)
        return self._dispatch(chunk=self.ex.prefill_chunk)

    def _dispatch(self, chunk: int) -> tuple[int, int]:
        """Plan, copy and launch one mixed step with prefill chunks of at
        most `chunk` tokens (0: decode rows only), then land the dispatch
        launched before it: the pipeline is one deep (DESIGN.md §10), so
        the host plans and stages step n+1 while the chip runs step n. A
        step with nothing to launch lands what is in flight."""
        with span("sched.plan"):
            plan = self.sched.plan_mixed(self._step_i,
                                         budget=self.token_budget,
                                         chunk=chunk)
        # CoW copies from BOTH prefill admission and the plan's page growth
        # must land before the dispatch that could write their source pages
        self.ex.run_copies(self.sched.drain_copies())
        if not plan.rows:
            self._drain("idle")
            return 0, 0
        nxt = self.ex.run_mixed(plan, self._step_i)
        prev, self._in_flight = self._in_flight, (plan, nxt)
        if prev is not None:
            self.metrics.overlapped_dispatches += 1
            self._land(*prev)
        return plan.B, plan.Sq

    def _land(self, plan, nxt) -> None:
        """Fetch one launched dispatch's tokens and commit them."""
        toks = self.ex.fetch_mixed(nxt)
        with span("sched.commit"):
            self.sched.commit_mixed(plan, toks, self.now())

    def _drain(self, cause: str) -> None:
        """Land the dispatch in flight, if any, so every request sits at a
        step boundary: before a step that launches nothing, a switch, a
        preemption or truncation, a cancel, deadline expiry or a fault,
        and at the end of `run`. Counted by cause."""
        if self._in_flight is None:
            return
        with span("exec.drain", cause=cause):
            prev, self._in_flight = self._in_flight, None
            self._land(*prev)
        drains = self.metrics.pipeline_drains
        drains[cause] = drains.get(cause, 0) + 1

    # ------------------------------------------------------------------
    # switch
    # ------------------------------------------------------------------
    def execute_switch(self, target: str) -> bool:
        """Live switch between decode iterations; no request is drained.
        The target may be ANY registered layout the engine keeps resident —
        the switch plan is the src->target slice-ownership diff.

        Monolithic mode (chunk_layers == 0) pauses decode for the whole
        migration. Chunked mode stages the destination buffers layer chunk
        by layer chunk with decode steps interleaved in between (still on
        the intact source layout), then pauses only for the dirty-page
        delta + commit (DESIGN.md §4.3). A chunked attempt can ABORT at a
        chunk boundary — injected fault or mid-switch policy reversal —
        leaving the source layout live (DESIGN.md §12); returns False in
        that case, True when the switch committed.
        """
        target = get_layout(target)
        assert target is not self.active, "switch target == active layout"
        assert target in self.layouts, \
            f"layout {target} not resident (EngineConfig.layouts)"
        with span("switch", direction=f"{self.active}_to_{target}") as sp:
            done = self._execute_switch(target)
            if done:
                sp.set_metadata(bytes_sent=self.switch_records[-1].bytes_sent)
            return done

    def _execute_switch(self, target: LayoutSpec) -> bool:
        cross_world = self.ex._is_cross_world(target)
        # fetch in-flight tokens so every request's kv_len and pages sit at
        # a step boundary before the plan snapshot
        self._drain("switch")
        self.ex.drain_decode()
        if self.ex._is_cross_world(target):
            # shrink feasibility gate, BEFORE any planning: the destination
            # world's page pool must hold every live request's pages.
            # Overflow holders are preempted through the normal requeue
            # protocol (teacher-forced re-prefill) — never dropped.
            w_dst = self.ex._world(target)
            cap_pages = PageAllocator(self.cc, self.cfg, w_dst,
                                      target).total_free()
            self.sched.ensure_shrink_feasible(cap_pages)
        if self.ecfg.chunk_layers > 0:
            rec = self._execute_switch_chunked(target)
            if rec is None:                # aborted; source layout live
                return False
        else:
            alloc, caches, st = self.ex.switch_monolithic(
                target, self.sched.live(), self.sched.alloc,
                self.sched.prefix)
            self.sched.alloc, self.sched.prefix = alloc, caches
            self.sched.set_layout(target)
            rec = SwitchRecord(
                t=self.now(), direction=st.direction, total_s=st.total_s,
                weights_s=st.weights_s, kv_s=st.kv_s, plan_s=st.plan_s,
                kv_pages=st.kv_pages, live_requests=st.live_requests,
                pause_s=st.pause_s, chunks=st.chunks,
                bytes_sent=st.bytes_sent)
        self.switch_records.append(rec)
        self.metrics.switch(rec.t, rec.direction, rec.pause_s, rec.total_s)
        if cross_world:
            self.metrics.cross_world_switches += 1
        # sync the coordinator with the engine's real layout (benches call
        # execute_switch directly, bypassing observe) + reset its backoff
        self.coord.switch_completed(self.active)
        return True

    def _execute_switch_chunked(self, target: LayoutSpec):
        """One chunked switch attempt; returns its SwitchRecord, or None
        when the attempt aborted (fault / policy reversal) at a chunk
        boundary — the abort path already recorded metrics + backoff."""
        inj = self._faults
        if inj is not None:
            inj.begin_switch()
        cap_ep = self.cc.capacity_tokens(self.cfg, self.G, EP)
        sess = self.ex.switch_start(target, self.sched.live(),
                                    self.ecfg.chunk_layers,
                                    self.sched.alloc, self.sched.prefix)
        abort_reason, rank_fault = None, None
        while not sess.done:
            self.ex.switch_advance()
            # overlap: decode continues in the source layout on the source
            # buffers while the chunk's collectives are in flight
            self._step_i += 1
            self._decode_step()
            boundary = sess.next_chunk - 1
            if inj is not None:
                for f in inj.poll_switch(boundary):
                    if f.kind == "chunk_slow":
                        # straggler chunk: charge the virtual clock and
                        # keep migrating
                        self.metrics.faults_injected += 1
                        self.metrics.chunk_slowdowns += 1
                        self._advance_clock(f.delay_s)
                    elif f.kind == "chunk_fail":
                        self.metrics.faults_injected += 1
                        abort_reason = f"chunk {boundary} failed"
                    elif f.kind == "rank_fail":
                        # applied after the break: fail_rank itself aborts
                        # the session before invalidating the rank
                        abort_reason = (f"rank {f.rank} failed at "
                                        f"chunk {boundary}")
                        rank_fault = f
                    elif f.kind != "switch":   # no nested switches
                        self._apply_fault(f)
                if abort_reason is not None:
                    break
            # mid-switch policy reversal: the scorer now prefers the SOURCE
            # layout for the post-commit queue state — finishing the
            # migration would buy a layout we'd immediately leave
            if self.coord.mid_switch_reversal(self.active, target,
                                              self.sched.snapshot(), cap_ep):
                abort_reason = "policy reversal"
                break
        if abort_reason is not None:
            self.ex.drain_decode()
            if rank_fault is not None:
                self._apply_fault(rank_fault)
            else:
                self.abort_switch(abort_reason)
            return None
        # drain to a step boundary so the commit-time dirty-page delta sees
        # every KV write the overlap window produced
        self.ex.drain_decode()
        alloc, caches, st = self.ex.switch_commit(target, self.sched.live())
        self.sched.alloc, self.sched.prefix = alloc, caches
        self.sched.set_layout(target)
        return SwitchRecord(
            t=self.now(), direction=st.direction, total_s=st.total_s,
            weights_s=0.0, kv_s=0.0, plan_s=st.plan_s,
            kv_pages=st.kv_pages, live_requests=st.live_requests,
            pause_s=st.pause_s, chunks=st.chunks,
            delta_pages=st.delta_pages, bytes_sent=st.bytes_sent)

    # ------------------------------------------------------------------
    # fault tolerance (DESIGN.md §12)
    # ------------------------------------------------------------------
    def switch_in_progress(self) -> bool:
        return self.ex.switch_in_progress()

    def layouts_summary(self) -> dict:
        """GET /v1/layouts payload: resident layouts with their worlds,
        the active layout, degraded pools, and switch/backoff state."""
        return {
            "active": str(self.active),
            "world": self.ex._world(self.active),
            "launch_world": self.G,
            "layouts": [{"name": str(l), "world": self.ex._world(l),
                         "active": l is self.active}
                        for l in self.layouts],
            "dead_pools": sorted(self.sched.dead_pools),
            "switch_in_progress": self.switch_in_progress(),
            "switches": len(self.metrics.switch_events),
            "switch_aborts": len(self.metrics.switch_abort_events),
            "cooldown_backoff": self.coord.backoff_mult,
        }

    def abort_switch(self, reason: str = "") -> bool:
        """Abandon the in-flight chunked switch at the current chunk
        boundary: staging buffers and planned dst pages are dropped, the
        source layout stays live and byte-identical (SwitchExecutor.abort).
        Grows the coordinator's cooldown backoff."""
        if not self.switch_in_progress():
            return False
        st = self.ex.switch_abort()
        now = self.now()
        self.metrics.switch_abort(now, st.direction, reason)
        self.coord.switch_aborted(self.active, now)
        return True

    def cancel(self, rid: int, *, kind: str = "disconnect") -> bool:
        """Client-side cancellation (SSE disconnect): drop the request
        wherever it sits and free its slot/pages through the scheduler's
        finish path. Returns False for an unknown/finished rid."""
        self._drain("cancel")         # cancel_request needs inflight == 0
        self.ex.drain_decode()
        r = self.sched.cancel_request(rid)
        if r is None:
            return False
        if kind == "disconnect":
            self.metrics.client_disconnects += 1
        return True

    def note_rank_failure(self, data_group: int, rank: int, hit: list,
                          degraded: bool) -> None:
        """Called by elastic.fail_rank after it requeued the hit requests:
        record the failure and start tracking its recovery — complete when
        every hit request has re-prefilled (left waiting/prefilling). A
        `degraded` (per-rank, EP) failure keeps the pool out of placement
        until then."""
        now = self.now()
        self.metrics.rank_failure(now, data_group, rank, len(hit))
        if not hit:
            # nothing to re-prefill: recovery is instantaneous
            self.metrics.recovery(now, 0, 0, degraded)
            if degraded:
                self.sched.revive_pool(data_group, rank)
            return
        self._recoveries.append({
            "rids": {r.rid for r in hit}, "d": data_group, "rank": rank,
            "start_step": self._step_i, "degraded": degraded})

    def _check_recoveries(self) -> None:
        """A recovery completes when none of its requests is still queued
        for (re-)prefill — each is running again or finished. Revives the
        dead pool of a degraded (per-rank) failure."""
        if not self._recoveries:
            return
        queued = {r.rid for r in (self.sched.waiting + self.sched.prefilling
                                  + list(self.sched.pending))}
        still = []
        for rec in self._recoveries:
            if rec["rids"] & queued:
                still.append(rec)
                continue
            self.metrics.recovery(self.now(),
                                  self._step_i - rec["start_step"],
                                  len(rec["rids"]), rec["degraded"])
            if rec["degraded"]:
                self.sched.revive_pool(rec["d"], rec["rank"])
        self._recoveries = still

    def _apply_fault(self, f) -> None:
        """Act on one fired Fault (see serving/faults.py for the kinds)."""
        if f.kind != "switch":         # a scripted switch drains itself
            self._drain("fault")
        self.metrics.faults_injected += 1
        if f.kind == "rank_fail":
            from repro.distributed.elastic import fail_rank
            fail_rank(self, f.data_group, f.rank)
        elif f.kind == "pool_exhaust":
            self.metrics.pool_exhaust_events += 1
            alloc = self.sched.alloc[f.data_group]
            n = alloc.free_pages(f.pool)
            pages = alloc.try_alloc(f.pool, n) if n > 0 else None
            if pages:
                self._holds.append({
                    "alloc": alloc, "d": f.data_group, "pool": f.pool,
                    "pages": pages,
                    "release_step": self._step_i + f.duration_steps})
        elif f.kind == "client_disconnect":
            self.cancel(f.rid)
        elif f.kind == "chunk_slow":
            self.metrics.chunk_slowdowns += 1
            self._advance_clock(f.delay_s)
        elif f.kind == "switch":
            # scripted event, not a fault: lets a plan place chunk faults
            if get_layout(f.target) is not self.active:
                self.execute_switch(f.target)
        # chunk_fail outside a switch: nothing to fail — ignored

    def _release_expired_holds(self) -> None:
        """Release expired pool_exhaust seizures — but only into the
        allocator that handed the pages out; a switch replaces the
        scheduler's allocators, and a hold dies with the old one."""
        if not self._holds:
            return
        keep = []
        for h in self._holds:
            if self._step_i < h["release_step"]:
                keep.append(h)
            elif self.sched.alloc[h["d"]] is h["alloc"]:
                h["alloc"].release(h["pool"], h["pages"])
        self._holds = keep

    def _advance_clock(self, dt: float) -> None:
        if dt <= 0:
            return
        if self._clock is not None:
            adv = getattr(self._clock, "advance", None)
            if adv is not None:
                adv(dt)
            return
        self._clock_skip += dt

    # ------------------------------------------------------------------
    # main loop
    # ------------------------------------------------------------------
    def step(self) -> None:
        self._step_i += 1
        m = self.metrics
        dec0, pre0 = m.decode_tokens, m.prefill_tokens
        passes0, ov0 = m.moe_weight_passes, m.overlapped_dispatches
        with span("step", step=self._step_i) as sp:
            with span("sched.admit"):
                if self.ecfg.idle_skip:
                    self._skip_idle()
                self._release_expired_holds()
                if self._faults is not None:
                    for f in self._faults.poll(self._step_i, self.now()):
                        self._apply_fault(f)
                self.sched.admit(self.now())
                if self.sched.deadline_due(self.now()):
                    # expiry finishes requests in place: drain both
                    # pipelines first so none has in-flight tokens
                    self._drain("deadline")
                    self.ex.drain_decode()
                    self.sched.expire_deadlines(self.now())
            # policy: sample once per iteration, between steps, through the
            # scheduler's queue snapshot (in-flight fused tokens count
            # toward the live-token load)
            with span("policy"):
                cap_ep = self.cc.capacity_tokens(self.cfg, self.G, EP)
                att = (m.recent_attainment("interactive")
                       if self.ecfg.qos else None)
                dec = self.coord.observe_queues(self.sched.snapshot(),
                                                cap_ep, attainment=att)
            if dec.switch:
                self.execute_switch(dec.target)
            with span("sched.admit"):
                started = self.sched.start_prefills()   # waiting -> prefill
                t = self.now()
                for d in started:
                    if d.req.prefill_start_s is None:
                        d.req.prefill_start_s = t
            B, Sq = self._mixed_step()
            with span("account"):
                self._check_recoveries()
                m.pages_resident(sum(a.total_held()
                                     for a in self.sched.alloc))
            sp.set_metadata(B=B, Sq=Sq, dec=m.decode_tokens - dec0,
                            pre=m.prefill_tokens - pre0,
                            passes=m.moe_weight_passes - passes0,
                            overlap=m.overlapped_dispatches - ov0)

    def run(self, max_steps: int = 100000):
        for _ in range(max_steps):
            if not self.sched.has_work():
                break
            self.step()
        self._drain("end")             # flush half-open pipelines
        self.ex.drain_decode()
        return self.metrics.summary()
