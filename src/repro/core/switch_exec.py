"""SwitchExecutor: the runtime that drives live layout switches.

Switches are planned between ANY ordered pair of registered `LayoutSpec`s:
the executor diffs the two specs' KV views (same view -> identity, the
allocators and pages pass through untouched) and their ExpertLayouts (the
generic pair resharder covers pairs across different expert-group sizes;
the paper's fused direct path is kept for the pure-EP tp<->ep pair).

Two execution modes over the movers in core/switch.py (DESIGN.md §4):

  * **monolithic** — the paper's baseline switch: plan, reshard all expert
    weights, migrate all planned KV pages, rewrite request metadata. Decode
    is paused for the whole operation (pause == total).

  * **chunked / overlapped** — pre-copy + delta, the live-migration shape of
    the paper's "switch between decode steps without draining" claim
    (§4.3-4.4). The expert store and the KV pool are migrated **layer chunk
    by layer chunk** into staged destination buffers while the source
    buffers stay live, so the engine interleaves decode steps between
    chunks. Decode keeps using the *old* layout, metadata, and allocator
    (`plan_switch` is pure — nothing on a request changes during the
    window). At commit the executor:

      1. re-copies the **dirty pages** — pages that received decode writes
         after the plan snapshot (the tail page(s) of each live request),
         plus pages allocated during the window — via the same chunk mover
         over all layers with a small plan width;
      2. releases destination pages of requests that finished mid-window;
      3. applies the planned metadata (pages / owner_rank) and returns the
         staged buffers + the destination allocator.

    Only step 1-3 pause decode, so pause_s is a small fraction of total_s.

The executor owns all jitted-mover caches (compiled once per (direction,
layer range, plan width); a later switch reuses the executable — runtime
preservation, paper §4.4).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.layouts import EP, TP, get_layout, group_info
from repro.core.switch import (apply_assignments, expert_bytes_sent,
                               expert_pair_dst_struct, kv_bytes_sent,
                               kv_migration_direction,
                               make_migrate_kv, make_migrate_kv_chunk,
                               make_reshard_experts_direct,
                               make_reshard_experts_direct_chunk,
                               make_reshard_experts_pair,
                               make_reshard_experts_pair_chunk,
                               pack_experts_host, pair_expert_layouts,
                               pairs_to_plan, plan_cross_world, plan_switch)
from repro.kernels.kv_pack.ops import gather_pages_rows
from repro.models.common import ModelConfig
from repro.models.moe import make_expert_layout
from repro.serving.kvcache import (CacheConfig, PageAllocator, PrefixCache,
                                   num_kv_layers)
from repro.tracing import span


def _pow2_pad(n: int, lo: int = 8) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


# Fixed plan width of every chunked KV mover call (layer chunks and the
# commit-time dirty-page delta). Wider plans are split into several calls
# of this width, so each mover compiles once per (direction, layers) at
# warmup — a live switch never compiles because it had more pages to move
# or its overlap window dirtied more of them.
KV_BLOCK = 8


@dataclass
class SwitchStats:
    direction: str
    total_s: float = 0.0
    pause_s: float = 0.0
    plan_s: float = 0.0
    weights_s: float = 0.0
    kv_s: float = 0.0
    kv_pages: int = 0
    delta_pages: int = 0
    chunks: int = 1
    live_requests: int = 0
    bytes_sent: int = 0     # owner-changed expert bytes the busiest chip
                            # sends + an even share of the moved pages'
                            # (0 across worlds: those movers use the host)


@dataclass
class SwitchSession:
    """State of one in-progress chunked switch."""
    src: object                             # source LayoutSpec
    dst: object                             # destination LayoutSpec
    direction: str                          # "<src>_to_<dst>" (stats label)
    kv_dir: str | None                      # KV-view mover direction
    t_start: float
    plan_blocks: list             # [(sp, dp, vm)] device, (Dd, G, KV_BLOCK)
    assignments: list                       # per data group lists merged
    new_alloc: list
    chunks: list                            # [(w_lo, w_hi, kv_lo, kv_hi)]
    next_chunk: int = 0
    experts_dst: dict | None = None
    kv_dst: object = None
    kv_pages: int = 0
    live_requests: int = 0
    plan_pause_s: float = 0.0       # decode-blocked time spent in start()
    cache_moves: list = None        # per-data-group planned cache remaps
    caches: list = None             # the engine's live PrefixCaches (or None)
    alive_moves: list = None        # commit-time: moves still worth keeping

    @property
    def done(self) -> bool:
        return self.next_chunk >= len(self.chunks)


class SwitchExecutor:
    """Builds, caches, and drives the jitted movers for live switches."""

    def __init__(self, cfg: ModelConfig, cc: CacheConfig, mesh, *,
                 model_axis: str = "model", data_axis: str = "data",
                 direct_reshard: bool = True, backend: str | None = None):
        self.cfg, self.cc, self.mesh = cfg, cc, mesh
        self.m, self.da = model_axis, data_axis
        # kernel backend for the fused staging movers (kv_pack page
        # gather/scatter + expert_reshard permutes); None = auto
        self.backend = backend
        self.G = mesh.shape[model_axis]
        self.Dd = mesh.shape[data_axis]
        self.chips = self.Dd * self.G
        self.Lk = num_kv_layers(cfg)
        self.direct_reshard = direct_reshard
        self._reshard_fns: dict = {}
        self._migrate_fns: dict = {}
        self._chunk_reshard_fns: dict = {}
        self._chunk_migrate_fns: dict = {}
        self._zeros_fns: dict = {}
        self.session: SwitchSession | None = None

    # ------------------------------------------------------------------
    # mover caches
    # ------------------------------------------------------------------
    def _use_direct(self, src, dst) -> bool:
        """The paper's fused shard_map path: pure-EP tp<->ep pairs only."""
        if {src, dst} != {TP, EP}:
            return False
        lay_ep = make_expert_layout(self.cfg.num_experts, self.G, EP)
        return self.direct_reshard and lay_ep.is_pure_ep

    @staticmethod
    def _direct_direction(src) -> str:
        return "ep_to_tp" if src is EP else "tp_to_ep"

    def reshard_fn(self, src, dst, experts):
        key = (src, dst)
        if key not in self._reshard_fns:
            if self._use_direct(src, dst):
                self._reshard_fns[key] = (
                    "direct",
                    make_reshard_experts_direct(self.cfg, self.mesh,
                                                self._direct_direction(src),
                                                model_axis=self.m,
                                                backend=self.backend))
            else:
                build = make_reshard_experts_pair(
                    self.cfg, self.mesh, src, dst, model_axis=self.m,
                    data_axes=(self.da,))
                sds = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), experts)
                self._reshard_fns[key] = ("xla", build(sds))
        return self._reshard_fns[key]

    def migrate_fn(self, direction: str, pmax: int):
        key = (direction, pmax)
        if key not in self._migrate_fns:
            self._migrate_fns[key] = make_migrate_kv(
                self.cfg, self.cc, self.mesh, direction, pmax,
                model_axis=self.m, data_axis=self.da, backend=self.backend)
        return self._migrate_fns[key]

    def chunk_reshard_fn(self, src, dst, lo: int, hi: int):
        key = (src, dst, lo, hi)
        if key not in self._chunk_reshard_fns:
            if self._use_direct(src, dst):
                fn = make_reshard_experts_direct_chunk(
                    self.cfg, self.mesh, self._direct_direction(src), lo, hi,
                    model_axis=self.m, backend=self.backend)
            else:
                fn = make_reshard_experts_pair_chunk(
                    self.cfg, self.mesh, src, dst, lo, hi,
                    model_axis=self.m, data_axes=(self.da,))
            self._chunk_reshard_fns[key] = fn
        return self._chunk_reshard_fns[key]

    def chunk_migrate_fn(self, direction: str, lo: int, hi: int, pmax: int):
        key = (direction, lo, hi, pmax)
        if key not in self._chunk_migrate_fns:
            self._chunk_migrate_fns[key] = make_migrate_kv_chunk(
                self.cfg, self.cc, self.mesh, direction, pmax, lo, hi,
                model_axis=self.m, data_axis=self.da, backend=self.backend)
        return self._chunk_migrate_fns[key]

    def _zeros(self, shape, dtype, spec):
        """Sharded zero buffer via a cached compiled initializer (staged
        destination buffers are re-created every chunked switch; the
        executable must not be)."""
        key = (tuple(shape), jnp.dtype(dtype).name, tuple(spec))
        if key not in self._zeros_fns:
            sh = NamedSharding(self.mesh, P(*spec))
            self._zeros_fns[key] = jax.jit(
                functools.partial(jnp.zeros, tuple(shape), dtype),
                out_shardings=sh)
        return self._zeros_fns[key]()

    # ------------------------------------------------------------------
    # shared planning
    # ------------------------------------------------------------------
    @staticmethod
    def _stack_plans(plans, min_width: int = 8) -> tuple:
        """Per-data-group KVPlans -> pow2-padded stacked (Dd, G, pmax)
        src/dst/valid arrays, at least min_width wide."""
        pmax = _pow2_pad(max(p.src_pages.shape[1] for p in plans),
                         lo=min_width)

        def padp(a):
            return np.pad(a, ((0, 0), (0, pmax - a.shape[1])))

        sp = np.stack([padp(p.src_pages) for p in plans])
        dp = np.stack([padp(p.dst_pages) for p in plans])
        vm = np.stack([padp(p.valid) for p in plans])
        return (sp, dp, vm), pmax

    def _plan(self, src, dst, live, *, mutate: bool, cur_alloc=None,
              caches=None):
        """Per-data-group plans + destination allocators for a src->dst
        switch. Same-KV-view pairs are identity on the KV side: the live
        allocators, every request's pages/owner, and the prefix caches pass
        through untouched. mutate=False keeps the requests untouched
        (chunked mode applies metadata at commit). `caches` (the engine's
        per-data-group PrefixCaches) joins the plan: shared pages migrate
        once per physical page and cache entries remap to the destination
        pools (see plan_switch)."""
        kv_dir = kv_migration_direction(src, dst)
        if kv_dir is None:
            empty = (np.zeros((self.Dd, self.G, 8), np.int32),
                     np.zeros((self.Dd, self.G, 8), np.int32),
                     np.zeros((self.Dd, self.G, 8), bool))
            return empty, 8, [], cur_alloc, None, None
        new_alloc = [PageAllocator(self.cc, self.cfg, self.G, dst)
                     for _ in range(self.Dd)]
        plans, assignments, cache_moves = [], [], []
        for d in range(self.Dd):
            reqs = [r for r in live if r.data_group == d and r.pages]
            plan, asg, moves = plan_switch(
                kv_dir, reqs, self.cfg, self.cc, new_alloc[d], self.G,
                cache=caches[d] if caches is not None else None)
            plans.append(plan)
            assignments.extend(asg)
            cache_moves.append(moves)
        if mutate:
            apply_assignments(assignments)
        arrays, pmax = self._stack_plans(plans)
        return arrays, pmax, assignments, new_alloc, kv_dir, cache_moves

    def _bytes_sent(self, src, dst, kv_dir, kv_pages: int,
                    kv_itemsize: int) -> int:
        """Owner-changed bytes a chip's movers send for a src->dst switch
        moving `kv_pages` pages (the plan's, and at commit the dirty-page
        delta's too), counted from the plan: the busiest chip's expert
        slices (switch.expert_bytes_sent) and an even share over the chips
        of the pages' head slices (switch.kv_bytes_sent)."""
        n = 0
        if self.cfg.is_moe:
            src_lay, dst_lay = pair_expert_layouts(self.cfg, src, dst,
                                                   self.G, self.chips)
            n += expert_bytes_sent(self.cfg, src_lay, dst_lay, self.chips,
                                   jnp.dtype(self.cfg.param_dtype).itemsize)
        if self.Lk > 0 and kv_dir is not None:
            n += kv_bytes_sent(self.cfg, self.cc, self.G, self.chips, kv_dir,
                               kv_pages, kv_itemsize)
        return n

    # ------------------------------------------------------------------
    # monolithic mode (the baseline; pause == total)
    # ------------------------------------------------------------------
    def monolithic(self, src, dst, live, experts, kv_flat, cur_alloc=None,
                   caches=None):
        """Full stop-the-world src->dst switch. Returns (experts', kv_flat',
        alloc', caches', stats); request metadata is rewritten in place."""
        src, dst = get_layout(src), get_layout(dst)
        t0 = time.perf_counter()
        with span("switch.plan"):
            (sp, dp, vm), pmax, _, new_alloc, kv_dir, cache_moves = \
                self._plan(src, dst, live, mutate=True, cur_alloc=cur_alloc,
                           caches=caches)
        t_plan = time.perf_counter() - t0

        with span("switch.commit"):
            t1 = time.perf_counter()
            if self.cfg.is_moe:
                kind, fn = self.reshard_fn(src, dst, experts)
                if kind == "direct":
                    w13, w2 = fn(experts["w13"], experts["w2"])
                    experts = {"w13": w13, "w2": w2}
                else:
                    out = fn(experts)
                    experts = {"w13": out["w13"], "w2": out["w2"]}
                jax.block_until_ready(experts["w13"])
            t_w = time.perf_counter() - t1

            t2 = time.perf_counter()
            if self.Lk > 0 and kv_dir is not None:
                mfn = self.migrate_fn(kv_dir, pmax)
                kv_flat = mfn(kv_flat, jnp.asarray(sp), jnp.asarray(dp),
                              jnp.asarray(vm))
                jax.block_until_ready(kv_flat)
            t_kv = time.perf_counter() - t2

            new_caches = caches
            if caches is not None and kv_dir is not None:
                new_caches = [PrefixCache.rebuild(new_alloc[d], cache_moves[d])
                              for d in range(self.Dd)]
            total = time.perf_counter() - t0
            kv_pages = int(vm.sum())
            stats = SwitchStats(direction=f"{src}_to_{dst}", total_s=total,
                                pause_s=total, plan_s=t_plan, weights_s=t_w,
                                kv_s=t_kv, kv_pages=kv_pages, chunks=1,
                                live_requests=len(live),
                                bytes_sent=self._bytes_sent(
                                    src, dst, kv_dir, kv_pages,
                                    kv_flat.dtype.itemsize))
        return experts, kv_flat, new_alloc, new_caches, stats

    # ------------------------------------------------------------------
    # chunked / overlapped mode
    # ------------------------------------------------------------------
    def _layer_chunks(self, chunk_layers: int) -> list:
        """Even [lo, hi) splits of the expert-stack and KV-layer ranges."""
        Lw = self.cfg.num_layers if self.cfg.is_moe else 0
        Lref = max(Lw, self.Lk, 1)
        n = max(1, -(-Lref // max(1, chunk_layers)))
        out = []
        for i in range(n):
            out.append((Lw * i // n, Lw * (i + 1) // n,
                        self.Lk * i // n, self.Lk * (i + 1) // n))
        return out

    def start(self, src, dst, live, experts, kv_flat,
              chunk_layers: int, cur_alloc=None, caches=None) -> SwitchSession:
        """Plan the src->dst switch and stage the destination buffers.
        Source buffers and request metadata stay live for overlap decode."""
        assert self.session is None, "switch already in progress"
        src, dst = get_layout(src), get_layout(dst)
        with span("switch.plan"):
            t0 = time.perf_counter()
            plan_arrays, pmax, assignments, new_alloc, kv_dir, cache_moves = \
                self._plan(src, dst, live, mutate=False, cur_alloc=cur_alloc,
                           caches=caches)
            experts_dst = None
            if self.cfg.is_moe:
                src_lay, dst_lay = pair_expert_layouts(self.cfg, src, dst,
                                                       self.G, self.chips)
                sds = expert_pair_dst_struct(self.cfg, src_lay, dst_lay,
                                             experts)
                dst_ax = dst.expert_axes((self.da,), self.m)
                experts_dst = {
                    k: self._zeros(s.shape, s.dtype,
                                   (None, dst_ax, None, None, None))
                    for k, s in sds.items()}
            kv_dst = None
            if self.Lk > 0 and kv_dir is not None:
                kv_dst = self._zeros(kv_flat.shape, kv_flat.dtype,
                                     (self.da, self.m))
            kv_pages = int(plan_arrays[2].sum())
            self.session = SwitchSession(
                src=src, dst=dst, direction=f"{src}_to_{dst}", kv_dir=kv_dir,
                t_start=t0,
                plan_blocks=[tuple(jnp.asarray(a[..., b:b + KV_BLOCK])
                                   for a in plan_arrays)
                             for b in range(0, pmax, KV_BLOCK)],
                assignments=assignments,
                new_alloc=new_alloc, chunks=self._layer_chunks(chunk_layers),
                experts_dst=experts_dst, kv_dst=kv_dst,
                kv_pages=kv_pages, live_requests=len(live),
                plan_pause_s=time.perf_counter() - t0,
                cache_moves=cache_moves, caches=caches)
            return self.session

    def advance(self, experts, kv_flat) -> bool:
        """Migrate the next layer chunk (dispatched async; decode may run
        before the chunk completes — both read the same source buffers).
        Returns True while chunks remain."""
        s = self.session
        assert s is not None and not s.done
        with span("switch.chunk", i=s.next_chunk):
            w_lo, w_hi, kv_lo, kv_hi = s.chunks[s.next_chunk]
            if self.cfg.is_moe and w_hi > w_lo:
                fn = self.chunk_reshard_fn(s.src, s.dst, w_lo, w_hi)
                d13, d2 = fn(experts["w13"], experts["w2"],
                             s.experts_dst["w13"], s.experts_dst["w2"])
                s.experts_dst = {"w13": d13, "w2": d2}
            if s.kv_dst is not None and kv_hi > kv_lo:
                mfn = self.chunk_migrate_fn(s.kv_dir, kv_lo, kv_hi, KV_BLOCK)
                for sp, dp, vm in s.plan_blocks:          # device-resident
                    s.kv_dst = mfn(kv_flat, s.kv_dst, sp, dp, vm)
            s.next_chunk += 1
        return not s.done

    def warmup_movers(self, src, dst, experts, kv_flat,
                      chunk_layers: int) -> None:
        """Compile every chunked-switch mover for src->dst before traffic:
        a dry start/advance/abort with an EMPTY plan (one KV_BLOCK-wide
        block, the width every live plan is split into) plus the
        commit-time delta executable, so a LIVE switch selects
        executables, never compiles (paper §4.4).

        Read-only on the live state: start() stages fresh zero destination
        buffers (the only donated arguments), plans with no requests, and
        the session is aborted — request metadata, allocators, and the
        source buffers are untouched by construction."""
        src, dst = get_layout(src), get_layout(dst)
        self.start(src, dst, [], experts, kv_flat, chunk_layers)
        s = self.session
        while self.advance(experts, kv_flat):
            pass
        if s.kv_dst is not None:
            # the commit-time dirty-page delta mover (all layers, fixed
            # KV_BLOCK width) only runs when a window got dirty — warm
            # it on a throwaway zero buffer so a dirty commit never compiles
            mfn = self.chunk_migrate_fn(s.kv_dir, 0, self.Lk, KV_BLOCK)
            sp, dp, vm = s.plan_blocks[0]
            scratch = self._zeros(kv_flat.shape, kv_flat.dtype,
                                  (self.da, self.m))
            jax.block_until_ready(mfn(kv_flat, scratch, sp, dp, vm))
        if s.experts_dst is not None:
            jax.block_until_ready(s.experts_dst["w13"])
        if s.kv_dst is not None:
            jax.block_until_ready(s.kv_dst)
        self.abort()

    def abort(self) -> SwitchStats:
        """Abandon the in-flight chunked session at a chunk boundary
        (DESIGN.md §12): the switch never happened.

        `start()` plans with mutate=False and `plan_switch` is pure on the
        source side, so nothing the live engine depends on — request
        metadata, the live allocators and prefix caches, the source
        expert/KV buffers decode kept reading — was ever touched. Dropping
        the session therefore *is* the rollback: the staged destination
        buffers become garbage, and every planned destination page and
        cache-move ref lives in the session's fresh `new_alloc`, which
        dies with it. The source layout simply remains live,
        byte-identical, and `SwitchExecutor` is immediately ready to plan
        a new switch."""
        s = self.session
        assert s is not None, "no switch in progress"
        self.session = None
        return SwitchStats(direction=s.direction,
                           total_s=time.perf_counter() - s.t_start,
                           plan_s=s.plan_pause_s, kv_pages=s.kv_pages,
                           chunks=s.next_chunk,
                           live_requests=s.live_requests)

    def _dst_page(self, d: int, pool: int) -> int:
        """Commit-time destination-pool allocation for a live request's
        top-up/CoW re-point. A full pool sacrifices still-alive planned
        cache moves first (dropping a cache entry is always safe; failing
        a live request's page is not); raises only on genuine exhaustion."""
        s = self.session
        got = s.new_alloc[d].try_alloc(pool, 1)
        if got is not None:
            return got[0]
        moves = s.alive_moves[d] if s.alive_moves is not None else []
        for m in list(moves):
            if m.dst_pool != pool:
                continue
            s.new_alloc[d].release(m.dst_pool, list(m.dst_pages))
            moves.remove(m)
            got = s.new_alloc[d].try_alloc(pool, 1)
            if got is not None:
                return got[0]
        return s.new_alloc[d].alloc(pool, 1)[0]

    def _delta_pairs(self, live_ids) -> tuple:
        """Dirty-page pairs per (data_group, plan row): pages that received
        decode writes after the plan snapshot, plus pages allocated during
        the window (destination pages are topped up here).

        CoW-aware: a page the request copy-on-write-forked during the
        window (r.pages[i] != the plan snapshot) keeps the *shared*
        destination page for the other sharers — this request's planned
        reference is dropped and a private destination page is allocated,
        then delta-copied from its private source."""
        s = self.session
        page = self.cc.page_size
        per = [{g: [] for g in range(self.G)} for _ in range(self.Dd)]
        n = 0
        for a in s.assignments:
            r = a.req
            if r.rid not in live_ids or not r.pages:
                continue
            if (r.kv_len == a.snap_kv_len
                    and len(a.new_pages) >= len(r.pages)
                    and list(a.snap_pages) == r.pages):
                continue    # untouched since snapshot: staged copy is final
            d = r.data_group
            dst_pool = max(a.new_owner, 0)
            while len(a.new_pages) < len(r.pages):
                a.new_pages.append(self._dst_page(d, dst_pool))
            lo_idx = max(a.snap_kv_len - 1, 0) // page
            hi_idx = min(len(r.pages) - 1, max(r.kv_len - 1, 0) // page)
            row = (r.pool_rank if s.kv_dir == "ep_to_tp"
                   else a.new_owner)
            for i in range(lo_idx, hi_idx + 1):
                cowed = i < len(a.snap_pages) and r.pages[i] != a.snap_pages[i]
                if cowed and s.new_alloc[d].refcount(
                        dst_pool, a.new_pages[i]) > 1:
                    s.new_alloc[d].release(dst_pool, [a.new_pages[i]])
                    a.new_pages[i] = self._dst_page(d, dst_pool)
                per[d][max(row, 0)].append((r.pages[i], a.new_pages[i]))
                n += 1
        return per, n

    def commit(self, live, kv_flat):
        """Pause-phase: delta-copy dirty pages, reconcile allocators and
        caches, apply metadata, hand over the staged buffers. Returns
        (experts', kv', alloc', caches', stats)."""
        s = self.session
        assert s is not None and s.done
        with span("switch.commit"):
            t_pause0 = time.perf_counter()
            live_ids = {r.rid for r in live}

            # requests that finished during the window: return their planned
            # destination pages to the new allocator
            for a in s.assignments:
                if a.req.rid not in live_ids and a.new_pages:
                    s.new_alloc[a.req.data_group].release(
                        max(a.new_owner, 0), a.new_pages)

            # cache entries evicted during the window: release their planned
            # destination refs NOW, before the delta pass — its top-up/CoW
            # allocations must be able to use those reclaimable pages
            if s.caches is not None and s.kv_dir is not None:
                s.alive_moves = []
                for d in range(self.Dd):
                    keep = []
                    for m in s.cache_moves[d]:
                        if s.caches[d].move_alive(m):
                            keep.append(m)
                        else:
                            s.new_alloc[d].release(m.dst_pool,
                                                   list(m.dst_pages))
                    s.alive_moves.append(keep)

            delta_pages = 0
            if s.kv_dst is not None:
                per, delta_pages = self._delta_pairs(live_ids)
                if delta_pages:
                    # fixed-width blocks -> one compiled delta executable per
                    # direction, regardless of how dirty the window got
                    W = KV_BLOCK
                    mfn = self.chunk_migrate_fn(s.kv_dir, 0, self.Lk, W)
                    nblocks = max(-(-len(pairs) // W)
                                  for rows in per for pairs in rows.values())
                    for b in range(nblocks):
                        plans = [pairs_to_plan(
                            s.kv_dir,
                            {g: per[d][g][b * W:(b + 1) * W]
                             for g in range(self.G)}, self.G)
                            for d in range(self.Dd)]
                        # blocks are <= W wide; min_width=W makes the padded
                        # width structurally equal to the compiled pmax
                        (sp, dp, vm), _ = self._stack_plans(plans, min_width=W)
                        s.kv_dst = mfn(kv_flat, s.kv_dst, jnp.asarray(sp),
                                       jnp.asarray(dp), jnp.asarray(vm))

            apply_assignments([a for a in s.assignments
                               if a.req.rid in live_ids])
            # surviving cache entries re-index under the destination pools
            # (dead moves released their dst refs above; _dst_page may have
            # sacrificed more to serve live requests' top-ups)
            new_caches = s.caches
            if s.caches is not None and s.kv_dir is not None:
                new_caches = [
                    PrefixCache.rebuild(s.new_alloc[d], s.alive_moves[d])
                    for d in range(self.Dd)]
            if s.kv_dst is not None:
                jax.block_until_ready(s.kv_dst)
            if s.experts_dst is not None:
                jax.block_until_ready(s.experts_dst["w13"])
            now = time.perf_counter()
            # pause = the synchronous plan/staging phase in start() plus this
            # commit phase — measured consistently with monolithic(), whose
            # pause likewise includes its plan time
            stats = SwitchStats(
                direction=s.direction, total_s=now - s.t_start,
                pause_s=s.plan_pause_s + (now - t_pause0),
                plan_s=s.plan_pause_s, kv_pages=s.kv_pages,
                delta_pages=delta_pages, chunks=len(s.chunks),
                live_requests=s.live_requests,
                bytes_sent=self._bytes_sent(
                    s.src, s.dst, s.kv_dir, s.kv_pages + delta_pages,
                    kv_flat.dtype.itemsize))
            out = (s.experts_dst,
                   s.kv_dst if s.kv_dst is not None else kv_flat,
                   s.new_alloc, new_caches, stats)
            self.session = None
            return out


# ---------------------------------------------------------------------------
# Cross-world switching (ordered pairs with DIFFERENT device counts)
# ---------------------------------------------------------------------------

@dataclass
class CrossWorldSession:
    """State of one in-progress chunked cross-world switch."""
    src: object                             # source LayoutSpec
    dst: object                             # destination LayoutSpec
    G_src: int
    G_dst: int
    direction: str                          # "<src>_to_<dst>" (stats label)
    t_start: float
    assignments: list                       # per data group lists merged
    moves: list                             # per-d (spool,spage,dpool,dpage)
    new_alloc: list                         # per-d PageAllocator @ G_dst
    chunks: list                            # [(w_lo, w_hi, kv_lo, kv_hi)]
    next_chunk: int = 0
    experts_chunks: list = None             # staged [(w13, w2)] np, in order
    kv_host: np.ndarray = None    # staged (Dd, G_dst, *rank_shape) np
    kv_pages: int = 0
    live_requests: int = 0
    plan_pause_s: float = 0.0
    caches: object = None                   # engine's PrefixCaches (or None)

    @property
    def done(self) -> bool:
        return self.next_chunk >= len(self.chunks)


class CrossWorldSwitcher:
    """Drives live switches between layouts on DIFFERENT device counts.

    No common mesh spans both worlds, so no collective can move the state;
    the movers bounce through the host instead: expert chunks are re-packed
    from the executor's canonical host copy (experts are read-only in
    serving, so the copy is never stale), and KV chunks snapshot the live
    source buffer (device_get) and copy planned pages into a staged host
    buffer in the destination world's view. The chunked pre-copy +
    commit-time dirty-page delta discipline is the same as
    `SwitchExecutor`'s: decode keeps running on the intact source between
    chunks, nothing on a request changes before commit, and `abort()` just
    drops the host buffers — the source device state was never mutated, so
    dropping the session *is* the rollback. Prefix caches do not migrate:
    a cross-world commit starts with fresh empty caches.
    """

    def __init__(self, cfg: ModelConfig, cc: CacheConfig, Dd: int,
                 moe_host: dict | None, *, model_axis: str = "model",
                 data_axis: str = "data", backend: str | None = None):
        self.cfg, self.cc, self.Dd = cfg, cc, Dd
        self.moe_host = moe_host        # canonical {"w13": (L,E,..)} np
        self.m, self.da = model_axis, data_axis
        self.backend = backend          # kv_pack backend for staged gathers
        self.Lk = num_kv_layers(cfg)
        self._stage_fns: dict = {}      # (view, lo, hi, W) -> jitted gather
        self.session: CrossWorldSession | None = None

    def _layer_chunks(self, chunk_layers: int) -> list:
        Lw = self.cfg.num_layers if self.cfg.is_moe else 0
        Lref = max(Lw, self.Lk, 1)
        n = max(1, -(-Lref // max(1, chunk_layers)))
        return [(Lw * i // n, Lw * (i + 1) // n,
                 self.Lk * i // n, self.Lk * (i + 1) // n)
                for i in range(n)]

    def start(self, src, dst, G_src: int, G_dst: int, live, kv_flat,
              chunk_layers: int, caches=None) -> CrossWorldSession:
        """Plan the cross-world switch and stage the host-side buffers.
        Source buffers and request metadata stay live for overlap decode."""
        assert self.session is None, "cross-world switch already in progress"
        src, dst = get_layout(src), get_layout(dst)
        with span("switch.plan"):
            t0 = time.perf_counter()
            new_alloc = [PageAllocator(self.cc, self.cfg, G_dst, dst)
                         for _ in range(self.Dd)]
            assignments, moves = [], []
            for d in range(self.Dd):
                reqs = [r for r in live if r.data_group == d]
                mv, asg = plan_cross_world(reqs, self.cfg, self.cc,
                                           new_alloc[d], src, dst, G_src,
                                           G_dst)
                moves.append(mv)
                assignments.extend(asg)
            kv_host = None
            if self.Lk > 0:
                # per-rank NE is world-independent (cc.nelems ignores G), so
                # the destination rows reuse the source buffer's trailing dim
                kv_host = np.zeros((self.Dd, G_dst) + kv_flat.shape[2:],
                                   dtype=kv_flat.dtype)
            self.session = CrossWorldSession(
                src=src, dst=dst, G_src=G_src, G_dst=G_dst,
                direction=f"{src}_to_{dst}", t_start=t0,
                assignments=assignments, moves=moves, new_alloc=new_alloc,
                chunks=self._layer_chunks(chunk_layers),
                experts_chunks=[] if self.cfg.is_moe else None,
                kv_host=kv_host, kv_pages=sum(len(m) for m in moves),
                live_requests=len(live),
                plan_pause_s=time.perf_counter() - t0, caches=caches)
            return self.session

    def _stage_fn(self, view: tuple, lo: int, hi: int, W: int):
        """Jitted fused page gather for one source rank's flat (NE,) row:
        layers [lo, hi) of the pool, W planned pages, ONE kv_pack kernel
        launch -> (Lc, 2, W, page, Kh, dh). Cached per (view, layer range,
        pow2 plan width) so later chunks/switches reuse the executable."""
        key = (view, lo, hi, W)
        fn = self._stage_fns.get(key)
        if fn is None:
            Lc, pages, tail = hi - lo, view[2], view[3:]
            backend = self.backend

            def stage(kv_row, idx):
                pool = kv_row.reshape(view)[lo:hi].reshape(Lc * 2, pages, -1)
                out = gather_pages_rows(pool, idx, backend=backend)
                return out.reshape((Lc, 2, W) + tail)

            fn = self._stage_fns[key] = jax.jit(stage)
        return fn

    def _stage_kv_chunk(self, d: int, kv_flat, s, moves, lo: int,
                        hi: int) -> None:
        """One data group's planned page copies for KV layers [lo, hi).

        The fused replacement for the device_get-everything + per-page
        host loop (`copy_kv_pages_host`, kept as the oracle): planned
        pages are grouped per (source pool, destination pool) and pulled
        out of the LIVE device buffer by one fused kv_pack row gather per
        group, so only the moved pages ever cross to the host. The packed
        block then lands in the staged host buffer through the same
        full-head canonicalization: per-rank (EP) source pages already
        hold all K heads; a pooled (TP) source page is reassembled from
        its kv_rep representative ranks; per-rank dst lands whole pages
        in the owner pool, pooled dst lands each rank's kv_block slice."""
        if not moves:
            return
        src_s, dst_s = s.src, s.dst
        gs = group_info(self.cfg, s.G_src)
        gd = group_info(self.cfg, s.G_dst)
        sv = self.cc.view_shape(self.cfg, s.G_src, src_s)
        dv = self.cc.view_shape(self.cfg, s.G_dst, dst_s)
        dst_views = [s.kv_host[d, g].reshape(dv) for g in range(s.G_dst)]
        groups: dict = {}
        for spool, sp, dpool, dp in moves:
            # pooled sides ignore their pool id (reads span the
            # representative ranks; writes span every rank's view)
            key = (spool if src_s.kv_per_rank else 0,
                   dpool if dst_s.kv_per_rank else 0)
            if key not in groups:
                groups[key] = ([], [])
            groups[key][0].append(sp)
            groups[key][1].append(dp)
        for (spool, dpool), (sps, dps) in groups.items():
            n = len(sps)
            W = _pow2_pad(n)
            idx = np.zeros(W, np.int32)
            idx[:n] = sps
            idxj = jnp.asarray(idx)
            fn = self._stage_fn(sv, lo, hi, W)
            if src_s.kv_per_rank:
                data = np.asarray(fn(kv_flat[d, spool], idxj))[:, :, :n]
            else:
                data = np.concatenate(
                    [np.asarray(fn(kv_flat[d, g], idxj))[:, :, :n]
                     for g in range(0, s.G_src, gs.kv_rep)], axis=4)
            dparr = np.asarray(dps)
            if dst_s.kv_per_rank:
                dst_views[dpool][lo:hi, :, dparr] = data
            else:
                for g in range(s.G_dst):
                    kb = gd.kv_block(g)
                    dst_views[g][lo:hi, :, dparr] = \
                        data[..., kb:kb + gd.kv_local, :]

    def advance(self, kv_flat) -> bool:
        """Stage the next layer chunk on host (decode may keep running on
        the source in between). Returns True while chunks remain."""
        s = self.session
        assert s is not None and not s.done
        with span("switch.chunk", i=s.next_chunk):
            w_lo, w_hi, kv_lo, kv_hi = s.chunks[s.next_chunk]
            if self.cfg.is_moe and w_hi > w_lo:
                eg = s.dst.expert_group(s.G_dst, self.Dd * s.G_dst)
                s.experts_chunks.append(
                    pack_experts_host(self.cfg, self.moe_host, s.dst, eg,
                                      w_lo, w_hi))
            if s.kv_host is not None and kv_hi > kv_lo:
                for d in range(self.Dd):
                    self._stage_kv_chunk(d, kv_flat, s, s.moves[d],
                                         kv_lo, kv_hi)
            s.next_chunk += 1
        return not s.done

    def abort(self) -> SwitchStats:
        """Abandon the in-flight session: the staged host buffers become
        garbage and every planned destination page dies with the session's
        fresh allocators — the source world was never touched."""
        s = self.session
        assert s is not None, "no cross-world switch in progress"
        self.session = None
        return SwitchStats(direction=s.direction,
                           total_s=time.perf_counter() - s.t_start,
                           plan_s=s.plan_pause_s, kv_pages=s.kv_pages,
                           chunks=s.next_chunk,
                           live_requests=s.live_requests)

    def _delta_moves(self, live_ids) -> tuple:
        """Commit-time dirty-page moves per data group: pages decode wrote
        after the plan snapshot, plus pages allocated during the window
        (destination pages topped up here). CoW semantics mirror
        `SwitchExecutor._delta_pairs`."""
        s = self.session
        page = self.cc.page_size
        per = [[] for _ in range(self.Dd)]
        n = 0
        for a in s.assignments:
            r = a.req
            if r.rid not in live_ids or not r.pages:
                continue
            if (r.kv_len == a.snap_kv_len
                    and len(a.new_pages) >= len(r.pages)
                    and list(a.snap_pages) == r.pages):
                continue
            d = r.data_group
            dst_pool = max(a.new_owner, 0)
            while len(a.new_pages) < len(r.pages):
                a.new_pages.append(s.new_alloc[d].alloc(dst_pool, 1)[0])
            lo_idx = max(a.snap_kv_len - 1, 0) // page
            hi_idx = min(len(r.pages) - 1, max(r.kv_len - 1, 0) // page)
            for i in range(lo_idx, hi_idx + 1):
                cowed = (i < len(a.snap_pages)
                         and r.pages[i] != a.snap_pages[i])
                if cowed and s.new_alloc[d].refcount(
                        dst_pool, a.new_pages[i]) > 1:
                    s.new_alloc[d].release(dst_pool, [a.new_pages[i]])
                    a.new_pages[i] = s.new_alloc[d].alloc(dst_pool, 1)[0]
                per[d].append((r.pool_rank, r.pages[i], dst_pool,
                               a.new_pages[i]))
                n += 1
        return per, n

    def commit(self, live, kv_flat, dst_mesh):
        """Pause-phase: delta-copy dirty pages on host, apply metadata,
        device_put the staged buffers onto the destination mesh. Returns
        (experts', kv', alloc', caches', stats)."""
        s = self.session
        assert s is not None and s.done
        with span("switch.commit"):
            t_pause0 = time.perf_counter()
            live_ids = {r.rid for r in live}
            for a in s.assignments:
                if a.req.rid not in live_ids and a.new_pages:
                    s.new_alloc[a.req.data_group].release(
                        max(a.new_owner, 0), a.new_pages)
            delta_pages = 0
            if s.kv_host is not None:
                per, delta_pages = self._delta_moves(live_ids)
                if delta_pages:
                    for d in range(self.Dd):
                        self._stage_kv_chunk(d, kv_flat, s, per[d], 0, self.Lk)
            apply_assignments([a for a in s.assignments
                               if a.req.rid in live_ids])
            experts = None
            if self.cfg.is_moe:
                w13 = np.concatenate([c[0] for c in s.experts_chunks], axis=0)
                w2 = np.concatenate([c[1] for c in s.experts_chunks], axis=0)
                dst_ax = s.dst.expert_axes((self.da,), self.m)
                esh = NamedSharding(dst_mesh,
                                    P(None, dst_ax, None, None, None))
                # numpy straight to the shards: no full copy on one device
                experts = {"w13": jax.device_put(w13, esh),
                           "w2": jax.device_put(w2, esh)}
            kv = None
            if s.kv_host is not None:
                kv = jax.device_put(
                    s.kv_host, NamedSharding(dst_mesh, P(self.da, self.m)))
                jax.block_until_ready(kv)
            # prefix caches never migrate across worlds: the commit starts
            # with fresh empty caches over the destination allocators
            new_caches = s.caches
            if s.caches is not None:
                new_caches = [PrefixCache(s.new_alloc[d])
                              for d in range(self.Dd)]
            now = time.perf_counter()
            stats = SwitchStats(
                direction=s.direction, total_s=now - s.t_start,
                pause_s=s.plan_pause_s + (now - t_pause0),
                plan_s=s.plan_pause_s, kv_pages=s.kv_pages,
                delta_pages=delta_pages, chunks=len(s.chunks),
                live_requests=s.live_requests)
            out = (experts, kv, s.new_alloc, new_caches, stats)
            self.session = None
            return out
