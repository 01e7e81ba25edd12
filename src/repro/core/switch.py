"""The layout switch: weights reshard, paged-KV migration, request
redistribution (paper §3, §4.3) — generalized to any ordered pair of
registered `LayoutSpec`s.

A switch plan is a *slice-ownership diff* between the source and the
destination spec: the KV side diffs the two specs' `kv_view`s (same view ->
identity, no pages move; "ep" -> "tp" gathers per-rank pages into the pooled
head-sliced view and vice versa), and the weight side diffs the two specs'
`ExpertLayout`s (any src rank-major form -> any dst rank-major form,
including across different expert-group sizes, e.g. TP over the 8-rank
switch group -> EP over the full data x model mesh).

Three movers, all operating on the single resident copy:

  1. `reshard_experts`         — XLA path: jit with src in_shardings / dst
     out_shardings over unpack∘pack (XLA emits the collectives). This is the
     "staged collective" baseline (paper's NCCL path).
  2. `reshard_experts_direct`  — explicit shard_map path implementing the
     paper's two-stage plan: EP->TP = local permute (pack per-peer chunks)
     then one all_to_all; TP->EP = all_to_all then local interleave. One HBM
     read + one link pass per element (paper Table 1 "Direct"). Pure-EP
     groups only (the paper's case); hybrids fall back to the XLA path.
  3. `migrate_kv_*` + `plan_*` — paged-KV migration: host-side page-indexed
     work descriptors (paper Fig. 8) + a shard_map gather -> all_to_all ->
     scatter over the unified flat buffer's two views.

Request redistribution (host metadata):
  EP->TP: global ordered list (metadata "all-gather" is free under the
  single-controller model). TP->EP: deterministic longest-first greedy
  least-loaded partition — doubles as the straggler-rebalancing primitive.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core.layouts import EP, TP, get_layout, group_info
from repro.kernels.expert_reshard.ops import (interleave_shards,
                                              interleave_width_shards,
                                              pack_peer_chunks,
                                              pack_width_chunks)
from repro.kernels.kv_pack.ops import gather_pages_rows, scatter_pages_rows
from repro.models.common import ModelConfig
from repro.models.moe import (ExpertLayout, make_expert_layout, pack_experts,
                              pack_w13, unpack_experts, unpack_w13)
from repro.serving.kvcache import (CacheConfig, CacheMove, PageAllocator,
                                   PrefixCache, num_kv_layers, pages_needed)


# ---------------------------------------------------------------------------
# 0. Pairwise switch geometry (slice-ownership diff between two specs)
# ---------------------------------------------------------------------------

def kv_migration_direction(src, dst) -> str | None:
    """Device-mover direction for the KV side of a src->dst switch.

    None when both specs share a KV view (the unified buffer is already in
    the destination form — identity migration, no pages move). Otherwise
    "ep_to_tp" / "tp_to_ep" names the view conversion, independent of which
    *layouts* are switching (e.g. tpep -> ep is a "tp_to_ep" KV move).
    """
    src, dst = get_layout(src), get_layout(dst)
    if src.kv_view == dst.kv_view:
        return None
    return "ep_to_tp" if src.kv_view == "ep" else "tp_to_ep"


def pair_expert_layouts(cfg: ModelConfig, src, dst, G: int,
                        chips: int | None = None
                        ) -> tuple[ExpertLayout, ExpertLayout]:
    """Source/destination rank-major ExpertLayouts of a src->dst switch."""
    src, dst = get_layout(src), get_layout(dst)
    return (src.expert_layout(cfg, G, chips), dst.expert_layout(cfg, G, chips))


def _expert_cells(lay: ExpertLayout, chip: int, E: int, I: int) -> tuple:
    """(expert range, width-column range) chip `chip` holds under `lay`:
    rank chip % G, whose (ep, tp) block is (rank // tp_inner, rank %
    tp_inner), as `pack_experts` orders ranks."""
    r = chip % lay.G
    e, t = r // lay.tp_inner, r % lay.tp_inner
    ne, nw = E // lay.ep, I // lay.tp_inner
    return (e * ne, (e + 1) * ne), (t * nw, (t + 1) * nw)


def expert_bytes_sent(cfg: ModelConfig, src_lay: ExpertLayout,
                      dst_lay: ExpertLayout, chips: int,
                      itemsize: int) -> int:
    """Bytes of the expert store the busiest chip sends in a src->dst
    reshard. A cell (expert, width column) holds 3 * D elements a layer (its
    gate and up rows of w13, its column of w2); a chip sends the cells it
    holds in src and not in dst, which every other cell's new owner lacks.
    tp->ep on G chips: E*I/G cells held, (E/G)*(I/G) kept, so a chip sends
    E*I*(G-1)/G^2 cells; ep->tp the same."""
    E, I = cfg.num_experts, cfg.d_expert
    worst = 0
    for c in range(chips):
        (se, sw), (de, dw) = (_expert_cells(lay, c, E, I)
                              for lay in (src_lay, dst_lay))
        keep = (max(0, min(se[1], de[1]) - max(se[0], de[0]))
                * max(0, min(sw[1], dw[1]) - max(sw[0], dw[0])))
        worst = max(worst, (se[1] - se[0]) * (sw[1] - sw[0]) - keep)
    return worst * 3 * cfg.d_model * cfg.num_layers * itemsize


def kv_bytes_sent(cfg: ModelConfig, cc: CacheConfig, G: int, chips: int,
                  kv_dir: str, pages: int, itemsize: int) -> int:
    """Bytes of `pages` moved KV pages a chip sends, spread evenly over
    `chips` (the planner balances pages over ranks). A page holds K heads
    of K and V in every KV layer; tp->ep its owner lacks the K - K/G heads
    other ranks hold, ep->tp each of the G - 1 other ranks lacks its K/G
    (kv_local) heads."""
    gi = group_info(cfg, G)
    heads = (cfg.num_kv_heads - gi.kv_local if kv_dir == "tp_to_ep"
             else (G - 1) * gi.kv_local)
    per_head = num_kv_layers(cfg) * 2 * cc.page_size * cfg.dh * itemsize
    return -(-pages * heads * per_head // chips)


# ---------------------------------------------------------------------------
# 1+2. Expert-weight resharding
# ---------------------------------------------------------------------------

def _convert(w, src: ExpertLayout, dst: ExpertLayout, width_axis: int, E: int):
    return pack_experts(unpack_experts(w, src, width_axis, E), dst, width_axis)


def _convert13(w, src: ExpertLayout, dst: ExpertLayout, E: int):
    return pack_w13(unpack_w13(w, src, E), dst)


def make_reshard_experts(cfg: ModelConfig, mesh, src_layout: str,
                         dst_layout: str, *, model_axis: str = "model",
                         donate: bool = True, stacked: bool = True):
    """XLA-path reshard: moe params pytree src rank-major -> dst rank-major.

    Same-extent wrapper over `make_reshard_experts_pair` (the tp<->ep call
    sites and benchmarks). Compiled once; a switch calls the compiled
    executable (runtime preservation — paper §4.4).
    """
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    return make_reshard_experts_pair(cfg, mesh, src_layout, dst_layout,
                                     model_axis=model_axis,
                                     data_axes=data_axes, donate=donate,
                                     stacked=stacked)


def make_reshard_experts_pair(cfg: ModelConfig, mesh, src, dst, *,
                              model_axis: str = "model",
                              data_axes=("data",), donate: bool = True,
                              stacked: bool = True):
    """Generic XLA-path reshard between ANY ordered pair of registered
    layout specs — including pairs whose expert shards span different mesh
    extents (tp/ep over the G-rank switch group vs tpep over the full
    data x model mesh). XLA emits the collectives from the in/out sharding
    diff of unpack(src) ∘ pack(dst). Compiled once per pair (runtime
    preservation, paper §4.4); returns `build(moe_example)`.
    """
    E = cfg.num_experts
    G = mesh.shape[model_axis]
    chips = int(np.prod([mesh.shape[a]
                         for a in tuple(data_axes) + (model_axis,)]))
    src_s, dst_s = get_layout(src), get_layout(dst)
    src_lay, dst_lay = pair_expert_layouts(cfg, src_s, dst_s, G, chips)
    src_ax = src_s.expert_axes(data_axes, model_axis)
    dst_ax = dst_s.expert_axes(data_axes, model_axis)
    nd_extra = 1 if stacked else 0

    def spec(ndim, ax):
        s = [None] * ndim
        s[nd_extra] = ax               # rank-major dim over the spec's axes
        return P(*s)

    def fn(moe):
        out = dict(moe)
        cv13 = lambda w: _convert13(w, src_lay, dst_lay, E)
        cv2 = lambda w: _convert(w, src_lay, dst_lay, 2, E)
        if stacked:
            cv13, cv2 = jax.vmap(cv13), jax.vmap(cv2)
        out["w13"] = cv13(moe["w13"])
        out["w2"] = cv2(moe["w2"])
        return out

    def shardings(moe, ax):
        return {k: NamedSharding(mesh, spec(v.ndim, ax)
                                 if k in ("w13", "w2") else P())
                for k, v in moe.items()}

    def build(moe_example):
        in_sh = shardings(moe_example, src_ax)
        out_sh = shardings(jax.eval_shape(fn, moe_example), dst_ax)
        return jax.jit(fn, in_shardings=(in_sh,), out_shardings=out_sh,
                       donate_argnums=(0,) if donate else ())

    return build


def reshard_experts_direct(cfg: ModelConfig, w13, w2, direction: str,
                           axis: str, G: int, *,
                           backend: str | None = None):
    """Explicit shard_map body (pure EP groups): the paper's two-stage plan.

    Shapes (rank-local, leading G consumed by shard_map):
      TP: w13 (L, E, 2I/G, D),    w2 (L, E, D, I/G)
      EP: w13 (L, E/G, 2I, D),    w2 (L, E/G, D, I)

    EP->TP: permute-then-exchange. Pack my E/G experts into per-peer width
    chunks, one all_to_all delivers every rank its width slice of every
    expert, already in place.
    TP->EP: exchange-then-permute. all_to_all delivers contiguous expert
    blocks; the local permute interleaves received width shards into
    complete experts.
    """
    L, = w13.shape[:1]
    if direction == "ep_to_tp":
        E_loc, W2, D = w13.shape[1], w13.shape[2], w13.shape[3]
        I = W2 // 2
        # local permute = the fused pack kernels: L folds into the expert
        # dim, so the per-chunk stage is ONE launch per weight tensor
        s13 = pack_peer_chunks(w13.reshape(L * E_loc, W2, D), G,
                               backend=backend)
        s13 = s13.reshape(G, L, E_loc, 2 * (I // G), D)
        r13 = lax.all_to_all(s13, axis, split_axis=0, concat_axis=0,
                             tiled=True)
        # received (G_src, L, E_loc, 2I/G, D) -> (L, E = G*E_loc, 2I/G, D)
        n13 = jnp.moveaxis(r13, 0, 1).reshape(L, G * E_loc, 2 * (I // G), D)
        I2 = w2.shape[3]
        s2 = pack_width_chunks(w2.reshape(L * E_loc, D, I2), G,
                               backend=backend)
        s2 = s2.reshape(G, L, E_loc, D, I2 // G)
        r2 = lax.all_to_all(s2, axis, split_axis=0, concat_axis=0, tiled=True)
        n2 = jnp.moveaxis(r2.reshape(G, L, E_loc, D, I2 // G), 0, 1) \
            .reshape(L, G * E_loc, D, I2 // G)
        return n13, n2
    # tp_to_ep
    E, Wl, D = w13.shape[1], w13.shape[2], w13.shape[3]
    E_loc = E // G
    Il13 = Wl // 2
    # exchange first: send each peer its expert block (my width slice).
    # The send side is a pure block split (no permute) -> plain moveaxis.
    s13 = jnp.moveaxis(w13.reshape(L, G, E_loc, 2, Il13, D), 1, 0)
    r13 = lax.all_to_all(s13, axis, split_axis=0, concat_axis=0, tiled=True)
    # received (G_src, L, E_loc, 2, I/G, D): src s holds I-block s ->
    # the fused interleave kernel rebuilds complete experts per half
    n13 = interleave_shards(
        r13.reshape(G, L * E_loc, 2 * Il13, D),
        backend=backend).reshape(L, E_loc, 2 * G * Il13, D)
    Il = w2.shape[3]
    s2 = jnp.moveaxis(w2.reshape(L, G, E_loc, D, Il), 1, 0)
    r2 = lax.all_to_all(s2, axis, split_axis=0, concat_axis=0, tiled=True)
    n2 = interleave_width_shards(
        r2.reshape(G, L * E_loc, D, Il),
        backend=backend).reshape(L, E_loc, D, G * Il)
    return n13, n2


def make_reshard_experts_direct(cfg: ModelConfig, mesh, direction: str, *,
                                model_axis: str = "model",
                                backend: str | None = None):
    """jit(shard_map(...)) wrapper for the direct path (pure EP only)."""
    G = mesh.shape[model_axis]
    lay_ep = make_expert_layout(cfg.num_experts, G, EP)
    if not lay_ep.is_pure_ep:
        raise ValueError("direct reshard path requires pure EP (G | E); "
                         "use the XLA path for hybrid groups")
    rm = P(None, model_axis, None, None, None)   # (L, G, ...)

    # check_vma=False: the Pallas permute kernels have no replication
    # rule; the specs are fully explicit, nothing is replicated
    @functools.partial(shard_map, mesh=mesh, in_specs=(rm, rm),
                       out_specs=(rm, rm), check_vma=False)
    def body(w13, w2):
        # local (L, 1, ...) -> squeeze the G dim
        n13, n2 = reshard_experts_direct(
            cfg, w13.squeeze(1), w2.squeeze(1), direction, model_axis, G,
            backend=backend)
        return n13[:, None], n2[:, None]

    return jax.jit(body, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# 3. Request redistribution (host)
# ---------------------------------------------------------------------------

def partition_requests(requests, G: int) -> dict[int, list]:
    """TP->EP: deterministic longest-first greedy least-loaded partition
    (paper §3.2). Balances token and request counts together. Also used for
    straggler rebalancing."""
    order = sorted(requests, key=lambda r: (-r.kv_len, r.rid))
    load = [(0, 0, g) for g in range(G)]      # (tokens, nreq, rank)
    buckets: dict[int, list] = {g: [] for g in range(G)}
    import heapq
    heapq.heapify(load)
    for r in order:
        tok, n, g = heapq.heappop(load)
        buckets[g].append(r)
        heapq.heappush(load, (tok + r.kv_len, n + 1, g))
    return buckets


# ---------------------------------------------------------------------------
# 3b. Paged-KV migration plans (host descriptors, paper Fig. 8)
# ---------------------------------------------------------------------------

@dataclass
class KVPlan:
    direction: str                 # "ep_to_tp" | "tp_to_ep"
    src_pages: np.ndarray          # (G, Pmax) int32, padded with 0
    dst_pages: np.ndarray          # (G, Pmax) int32
    valid: np.ndarray              # (G, Pmax) bool
    n_pages: int = 0


@dataclass
class Assignment:
    """One live request's planned placement in the destination layout.

    Pure planning output: nothing on the request is touched until
    `apply_assignments` (monolithic switch: immediately; chunked switch:
    at commit, after the overlap window — decode keeps reading the old
    metadata in between).
    """
    req: object
    new_pages: list
    new_owner: int
    snap_kv_len: int               # kv_len when the plan was taken
    snap_pages: tuple = ()         # page list at plan time (CoW detection)


def pairs_to_plan(direction: str, per_rank: dict[int, list], G: int) -> KVPlan:
    """Rank-keyed (old_page, new_page) pair lists -> padded plan arrays.
    ep_to_tp rows are keyed by *source* rank, tp_to_ep rows by *destination*
    rank (the row semantics the device movers expect)."""
    pmax = max(1, max((len(v) for v in per_rank.values()), default=1))
    src = np.zeros((G, pmax), np.int32)
    dst = np.zeros((G, pmax), np.int32)
    val = np.zeros((G, pmax), bool)
    total = 0
    for g, pairs in per_rank.items():
        for i, (a, b) in enumerate(pairs):
            src[g, i], dst[g, i], val[g, i] = a, b, True
        total += len(pairs)
    return KVPlan(direction, src, dst, val, total)


def plan_switch(direction: str, requests, cfg: ModelConfig, cc: CacheConfig,
                new_alloc: PageAllocator, G: int, cache: PrefixCache = None
                ) -> tuple[KVPlan, list[Assignment], list[CacheMove]]:
    """Pure switch plan: allocate destination pages and build the page-pair
    descriptors without mutating any request.

    Refcount-aware: a physical page shared by several requests (prefix
    cache) is migrated ONCE per destination pool — later sharers `fork`
    the already-planned destination page instead of allocating a second
    copy. (A page whose sharers are partitioned onto different EP ranks is
    duplicated, once per rank — each rank's attention reads only its own
    pool.) When a `cache` is given, its entries are remapped too: entries
    whose pages already migrate with a live request ride along for free;
    cache-only pages are migrated best-effort (dropped if the destination
    pool is short).
    """
    per_rank: dict[int, list[tuple[int, int]]] = {g: [] for g in range(G)}
    assignments: list[Assignment] = []
    # (src_pool, src_page, dst_pool) -> dst_page (the dedup map)
    mapped: dict[tuple[int, int, int], int] = {}

    def migrate_page(src_pool: int, page: int, dst_pool: int,
                     row: int) -> int:
        """One physical copy per (src page, dst pool); sharers fork it."""
        key = (src_pool, page, dst_pool)
        dp = mapped.get(key)
        if dp is not None:
            new_alloc.fork(dst_pool, [dp])
            return dp
        dp = new_alloc.alloc(dst_pool, 1)[0]
        mapped[key] = dp
        per_rank[row].append((page, dp))
        return dp

    if direction == "ep_to_tp":
        for r in sorted(requests, key=lambda q: q.rid):
            if not r.pages:
                assignments.append(Assignment(r, [], -1, r.kv_len, ()))
                continue
            new_pages = [migrate_page(r.pool_rank, p, 0, r.pool_rank)
                         for p in r.pages]
            assignments.append(Assignment(r, new_pages, -1, r.kv_len,
                                          tuple(r.pages)))
    else:
        buckets = partition_requests([r for r in requests if r.pages], G)
        for g, reqs in buckets.items():
            for r in reqs:
                new_pages = [migrate_page(r.pool_rank, p, g, g)
                             for p in r.pages]
                assignments.append(Assignment(r, new_pages, g, r.kv_len,
                                              tuple(r.pages)))
    cache_moves: list[CacheMove] = []
    if cache is not None:
        cache_moves = _plan_cache_moves(direction, cache, new_alloc,
                                        mapped, per_rank, G)
    return pairs_to_plan(direction, per_rank, G), assignments, cache_moves


def _plan_cache_moves(direction: str, cache: PrefixCache,
                      new_alloc: PageAllocator, mapped: dict,
                      per_rank: dict, G: int) -> list[CacheMove]:
    """Remap prefix-cache entries into the destination pools.

    Pages already migrating with a live request are forked (zero extra
    copies); cache-only pages join the migration plan via `try_alloc` and
    the entry is dropped when the destination pool can't take them. Multi-
    page (full-prompt) entries must land wholly in ONE destination pool.
    """
    moves: list[CacheMove] = []
    dst_pools = [0] if direction == "ep_to_tp" else list(range(G))

    def target_pool(src_pool: int, pages) -> int:
        for dp in dst_pools:                 # prefer a pool already holding it
            if (src_pool, pages[0], dp) in mapped:
                return dp
        if direction == "ep_to_tp":
            return 0
        return max(dst_pools, key=lambda g: new_alloc.free_pages(g))

    for kind, pool, key, pages, plen in cache.entries():
        dpool = target_pool(pool, pages)
        row = pool if direction == "ep_to_tp" else dpool
        dst, taken = [], []
        for p in pages:
            mk = (pool, p, dpool)
            dp = mapped.get(mk)
            if dp is not None:
                new_alloc.fork(dpool, [dp])
            else:
                got = new_alloc.try_alloc(dpool, 1)
                if got is None:
                    break                    # pool short: drop the entry
                dp = got[0]
                mapped[mk] = dp
                per_rank[row].append((p, dp))
                taken.append((p, dp))
            dst.append(dp)
        if len(dst) < len(pages):            # roll back a partial entry
            new_alloc.release(dpool, dst)
            for p, dp in taken:
                del mapped[(pool, p, dpool)]
                per_rank[row].remove((p, dp))
            continue
        moves.append(CacheMove(kind, pool, key, tuple(pages), dpool,
                               tuple(dst), plen))
    return moves


def apply_assignments(assignments: list[Assignment]) -> None:
    """Commit the planned placement to the host request metadata (including
    the recorded release pool — pages now live in the destination pools)."""
    for a in assignments:
        a.req.pages = a.new_pages
        a.req.owner_rank = a.new_owner
        a.req.pool_rank = max(a.new_owner, 0)


# ---------------------------------------------------------------------------
# 3b'. Cross-world plans (ordered pairs with different device counts)
# ---------------------------------------------------------------------------

def affected_by_pool_loss(requests, data_group: int, rank: int,
                          per_rank: bool) -> list:
    """Requests whose KV touches pool `rank` of `data_group` — the cross-
    world ownership rule: dropping a pool hits its owner's requests under a
    per-rank view, or every request in the group under the pooled
    head-sliced view (each page shards every head across the ranks)."""
    hit = []
    for r in requests:
        if r.data_group != data_group:
            continue
        if per_rank and r.owner_rank != rank:
            continue
        hit.append(r)
    return hit


def plan_rank_shrink(requests, data_group: int, rank: int,
                     per_rank: bool) -> list:
    """Rank failure as a degenerate cross-world shrink: dst = src minus the
    dead pool. The dead pool's HBM is unrecoverable, so no pages move — the
    plan *is* the requeue set (teacher-forced re-prefill is the recovery
    mover). `distributed/elastic.py` routes through this instead of a
    bespoke classification."""
    return affected_by_pool_loss(requests, data_group, rank, per_rank)


def plan_cross_world(requests, cfg: ModelConfig, cc: CacheConfig,
                     new_alloc: PageAllocator, src, dst,
                     G_src: int, G_dst: int
                     ) -> tuple[list[tuple], list[Assignment]]:
    """Pure switch plan between layouts on DIFFERENT device counts.

    Returns `(moves, assignments)`: `moves` is a flat list of
    `(src_pool, src_page, dst_pool, dst_page)` host-copy descriptors. A
    cross-world pair has no common mesh for an all_to_all, so its KV moves
    bounce through the host (core.switch.copy_kv_pages_host) and the plan
    stays pool-indexed instead of the same-world (G, Pmax) arrays.
    Dedup/fork semantics match `plan_switch`: one physical copy per
    (src page, dst pool); later sharers fork the planned page. Prefix-cache
    entries do NOT ride along — a cross-world commit starts with fresh
    caches (the cache is an optimization, not state).
    """
    src_s, dst_s = get_layout(src), get_layout(dst)
    moves: list[tuple[int, int, int, int]] = []
    assignments: list[Assignment] = []
    mapped: dict[tuple[int, int, int], int] = {}

    def migrate_page(src_pool: int, page: int, dst_pool: int) -> int:
        key = (src_pool, page, dst_pool)
        dp = mapped.get(key)
        if dp is not None:
            new_alloc.fork(dst_pool, [dp])
            return dp
        dp = new_alloc.alloc(dst_pool, 1)[0]
        mapped[key] = dp
        moves.append((src_pool, page, dst_pool, dp))
        return dp

    if not dst_s.kv_per_rank:
        for r in sorted(requests, key=lambda q: q.rid):
            if not r.pages:
                assignments.append(Assignment(r, [], -1, r.kv_len, ()))
                continue
            new_pages = [migrate_page(r.pool_rank, p, 0) for p in r.pages]
            assignments.append(Assignment(r, new_pages, -1, r.kv_len,
                                          tuple(r.pages)))
    else:
        # pageless requests partition too: a shrink may leave a stale
        # owner_rank >= G_dst, so every request gets a valid dst owner
        buckets = partition_requests(list(requests), G_dst)
        for g, reqs in buckets.items():
            for r in reqs:
                new_pages = [migrate_page(r.pool_rank, p, g)
                             for p in r.pages]
                assignments.append(Assignment(r, new_pages, g, r.kv_len,
                                              tuple(r.pages)))
    return moves, assignments


def copy_kv_pages_host(cfg: ModelConfig, cc: CacheConfig, src, dst,
                       G_src: int, G_dst: int, src_host: np.ndarray,
                       dst_host: np.ndarray, moves, lo: int, hi: int) -> None:
    """Host-side cross-world KV page copies for KV layers [lo, hi).

    `src_host` / `dst_host` are ONE data group's flat per-rank buffers,
    shape (G, *rank_shape) — src a device_get snapshot, dst the staged buffer this
    writes into. Pages canonicalize through the full-head form: a per-rank
    (EP) source page already holds all K heads; a pooled (TP) source page
    is reassembled from its kv_rep representative ranks. Writes mirror the
    reads: per-rank dst lands whole pages in the owner pool; pooled dst
    lands each rank's `kv_block` head slice in every rank's view.
    """
    src_s, dst_s = get_layout(src), get_layout(dst)
    gs, gd = group_info(cfg, G_src), group_info(cfg, G_dst)
    sv = cc.view_shape(cfg, G_src, src_s)
    dv = cc.view_shape(cfg, G_dst, dst_s)
    src_views = [src_host[g].reshape(sv) for g in range(G_src)]
    dst_views = [dst_host[g].reshape(dv) for g in range(G_dst)]
    for spool, sp, dpool, dp in moves:
        if src_s.kv_per_rank:
            data = src_views[spool][lo:hi, :, sp]     # (Lc,2,page,K,dh)
        else:
            data = np.concatenate(
                [src_views[g][lo:hi, :, sp]           # (Lc,2,page,Kl,dh)
                 for g in range(0, G_src, gs.kv_rep)], axis=3)
        if dst_s.kv_per_rank:
            dst_views[dpool][lo:hi, :, dp] = data
        else:
            for g in range(G_dst):
                kb = gd.kv_block(g)
                dst_views[g][lo:hi, :, dp] = \
                    data[..., kb:kb + gd.kv_local, :]


def pack_experts_host(cfg: ModelConfig, moe_host: dict, dst, expert_G: int,
                      lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
    """Re-pack canonical (L, E, ...) expert weights into `dst`'s rank-major
    stored form for layers [lo, hi), off the serving meshes.

    The cross-world weight mover: the executor keeps the canonical host
    copy (experts are read-only in serving), so a chunk's destination
    shard is a fresh pack — no cross-mesh collective, no unpack.
    """
    lay = make_expert_layout(cfg.num_experts, expert_G,
                             get_layout(dst).expert_kind)
    w13 = jax.vmap(lambda w: pack_w13(w, lay))(
        jnp.asarray(moe_host["w13"][lo:hi]))
    w2 = jax.vmap(lambda w: pack_experts(w, lay, width_axis=2))(
        jnp.asarray(moe_host["w2"][lo:hi]))
    return np.asarray(w13), np.asarray(w2)


def plan_ep_to_tp(requests, cfg: ModelConfig, cc: CacheConfig,
                  tp_alloc: PageAllocator, G: int) -> KVPlan:
    """Live EP requests (owner_rank, pages) -> fresh TP pages. Rewrites
    request.pages / owner_rank in place (the monolithic-switch contract)."""
    plan, assignments, _ = plan_switch("ep_to_tp", requests, cfg, cc,
                                       tp_alloc, G)
    apply_assignments(assignments)
    return plan


def plan_tp_to_ep(requests, cfg: ModelConfig, cc: CacheConfig,
                  ep_alloc: PageAllocator, G: int) -> KVPlan:
    """Live TP requests -> per-rank EP pages via the greedy partition."""
    plan, assignments, _ = plan_switch("tp_to_ep", requests, cfg, cc,
                                       ep_alloc, G)
    apply_assignments(assignments)
    return plan


# ---------------------------------------------------------------------------
# 3c. Device KV transfer (shard_map over the flat buffer's two views)
# ---------------------------------------------------------------------------

def _kv_migrate_body(cfg: ModelConfig, cc: CacheConfig, G: int,
                     direction: str, pmax: int, lo: int, hi: int,
                     model_axis: str, backend: str | None = None):
    """Per-rank KV migration body for layers [lo, hi): three-stage
    gather -> all_to_all -> scatter from the source view into a provided
    destination buffer. Shared by the monolithic mover ((lo, hi) = (0, L)
    over a fresh zero buffer) and the chunked/delta movers (staged dst).

    Plans are (Dd, G, Pmax): ep_to_tp rows are rank-private sources
    (sharded gather, replicated scatter — every rank writes every source's
    pages into its own head-slice view); tp_to_ep rows are destination
    ranks. Invalid entries map to the null page 0 on both sides.
    """
    gi = group_info(cfg, G)
    ep_shape = cc.view_shape(cfg, G, EP)     # (L,2,pages_ep,page,K,dh)
    tp_shape = cc.view_shape(cfg, G, TP)     # (L,2,pages_tp,page,Kl,dh)
    _, _, _, page, K, dh = ep_shape
    Lc = hi - lo
    Kl, kv_rep = gi.kv_local, gi.kv_rep

    def ep_to_tp(kv_src, kv_dst, src_pages, dst_pages, valid):
        r = lax.axis_index(model_axis)
        pool = kv_src.reshape((1, 1) + ep_shape)[0, 0][lo:hi]
        sp = src_pages[0][r]                          # my row (Pmax,)
        # fused page pack: every (layer, K/V) row of the chunk in ONE launch
        gathered = gather_pages_rows(
            pool.reshape(Lc * 2, ep_shape[2], page * K * dh), sp,
            backend=backend).reshape(Lc, 2, pmax, page, K, dh)
        # heads -> per-dst slices: K = (G/kv_rep) blocks of Kl, tiled kv_rep
        g = gathered.reshape(Lc, 2, pmax, page, K // Kl, Kl, dh)
        g = jnp.moveaxis(g, 4, 0)                     # (K/Kl,Lc,2,P,page,Kl,dh)
        g = jnp.repeat(g, kv_rep, axis=0)             # (G, ...) dst-major
        recv = lax.all_to_all(g, model_axis, split_axis=0, concat_axis=0,
                              tiled=True)             # (G_src, Lc,2,P,page,Kl,dh)
        # scatter into the TP view: dst page ids from all srcs (replicated)
        dp = jnp.where(valid[0], dst_pages[0], 0)     # (G, Pmax); invalid->null
        flat_dst = dp.reshape(-1)
        moved = jnp.moveaxis(recv, 0, 2)              # (Lc,2,G,P,page,Kl,dh)
        moved = moved.reshape(Lc * 2, G * pmax, page * Kl * dh)
        dst = kv_dst.reshape((1, 1) + tp_shape)[0, 0]
        dst = scatter_pages_rows(
            dst.reshape(dst.shape[0] * 2, tp_shape[2], page * Kl * dh),
            flat_dst, moved, row0=lo * 2, backend=backend)
        return dst.reshape(kv_dst.shape)

    def tp_to_ep(kv_src, kv_dst, src_pages, dst_pages, valid):
        r = lax.axis_index(model_axis)
        pool = kv_src.reshape((1, 1) + tp_shape)[0, 0][lo:hi]
        # every rank holds head-slices of ALL pages; send dst d its pages
        sp = jnp.where(valid[0], src_pages[0], 0)     # (G, Pmax)
        gathered = gather_pages_rows(
            pool.reshape(Lc * 2, tp_shape[2], page * Kl * dh),
            sp.reshape(-1), backend=backend).reshape(
            Lc, 2, G, pmax, page, Kl, dh)
        send = jnp.moveaxis(gathered, 2, 0)           # (G_dst,Lc,2,P,page,Kl,dh)
        recv = lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                              tiled=True)             # (G_src, ...)
        # reassemble K heads from the G/kv_rep representative sources
        reps = recv[::kv_rep]                         # (K/Kl,Lc,2,P,page,Kl,dh)
        full = jnp.moveaxis(reps, 0, 4)               # (Lc,2,P,page,K/Kl,Kl,dh)
        full = full.reshape(Lc, 2, pmax, page, K, dh)
        dp = jnp.where(valid[0][r], dst_pages[0][r], 0)   # my new pages
        dst = kv_dst.reshape((1, 1) + ep_shape)[0, 0]
        dst = scatter_pages_rows(
            dst.reshape(dst.shape[0] * 2, ep_shape[2], page * K * dh),
            dp, full.reshape(Lc * 2, pmax, page * K * dh),
            row0=lo * 2, backend=backend)
        return dst.reshape(kv_dst.shape)

    return ep_to_tp if direction == "ep_to_tp" else tp_to_ep


def make_migrate_kv(cfg: ModelConfig, cc: CacheConfig, mesh, direction: str,
                    pmax: int, *, model_axis: str = "model",
                    data_axis: str = "data", backend: str | None = None):
    """Build the jitted monolithic KV migration for a fixed plan width
    `pmax`: the shared body over all layers, scattering into a fresh zero
    buffer; the source is donated (single resident copy)."""
    G = mesh.shape[model_axis]
    L = cc.view_shape(cfg, G, EP)[0]
    inner = _kv_migrate_body(cfg, cc, G, direction, pmax, 0, L, model_axis,
                             backend)

    def body(kv_flat, src_pages, dst_pages, valid):
        dst = jnp.zeros_like(kv_flat)
        return inner(kv_flat, dst, src_pages, dst_pages, valid)

    flat_spec = P(data_axis, model_axis)
    rep_spec = P(data_axis, None, None)          # plans replicated over model
    smapped = shard_map(body, mesh=mesh,
                        in_specs=(flat_spec, rep_spec, rep_spec, rep_spec),
                        out_specs=flat_spec, check_vma=False)
    return jax.jit(smapped, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# 4. Layer-chunked movers (overlapped switch, DESIGN.md §4.3)
#
# The monolithic movers above convert everything in one call — decode is
# paused for the whole transfer. The chunked movers below migrate a layer
# range [lo, hi) from the live *source* buffers into a staged *destination*
# buffer, so the SwitchExecutor can interleave decode steps (still running
# on the intact source) between chunks and only pause for a small dirty-page
# delta at commit.
# ---------------------------------------------------------------------------

def expert_pair_converters(cfg: ModelConfig, src_lay: ExpertLayout,
                           dst_lay: ExpertLayout):
    """Stacked (L, G_src, ...) -> (L, G_dst, ...) converters (vmapped)."""
    E = cfg.num_experts
    cv13 = jax.vmap(lambda w: _convert13(w, src_lay, dst_lay, E))
    cv2 = jax.vmap(lambda w: _convert(w, src_lay, dst_lay, 2, E))
    return cv13, cv2


def expert_pair_dst_struct(cfg: ModelConfig, src_lay: ExpertLayout,
                           dst_lay: ExpertLayout, experts):
    """ShapeDtypeStructs of the destination-layout expert store."""
    cv13, cv2 = expert_pair_converters(cfg, src_lay, dst_lay)
    return jax.eval_shape(
        lambda m: {"w13": cv13(m["w13"]), "w2": cv2(m["w2"])},
        {"w13": experts["w13"], "w2": experts["w2"]})


def make_reshard_experts_pair_chunk(cfg: ModelConfig, mesh, src, dst,
                                    lo: int, hi: int, *,
                                    model_axis: str = "model",
                                    data_axes=("data",)):
    """XLA-path chunk mover for any ordered spec pair: convert layers
    [lo, hi) of the stacked expert store into the (donated) destination
    buffer; src stays intact."""
    G = mesh.shape[model_axis]
    chips = int(np.prod([mesh.shape[a]
                         for a in tuple(data_axes) + (model_axis,)]))
    src_s, dst_s = get_layout(src), get_layout(dst)
    src_lay, dst_lay = pair_expert_layouts(cfg, src_s, dst_s, G, chips)
    cv13, cv2 = expert_pair_converters(cfg, src_lay, dst_lay)

    def sh(ax):
        return NamedSharding(mesh, P(None, ax, None, None, None))

    s_sh = sh(src_s.expert_axes(data_axes, model_axis))
    d_sh = sh(dst_s.expert_axes(data_axes, model_axis))

    def fn(w13_src, w2_src, w13_dst, w2_dst):
        return (w13_dst.at[lo:hi].set(cv13(w13_src[lo:hi])),
                w2_dst.at[lo:hi].set(cv2(w2_src[lo:hi])))

    return jax.jit(fn, in_shardings=(s_sh, s_sh, d_sh, d_sh),
                   out_shardings=(d_sh, d_sh), donate_argnums=(2, 3))


def make_reshard_experts_direct_chunk(cfg: ModelConfig, mesh, direction: str,
                                      lo: int, hi: int, *,
                                      model_axis: str = "model",
                                      backend: str | None = None):
    """Direct-path chunk mover (pure EP groups): the two-stage shard_map
    plan of `reshard_experts_direct`, restricted to layers [lo, hi)."""
    G = mesh.shape[model_axis]
    lay_ep = make_expert_layout(cfg.num_experts, G, EP)
    if not lay_ep.is_pure_ep:
        raise ValueError("direct reshard path requires pure EP (G | E); "
                         "use the XLA path for hybrid groups")
    rm = P(None, model_axis, None, None, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(rm, rm, rm, rm),
                       out_specs=(rm, rm), check_vma=False)
    def body(w13, w2, d13, d2):
        n13, n2 = reshard_experts_direct(
            cfg, w13[lo:hi].squeeze(1), w2[lo:hi].squeeze(1), direction,
            model_axis, G, backend=backend)
        return d13.at[lo:hi].set(n13[:, None]), d2.at[lo:hi].set(n2[:, None])

    return jax.jit(body, donate_argnums=(2, 3))


def make_migrate_kv_chunk(cfg: ModelConfig, cc: CacheConfig, mesh,
                          direction: str, pmax: int, lo: int, hi: int, *,
                          model_axis: str = "model", data_axis: str = "data",
                          backend: str | None = None):
    """Chunked KV migration: move plan pages of KV layers [lo, hi) from the
    live source buffer into the (donated) staged destination buffer.

    The shared `_kv_migrate_body`, with the source read-only (decode keeps
    appending to it between chunks) and the destination accumulating
    across calls. The same builder with (lo, hi) = (0, L) and a small pmax
    serves as the commit-time dirty-page delta pass.
    """
    G = mesh.shape[model_axis]
    body = _kv_migrate_body(cfg, cc, G, direction, pmax, lo, hi, model_axis,
                            backend)
    flat_spec = P(data_axis, model_axis)
    rep_spec = P(data_axis, None, None)
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(flat_spec, flat_spec, rep_spec, rep_spec, rep_spec),
        out_specs=flat_spec, check_vma=False)
    return jax.jit(smapped, donate_argnums=(1,))
