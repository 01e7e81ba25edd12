"""Paged KV cache: unified flat buffer + per-layout views + host allocators.

The TPU analogue of the paper's unified memory manager (§4.2): each rank owns
ONE flat element pool; the EP and TP layouts are *views* (reshapes) of the
same bytes:

  flat:    (Dd, G, NE/lane, lane)           sharded P("data", "model")
  EP view: (Dd, G, L, 2, pages_ep, page, K,  dh)   pages per model-rank
  TP view: (Dd, G, L, 2, pages_tp, page, Kl, dh)   pages shared across the
                                                    group, head-sliced per rank

pages_tp = pages_ep * K // Kl, so both views cover exactly NE elements.
Each rank's NE elements are stored as (NE/lane, lane) rows, lane = 128
where NE allows: a rank block shaped (1, 1, NE) would put a size-1 dim in
the TPU's tiled minor pair, padding the pool in HBM and making every
program that reshapes it slow to compile.
Group token capacity: EP = G*pages_ep*page, TP = pages_tp*page =
EP / kv_rep — the paper's KV-head-replication capacity penalty falls out of
the byte accounting.

Page 0 of every view is the NULL page: inactive decode slots write there.

The refcounted page lifecycle, prefix hashing, and the prefix-cache index
are PURE host logic and live in `serving/paging.py` (device-free so the
Scheduler can import them without pulling in jax); this module adds the
pieces that need model/layout geometry or a device: `CacheConfig` (view
shapes / capacities), the geometry-aware `PageAllocator` constructor, and
the jitted copy-on-write page mover. Everything is re-exported here, so
`kvcache` remains the one-stop import for device-side callers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from repro.core.layouts import LayoutSpec, get_layout, group_info
from repro.models.common import ModelConfig
from repro.serving.paging import (CacheMove, PagePoolAllocator, PrefixCache,
                                  block_table_array, full_prompt_hash,
                                  pages_needed, token_page_hashes)

__all__ = [
    "CacheConfig", "CacheMove", "COPY_W", "PageAllocator",
    "PagePoolAllocator", "PrefixCache", "block_table_array",
    "full_prompt_hash", "make_copy_pages", "num_kv_layers", "pages_needed",
    "token_page_hashes",
]


@dataclass(frozen=True)
class CacheConfig:
    page_size: int = 16
    pages_ep: int = 64            # per model-rank pages in the EP view
    max_pages_per_req: int = 32   # block-table width

    def nelems(self, cfg: ModelConfig, G: int) -> int:
        gi = group_info(cfg, G)
        L = num_kv_layers(cfg)
        return (L * 2 * self.pages_ep * self.page_size
                * cfg.num_kv_heads * cfg.dh)

    def rank_shape(self, cfg: ModelConfig, G: int) -> tuple[int, int]:
        """Stored shape of one rank's NE elements: lane-dense rows."""
        ne = self.nelems(cfg, G)
        lane = math.gcd(ne, 128)
        return (ne // lane, lane)

    def pages_tp(self, cfg: ModelConfig, G: int) -> int:
        gi = group_info(cfg, G)
        return self.pages_ep * cfg.num_kv_heads // gi.kv_local

    def view_shape(self, cfg: ModelConfig, G: int, layout: str) -> tuple:
        """Shape of the flat pool under `layout`'s KV view (spec.kv_view)."""
        gi = group_info(cfg, G)
        L = num_kv_layers(cfg)
        if get_layout(layout).kv_view == "ep":
            return (L, 2, self.pages_ep, self.page_size,
                    cfg.num_kv_heads, cfg.dh)
        return (L, 2, self.pages_tp(cfg, G), self.page_size,
                gi.kv_local, cfg.dh)

    def capacity_tokens(self, cfg: ModelConfig, G: int, layout: str) -> int:
        """Group-wide token capacity (excluding the null pages)."""
        if get_layout(layout).kv_view == "ep":
            return G * (self.pages_ep - 1) * self.page_size
        return (self.pages_tp(cfg, G) - 1) * self.page_size


def num_kv_layers(cfg: ModelConfig) -> int:
    """Attention sites that carry paged KV."""
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_every
    return cfg.num_layers


class PageAllocator(PagePoolAllocator):
    """Refcounted page allocator for one data group under one layout spec.

    spec.kv_per_rank: pages are per-model-rank pools (page ids local to the
    rank). Pooled views: one shared pool (page ids global to the group).
    The refcount lifecycle itself lives in `paging.PagePoolAllocator`; this
    subclass only derives the pool geometry from the layout spec.
    """

    def __init__(self, cc: CacheConfig, cfg: ModelConfig, G: int,
                 layout: str | LayoutSpec):
        self.spec = get_layout(layout)
        self.cc, self.layout, self.G = cc, self.spec, G
        if self.spec.kv_per_rank:
            super().__init__(G, cc.pages_ep, per_rank=True)
        else:
            super().__init__(1, cc.pages_tp(cfg, G), per_rank=False)


# ---------------------------------------------------------------------------
# Device page copy (copy-on-write mover; same-view, within each pool)
# ---------------------------------------------------------------------------

# fixed pair-width per compiled copy executable (the KV_BLOCK idiom):
# wider CoW bursts split into COPY_W blocks, so the serving loop compiles
# the copier exactly once per layout view
COPY_W = 4


def make_copy_pages(cfg: ModelConfig, cc: CacheConfig, mesh, layout, *,
                    pmax: int = COPY_W, model_axis: str = "model",
                    data_axis: str = "data"):
    """Jitted same-view page copy: dst_page[i] <- src_page[i] across all KV
    layers, within each rank's slice of the active view. Pair arrays are
    (Dd, G, pmax); invalid rows map to the null page (0 -> 0 self-copy).
    EP view: each rank applies only its own row (per-rank pools); TP view:
    callers replicate the pair row across the G dim (every rank holds the
    head-slice of every page)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    spec = get_layout(layout)
    G = mesh.shape[model_axis]
    view = cc.view_shape(cfg, G, spec)

    def body(kv_flat, src, dst, valid):
        r = lax.axis_index(model_axis)
        pool = kv_flat.reshape((1, 1) + view)[0, 0]
        sp = jnp.where(valid[0][r], src[0][r], 0)          # (pmax,)
        dp = jnp.where(valid[0][r], dst[0][r], 0)
        data = pool[:, :, sp]                              # (L,2,pmax,...)
        pool = pool.at[:, :, dp].set(data)
        return pool.reshape(kv_flat.shape)

    flat_spec = P(data_axis, model_axis)
    rep = P(data_axis, None, None)
    smapped = shard_map(body, mesh=mesh,
                        in_specs=(flat_spec, rep, rep, rep),
                        out_specs=flat_spec)
    return jax.jit(smapped, donate_argnums=(0,))
