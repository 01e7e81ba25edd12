"""Unified KV buffer: view byte-parity, capacity accounting, allocator."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.layouts import EP, TP, group_info
from repro.serving.kvcache import (CacheConfig, PageAllocator,
                                   block_table_array, pages_needed)


@pytest.mark.parametrize("K,G", [(2, 4), (4, 4), (8, 4), (1, 8), (16, 8)])
def test_view_byte_parity(K, G):
    """Both layout views cover exactly the same flat element count."""
    cfg = get_config("internlm2-1.8b").reduced(num_kv_heads=K,
                                               num_heads=max(K, 8))
    cc = CacheConfig(page_size=8, pages_ep=12)
    ep = cc.view_shape(cfg, G, EP)
    tp = cc.view_shape(cfg, G, TP)
    assert int(np.prod(ep)) == int(np.prod(tp)) == cc.nelems(cfg, G)
    assert int(np.prod(cc.rank_shape(cfg, G))) == cc.nelems(cfg, G)


@pytest.mark.parametrize("G", [1, 4])
def test_rank_shape_is_lane_dense(G):
    """A rank's KV block is stored as (NE/128, 128) rows: no size-1 dim in
    the TPU's tiled minor pair, which would pad the pool in HBM."""
    cfg = get_config("mixtral-8x7b").replace(num_layers=4)
    cc = CacheConfig(page_size=16, pages_ep=288)
    rows, lane = cc.rank_shape(cfg, G)
    assert lane == 128 and rows * lane == cc.nelems(cfg, G)


@pytest.mark.parametrize("K,G,expected_ratio", [(4, 8, 2), (2, 8, 4),
                                                (8, 8, 1), (16, 8, 1)])
def test_capacity_penalty_matches_kv_replication(K, G, expected_ratio):
    """Paper: TP group capacity = EP / kv_rep."""
    cfg = get_config("internlm2-1.8b").reduced(num_kv_heads=K,
                                               num_heads=max(K, 8))
    cc = CacheConfig(page_size=8, pages_ep=64)
    cap_ep = cc.capacity_tokens(cfg, G, EP)
    cap_tp = cc.capacity_tokens(cfg, G, TP)
    gi = group_info(cfg, G)
    assert gi.kv_rep == expected_ratio
    # ratio approaches kv_rep as null-page overhead amortizes
    assert abs(cap_ep / cap_tp - expected_ratio) / expected_ratio < 0.2


def test_allocator_reuse_and_exhaustion():
    cfg = get_config("internlm2-1.8b").reduced(num_kv_heads=2, num_heads=4)
    cc = CacheConfig(page_size=8, pages_ep=8)
    al = PageAllocator(cc, cfg, 4, EP)
    got = al.alloc(1, 7)
    assert len(set(got)) == 7 and 0 not in got      # null page reserved
    with pytest.raises(MemoryError):
        al.alloc(1, 1)
    al.release(1, got[:3])
    assert al.free_pages(1) == 3


def test_block_table_array():
    from repro.serving.request import Request
    r = Request(rid=0, prompt=[1], max_new_tokens=1)
    r.slot, r.pages = 1, [5, 6]
    bt = block_table_array([r], slots=3, max_pages=4)
    assert bt.shape == (3, 4)
    assert bt[1, 0] == 5 and bt[1, 1] == 6 and bt[0, 0] == 0
    assert pages_needed(17, 8) == 3
