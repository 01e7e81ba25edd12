"""LayoutSpec registry + pairwise switch geometry (host-only, single device).

The N-layout runtime's contracts: spec resolution and string compat, frozen
specs, batch/KV/expert geometry, the pairwise KV-view diff, and cost-model
scoring of the hybrid tpep layout.
"""
import pytest

from repro.configs import get_config
from repro.core.layouts import (EP, TP, TPEP, LayoutSpec, get_layout,
                                register_layout, registered_layouts)
from repro.core.switch import kv_migration_direction, pair_expert_layouts
from repro.serving.kvcache import CacheConfig, PageAllocator


def test_registry_resolution_and_str_compat():
    assert get_layout("tp") is TP and get_layout(TP) is TP
    assert get_layout("tpep") is TPEP
    assert TP == "tp" and isinstance(TP, str)      # legacy call sites
    assert {"ep": 1}[EP] == 1                      # dict-key compat
    assert set(registered_layouts()) >= {TP, EP, TPEP}
    with pytest.raises(KeyError):
        get_layout("nope")
    with pytest.raises(ValueError):
        register_layout(LayoutSpec(
            "tp", slots_sharded=False, kv_view="tp", dense_tp=True,
            expert_kind="tp", expert_full_mesh=False))


def test_spec_is_frozen():
    with pytest.raises(AttributeError):
        TP.kv_view = "ep"


def test_batch_slot_geometry():
    G = 4
    # ladder rounding: slot-sharded and full-mesh layouts need G | B
    assert TP.decode_ladder((3, 8), G) == (3, 8)
    assert EP.decode_ladder((3, 8), G) == (4, 8)
    assert TPEP.decode_ladder((2, 6), G) == (4, 8)
    # full-mesh experts split each prefill chunk 1/G per rank
    assert TPEP.prefill_quantum(G) == G and TP.prefill_quantum(G) == 1
    assert EP.prefill_quantum(G) == 1


def test_kv_ownership_and_capacity():
    cfg = get_config("internlm2-1.8b").reduced(num_kv_heads=2, num_heads=8)
    cc = CacheConfig(page_size=8, pages_ep=64)
    G = 8                                          # kv_rep = 8 // 2 = 4
    cap_ep = cc.capacity_tokens(cfg, G, EP)
    assert EP.kv_capacity_tokens(cfg, G, cap_ep) == cap_ep
    assert TP.kv_capacity_tokens(cfg, G, cap_ep) == cap_ep // 4
    assert TPEP.kv_capacity_tokens(cfg, G, cap_ep) == cap_ep // 4
    # allocator pooling follows the spec: per-rank pools vs one shared pool
    assert len(PageAllocator(cc, cfg, G, EP).free) == G
    assert len(PageAllocator(cc, cfg, G, TP).free) == 1
    assert len(PageAllocator(cc, cfg, G, "tpep").free) == 1
    # tpep shares the pooled head-sliced KV view with tp
    assert cc.view_shape(cfg, G, TPEP) == cc.view_shape(cfg, G, TP)


def test_pairwise_kv_direction_matrix():
    """The switch plan is a kv_view diff: same view -> identity."""
    assert kv_migration_direction(TP, TPEP) is None
    assert kv_migration_direction(TPEP, TP) is None
    assert kv_migration_direction(EP, TP) == "ep_to_tp"
    assert kv_migration_direction(EP, TPEP) == "ep_to_tp"
    assert kv_migration_direction(TP, EP) == "tp_to_ep"
    assert kv_migration_direction(TPEP, EP) == "tp_to_ep"


def test_pair_expert_layouts_span_mesh():
    cfg = get_config("mixtral-8x7b").reduced(num_experts=8)
    src, dst = pair_expert_layouts(cfg, TP, TPEP, G=4, chips=8)
    assert src.G == 4 and src.tp_inner == 4     # width slices over the group
    assert dst.G == 8 and dst.ep == 8           # whole experts, full mesh
    src, dst = pair_expert_layouts(cfg, EP, TP, G=4)
    assert src.ep == 4 and dst.tp_inner == 4


def test_cost_model_scores_every_registered_layout():
    from repro.core.cost_model import decode_step_time
    cfg = get_config("qwen3-235b-a22b")
    for layout in registered_layouts():
        t = decode_step_time(cfg, layout, 256, 2048, G=8, chips=64)
        assert 0 < t["total"] < 10, (layout, t["total"])
