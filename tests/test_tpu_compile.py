"""Compile every kernel entry point of the serve and switch paths for a
described (not attached) TPU v5e chip, at mixtral-8x7b widths in bf16.

Nothing runs: the TPU compiler installed with JAX lowers each kernel and
refuses what the chip would refuse (unaligned blocks, rank-1 blocks,
direct loads from HBM refs, VMEM overflow). The serve-path shapes are one
chip's; the switch movers use the per-rank shapes of a G=4 group. The
topology is described inside a fixture, so no process touches the TPU
library before a test of this file runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.expert_reshard.kernel import (
    interleave_shards_pallas, interleave_width_shards_pallas,
    pack_peer_chunks_pallas, pack_width_chunks_pallas)
from repro.kernels.kv_pack.kernel import (gather_pages_pallas,
                                          gather_pages_rows_pallas,
                                          scatter_pages_pallas,
                                          scatter_pages_rows_pallas)
from repro.kernels.moe_gemm.kernel import grouped_matmul_pallas
from repro.kernels.paged_attention.kernel import paged_attention_pallas

CFG = get_config("mixtral-8x7b")
QWEN = get_config("qwen3-235b-a22b")
BF = jnp.bfloat16
I32 = jnp.int32
G = 4                      # switch group of the four-chip host
PAGE, PAGES, MAXP = 16, 1024, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back here; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _attn(B, Sq):
    H, K, dh = CFG.num_heads, CFG.num_kv_heads, CFG.dh
    pool = ((PAGES, PAGE, K, dh), BF)
    return (lambda q, k, v, bt, kl, qo: paged_attention_pallas(
                q, k, v, bt, kl, q_offset=qo, window=CFG.sliding_window,
                interpret=False),
            [((B, Sq, H, dh), BF), pool, pool, ((B, MAXP), I32),
             ((B,), I32), ((B,), I32)])


def _gmm(C, W, Dc, E=CFG.num_experts):
    """A two-layer weight stack, its layer-index operand and the routed
    row counts."""
    return (lambda x, w, li, n: grouped_matmul_pallas(x, w, li, n,
                                                      interpret=False),
            [((E, C, Dc), BF), ((2, E, W, Dc), BF), ((1,), I32),
             ((E,), I32)])


# per-rank switch shapes: EP holds E/G whole experts, TP a 1/G width slice
D, I = CFG.d_model, CFG.d_expert
E_LOC = CFG.num_experts // G
M_EP = PAGE * CFG.num_kv_heads * CFG.dh            # one page, EP view
M_TP = PAGE * (CFG.num_kv_heads // G) * CFG.dh     # one page, TP view
N = 8                                              # planned pages / chunk
CASES = {
    "paged_attention_decode": lambda: _attn(8, 1),
    "paged_attention_mixed": lambda: _attn(8, 64),
    "grouped_matmul_w13": lambda: _gmm(64, 2 * I, D),
    "grouped_matmul_w2": lambda: _gmm(64, D, I),
    # the largest grouped GEMM of each cell: chat's rung 16 x chunk 64,
    # the rollout's 128 experts at C 1024, and the four-chip EP step's
    # receive slots for its 2 local experts at rung 16 (C2 = 512 x 4)
    "grouped_matmul_chat_w2_c1024": lambda: _gmm(1024, D, I),
    "grouped_matmul_rollout_w13_c1024": lambda: _gmm(
        1024, 2 * QWEN.d_expert, QWEN.d_model, QWEN.num_experts),
    "grouped_matmul_rollout_w2_c1024": lambda: _gmm(
        1024, QWEN.d_model, QWEN.d_expert, QWEN.num_experts),
    "grouped_matmul_ep_w2_c2048": lambda: _gmm(2048, D, I, E_LOC),
    "gather_pages": lambda: (
        lambda p, i: gather_pages_pallas(p, i, interpret=False),
        [((PAGES, PAGE, CFG.num_kv_heads, CFG.dh), BF), ((N,), I32)]),
    "scatter_pages": lambda: (
        lambda p, i, v: scatter_pages_pallas(p, i, v, interpret=False),
        [((PAGES, PAGE, CFG.num_kv_heads, CFG.dh), BF), ((N,), I32),
         ((N, PAGE, CFG.num_kv_heads, CFG.dh), BF)]),
    "gather_pages_rows": lambda: (
        lambda p, i: gather_pages_rows_pallas(p, i, interpret=False),
        [((2, PAGES, M_EP), BF), ((N,), I32)]),
    "scatter_pages_rows": lambda: (
        lambda p, i, v: scatter_pages_rows_pallas(p, i, v, row0=2,
                                                  interpret=False),
        [((4, PAGES, M_TP), BF), ((G * N,), I32), ((2, G * N, M_TP), BF)]),
    "pack_peer_chunks": lambda: (
        lambda w: pack_peer_chunks_pallas(w, G, interpret=False),
        [((E_LOC, 2 * I, D), BF)]),
    "pack_width_chunks": lambda: (
        lambda w: pack_width_chunks_pallas(w, G, interpret=False),
        [((E_LOC, D, I), BF)]),
    "interleave_shards": lambda: (
        lambda c: interleave_shards_pallas(c, interpret=False),
        [((G, E_LOC, 2 * (I // G), D), BF)]),
    "interleave_width_shards": lambda: (
        lambda c: interleave_width_shards_pallas(c, interpret=False),
        [((G, E_LOC, D, I // G), BF)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_cache):
    fn, args = CASES[name]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_mixed_step_reads_expert_stacks_in_place(topo, no_cache,
                                                 monkeypatch):
    """The whole serve step at mixtral-8x7b widths, 2 layers, one chip,
    rung 4 x chunk 64: the layer scan hands the grouped GEMM the stacked
    expert weights, so the optimised HLO slices no layer's w13 or w2 out
    of them and the step's temporaries stay below one layer's w13."""
    import re

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.kernels import dispatch
    from repro.serving.kvcache import CacheConfig
    from repro.serving.steps import (_pack_specs_for, _params_like,
                                     build_decode_pack, build_mixed_step)

    cfg = CFG.replace(num_layers=2, sliding_window=0,
                      capacity_factor=CFG.num_experts / CFG.top_k)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    cc = CacheConfig(page_size=PAGE, pages_ep=64, max_pages_per_req=16)
    B, Sq = 4, 64
    # the process runs on the CPU; the kernels are compiled for the chip
    monkeypatch.setattr(dispatch, "resolve_backend",
                        lambda *a, **k: "pallas")
    step = build_mixed_step(cfg, mesh, "tp", cc, B, Sq, donate=False)
    pack = jax.eval_shape(lambda p: build_decode_pack(cfg, p, "tp", 1),
                          _params_like(cfg, "tp", 1, 1))
    specs = _pack_specs_for(cfg, "tp", 1, 1, "model", ("data", "model"))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pack = jax.tree.map(lambda a, s: arg(a.shape, a.dtype, s), pack, specs,
                        is_leaf=lambda x: isinstance(x, P))
    compiled = step.lower(
        pack, arg((1, 1, *cc.rank_shape(cfg, 1)), BF, P("data", "model")),
        arg((1, B, Sq), I32, P("data", None, None)),
        arg((1, B), I32, P("data", None)), arg((1, B), I32, P("data", None)),
        arg((1, B, cc.max_pages_per_req), I32, P("data", None, None)),
        arg((2,), jnp.uint32, P()), arg((1, B), I32, P("data", None)),
        arg((1, B), I32, P("data", None))).compile()
    moe = pack["layers"]["moe"]
    layer_bytes = {int(np.prod(w.shape[1:])) * w.dtype.itemsize
                   for w in (moe["w13"], moe["w2"])}
    itemsize = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    for dt, dims in re.findall(r"= (\w+)\[([\d,]*)\]\{[^}]*\} dynamic-slice",
                               compiled.as_text()):
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        assert n * itemsize.get(dt, 0) not in layer_bytes, (dt, dims)
    w13_bytes = max(layer_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < w13_bytes
