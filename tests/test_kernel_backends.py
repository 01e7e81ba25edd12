"""Cross-backend kernel dispatch tests (DESIGN.md §14).

One `resolve_backend` governs all four kernel packages; these tests pin

  * the resolution matrix (explicit choice x platform),
  * ref vs pallas-interpret parity THROUGH the ops.py dispatchers for all
    four kernels, over hypothesis-drawn shapes: GQA ratios, Sq > 1 mixed
    rows, sliding windows, ragged per-expert token counts including
    zero-token experts, and non-divisible page counts,
  * the serving integration: `moe_backend="interpret"` decode tokens match
    the einsum path exactly (fp32) including across a live tp->ep chunked
    switch, and the chunked switch staging actually routes through the
    fused kv_pack / expert_reshard ops (dispatch trace counters).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # offline fallback (tests/_hypothesis_compat.py)
    from tests._hypothesis_compat import given, settings, strategies as st

from repro.kernels import dispatch
from repro.launch.mesh import make_mesh

HYP = dict(deadline=None, max_examples=10)


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# resolution matrix
# ---------------------------------------------------------------------------
def test_resolve_backend_matrix():
    rb = dispatch.resolve_backend
    # auto: kernel on TPU, ref elsewhere
    assert rb(None, platform="tpu") == "pallas"
    assert rb(None, platform="cpu") == "ref"
    # explicit ref / interpret are honoured everywhere
    for plat in ("tpu", "cpu"):
        assert rb("ref", platform=plat) == "ref"
        assert rb("interpret", platform=plat) == "interpret"
    # the compiled kernel exists only on the chip: no silent substitute
    assert rb("pallas", platform="tpu") == "pallas"
    with pytest.raises(ValueError):
        rb("pallas", platform="cpu")
    for bad in ("mystery", "kernel"):
        with pytest.raises(ValueError):
            rb(bad, platform="cpu")


def test_force_ref_env_unifies_all_dispatchers(monkeypatch):
    """Every kernel package resolves auto through the one shared resolver
    (ref off the chip), and the retired REPRO_FORCE_REF switch no longer
    changes what any dispatcher picks."""
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    assert dispatch.resolve_backend(None, platform="tpu") == "pallas"
    dispatch.reset_counts()
    from repro.kernels.expert_reshard.ops import pack_peer_chunks
    from repro.kernels.kv_pack.ops import gather_pages
    from repro.kernels.moe_gemm.ops import grouped_matmul
    from repro.kernels.paged_attention.ops import paged_attention
    grouped_matmul(jnp.ones((2, 4, 8)), jnp.ones((1, 2, 4, 8)), 0,
                   jnp.full((2,), 4, jnp.int32))
    gather_pages(jnp.ones((4, 2, 1, 4)), jnp.array([0, 1]))
    pack_peer_chunks(jnp.ones((2, 8, 4)), 2)
    paged_attention(jnp.ones((1, 1, 2, 4)), jnp.ones((4, 2, 2, 4)),
                    jnp.ones((4, 2, 2, 4)), jnp.zeros((1, 2), jnp.int32),
                    jnp.array([2]), q_offset=jnp.array([1]))
    for op in ("moe_gemm.grouped_matmul", "kv_pack.gather_pages",
               "expert_reshard.pack_peer_chunks",
               "paged_attention.paged_attention"):
        assert dispatch.calls(op, "ref") >= 1, (op, dict(dispatch.COUNTS))
        assert dispatch.calls(op, "interpret") == 0
        assert dispatch.calls(op, "pallas") == 0


# ---------------------------------------------------------------------------
# per-kernel ref vs interpret parity through the dispatchers
# ---------------------------------------------------------------------------
@settings(**HYP)
@given(E=st.integers(1, 6), C=st.sampled_from([4, 17, 64]),
       D=st.sampled_from([8, 48]), W=st.sampled_from([8, 96]),
       zero_experts=st.booleans(), seed=st.integers(0, 50))
def test_grouped_matmul_backends_ragged(E, C, D, W, zero_experts, seed):
    """Ref vs interpret through ops.grouped_matmul with ragged per-expert
    token counts: each expert's capacity bucket is only partially filled,
    some experts receive ZERO tokens (all-zero rows) — the serving shape."""
    from repro.kernels.moe_gemm.ops import grouped_matmul
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (E, C, D), jnp.float32)
    w = jax.random.normal(ks[1], (E, W, D), jnp.float32)
    counts = jax.random.randint(ks[2], (E,), 0, C + 1)
    if zero_experts:
        counts = counts.at[0].set(0)
    # zero out the unfilled tail of each expert's bucket (ragged loads)
    mask = (jnp.arange(C)[None, :] < counts[:, None]).astype(jnp.float32)
    x = x * mask[..., None]
    r = grouped_matmul(x, w[None], 0, counts, backend="ref")
    k = grouped_matmul(x, w[None], 0, counts, backend="interpret")
    np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                               rtol=1e-5, atol=1e-4)
    # zero-token experts must produce exactly zero output in both
    if zero_experts:
        assert not np.asarray(r[0]).any() and not np.asarray(k[0]).any()


@settings(**HYP)
@given(R=st.sampled_from([2, 6]), pages=st.integers(4, 20),
       n=st.integers(1, 8), row0=st.integers(0, 2), seed=st.integers(0, 50))
def test_kv_pack_rows_backends(R, pages, n, row0, seed):
    """Row-batched page gather/scatter (the fused switch-staging movers):
    ref vs interpret bitwise, including scatter at a row offset into a
    taller destination (the layer-chunk [lo, hi) write)."""
    from repro.kernels.kv_pack.ops import (gather_pages_rows,
                                           scatter_pages_rows)
    M = 24
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(ks[0], (R, pages, M), jnp.float32)
    idx = jax.random.randint(ks[1], (n,), 0, pages)
    g_r = gather_pages_rows(pool, idx, backend="ref")
    g_i = gather_pages_rows(pool, idx, backend="interpret")
    np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_i))
    np.testing.assert_array_equal(np.asarray(g_r),
                                  np.asarray(pool)[:, np.asarray(idx)])
    if len(set(np.asarray(idx).tolist())) == n:    # scatter defined: no dups
        dst = jax.random.normal(ks[2], (R + row0 + 1, pages, M), jnp.float32)
        vals = g_r + 1.0
        s_r = scatter_pages_rows(dst, idx, vals, row0=row0, backend="ref")
        s_i = scatter_pages_rows(dst, idx, vals, row0=row0,
                                 backend="interpret")
        np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_i))
        # untouched rows/pages preserved
        keep = np.ones(pages, bool)
        keep[np.asarray(idx)] = False
        np.testing.assert_array_equal(np.asarray(s_r)[:, keep],
                                      np.asarray(dst)[:, keep])
        np.testing.assert_array_equal(np.asarray(s_r)[:row0],
                                      np.asarray(dst)[:row0])


@settings(**HYP)
@given(E_loc=st.integers(1, 4), I=st.sampled_from([8, 24, 48]),
       D=st.sampled_from([4, 12]), G=st.sampled_from([2, 4]),
       seed=st.integers(0, 50))
def test_expert_reshard_width_backends(E_loc, I, D, G, seed):
    """Down-proj (width-last) permute pair: ref vs interpret bitwise and
    pack->interleave roundtrip identity."""
    if I % G:
        return
    from repro.kernels.expert_reshard.ops import (interleave_width_shards,
                                                  pack_width_chunks)
    w2 = jax.random.normal(jax.random.PRNGKey(seed), (E_loc, D, I),
                           jnp.float32)
    p_r = pack_width_chunks(w2, G, backend="ref")
    p_i = pack_width_chunks(w2, G, backend="interpret")
    np.testing.assert_array_equal(np.asarray(p_r), np.asarray(p_i))
    i_r = interleave_width_shards(p_r, backend="ref")
    i_i = interleave_width_shards(p_r, backend="interpret")
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_i))
    np.testing.assert_array_equal(np.asarray(i_r), np.asarray(w2))


@settings(**HYP)
@given(B=st.integers(1, 3), Sq=st.sampled_from([1, 2, 5]),
       HK=st.sampled_from([(4, 1), (4, 4), (8, 2), (6, 3)]),
       page=st.sampled_from([2, 4]), maxp=st.sampled_from([3, 5, 8]),
       window=st.sampled_from([0, 3, 7]), seed=st.integers(0, 100))
def test_paged_attention_backends(B, Sq, HK, page, maxp, window, seed):
    """Ref vs interpret through ops.paged_attention: GQA ratios (H/K in
    {1, 2, 4}), mixed rows (Sq > 1), sliding window, and page counts NOT
    divisible by page_chunk (the block-table padding + early-exit path).
    Every row has >= 1 valid position (rows with none are unspecified)."""
    from repro.kernels.paged_attention.ops import paged_attention
    H, K = HK
    dh, pages = 8, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (pages, page, K, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (pages, page, K, dh), jnp.float32)
    bt = jax.random.randint(ks[3], (B, maxp), 0, pages)
    # kv_len >= q_off + Sq so every query row attends to itself
    q_off = jnp.minimum(jnp.arange(B) * 3, maxp * page - Sq)
    kv_lens = jnp.minimum(q_off + Sq + jnp.arange(B) * 5, maxp * page)
    r = paged_attention(q, kp, vp, bt, kv_lens, q_offset=q_off,
                        window=window, page_chunk=2, backend="ref")
    k = paged_attention(q, kp, vp, bt, kv_lens, q_offset=q_off,
                        window=window, page_chunk=2, backend="interpret")
    np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# serving integration: moe_backend parity + fused switch staging
# ---------------------------------------------------------------------------
def _serve(cfg, mesh, *, moe_backend=None, switch_backend=None,
           switch_to=None, chunk_layers=1, warm=False):
    from repro.core.policy import PolicyConfig
    from repro.serving.engine import EngineConfig, MoebiusEngine
    from repro.serving.kvcache import CacheConfig
    from repro.serving.request import Request
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(
        cfg, mesh, CacheConfig(page_size=4, pages_ep=64,
                               max_pages_per_req=16),
        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8), prefill_chunk=8,
                          temperature=0.0, policy=pol, seed=0,
                          chunk_layers=chunk_layers, moe_backend=moe_backend,
                          switch_backend=switch_backend, warm_switches=warm))
    if warm:
        eng.warmup()
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                           max_new_tokens=int(rng.integers(4, 9)),
                           arrival_s=0.0))
    switched = switch_to is None
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if not switched and eng.running:
            eng.execute_switch(switch_to)
            switched = True
        eng.step()
        i += 1
        assert i < 1000
    assert switched
    return {r.rid: tuple(r.output) for r in eng.finished}


def test_moe_backend_decode_parity_across_switch(tiny_moe, mesh11):
    """moe_backend="interpret" greedy decode == einsum path, token for token,
    with and without a live tp->ep chunked switch in the middle (fp32
    compute: byte-identical per DESIGN.md §14)."""
    for sw in (None, "ep"):
        ref = _serve(tiny_moe, mesh11, moe_backend="ref", switch_to=sw)
        ker = _serve(tiny_moe, mesh11, moe_backend="interpret", switch_to=sw)
        assert ref == ker, f"kernel MoE diverged (switch={sw})"


def test_switch_staging_routes_through_fused_kernels(tiny_moe, mesh11):
    """The chunked switch staging path must trace through the fused
    kv_pack row movers and the expert_reshard permute kernels — not
    generic per-page gathers (dispatch records at trace time)."""
    dispatch.reset_counts()
    _serve(tiny_moe, mesh11, switch_backend="ref", switch_to="ep",
           warm=True)
    for op in ("kv_pack.gather_pages_rows", "kv_pack.scatter_pages_rows",
               "expert_reshard.interleave_shards",
               "expert_reshard.interleave_width_shards"):
        assert dispatch.calls(op, "ref") >= 1, (op, dict(dispatch.COUNTS))


def test_warm_switches_precompiles_movers(tiny_moe, mesh11):
    """warm_switches=True compiles the chunked movers during warmup: the
    live switch must not trace any NEW fused-op call (executable reuse,
    paper §4.4)."""
    from repro.core.policy import PolicyConfig
    from repro.serving.engine import EngineConfig, MoebiusEngine
    from repro.serving.kvcache import CacheConfig
    from repro.serving.request import Request
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(
        tiny_moe, mesh11,
        CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=16),
        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8), prefill_chunk=8,
                          temperature=0.0, policy=pol, seed=0,
                          chunk_layers=1, switch_backend="ref",
                          warm_switches=True))
    eng.warmup()
    dispatch.reset_counts()
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 6)),
                           max_new_tokens=5, arrival_s=0.0))
    switched = False
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if not switched and eng.running:
            eng.execute_switch("ep")
            switched = True
        eng.step()
        i += 1
        assert i < 1000
    assert switched
    # pre-copy + commit reused the warmed executables: no re-trace of the
    # chunk movers (the only allowed trace is none at all — same plan
    # width 8 and same layer chunks as the warm dry-run)
    assert dispatch.calls("kv_pack.gather_pages_rows") == 0, \
        dict(dispatch.COUNTS)
    assert dispatch.calls("expert_reshard.interleave_shards") == 0, \
        dict(dispatch.COUNTS)
