"""Per-kernel interpret-mode validation vs pure-jnp oracles, with
hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # offline fallback (tests/_hypothesis_compat.py)
    from tests._hypothesis_compat import given, settings, strategies as st

from repro.kernels.expert_reshard.kernel import (interleave_shards_pallas,
                                                 pack_peer_chunks_pallas)
from repro.kernels.expert_reshard.ref import (interleave_shards_ref,
                                              pack_peer_chunks_ref)
from repro.kernels.kv_pack.kernel import (gather_pages_pallas,
                                          scatter_pages_pallas)
from repro.kernels.kv_pack.ref import gather_pages_ref, scatter_pages_ref
from repro.kernels.moe_gemm.kernel import grouped_matmul_pallas
from repro.kernels.moe_gemm.ref import grouped_matmul_ref
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models.common import flash_attention

HYP = dict(deadline=None, max_examples=12)


@settings(**HYP)
@given(B=st.integers(1, 4), Sq=st.sampled_from([1, 3, 4]),
       H=st.sampled_from([4, 8]), K=st.sampled_from([1, 2, 4]),
       page=st.sampled_from([4, 8]), dtype=st.sampled_from(["f32", "bf16"]),
       window=st.sampled_from([0, 8]), seed=st.integers(0, 100))
def test_paged_attention_matches_ref(B, Sq, H, K, page, dtype, window, seed):
    if H % K:
        K = 1
    dh, pages, maxp = 16, 12, 6
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dt)
    kp = jax.random.normal(ks[1], (pages, page, K, dh), dt)
    vp = jax.random.normal(ks[2], (pages, page, K, dh), dt)
    bt = jax.random.randint(ks[3], (B, maxp), 0, pages)
    kv_lens = jnp.minimum(jnp.arange(B) * 7 + Sq + 2, maxp * page)
    q_off = kv_lens - Sq
    ref = paged_attention_ref(q, kp, vp, bt, kv_lens, q_offset=q_off,
                              window=window, page_chunk=2)
    out = paged_attention_pallas(q, kp, vp, bt, kv_lens, q_offset=q_off,
                                 window=window, page_chunk=2, interpret=True)
    tol = 1e-5 if dtype == "f32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_matches_dense_flash():
    """Contiguous pages == dense flash attention (oracle of the oracle)."""
    B, Sq, H, K, dh, page, maxp = 2, 4, 8, 2, 16, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (maxp, page, K, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (maxp, page, K, dh), jnp.float32)
    bt = jnp.arange(maxp)[None, :].repeat(B, 0)
    kv_lens = jnp.array([20, 44])
    q_off = kv_lens - Sq
    ref = paged_attention_ref(q, kp, vp, bt, kv_lens, q_offset=q_off)
    kd = kp.reshape(1, -1, K, dh).repeat(B, 0)
    vd = vp.reshape(1, -1, K, dh).repeat(B, 0)
    for b in range(B):
        fl = flash_attention(q[b:b + 1], kd[b:b + 1], vd[b:b + 1],
                             causal=True, q_offset=int(q_off[b]),
                             kv_len=kv_lens[b:b + 1], block_k=16)
        np.testing.assert_allclose(np.asarray(ref[b]), np.asarray(fl[0]),
                                   rtol=1e-5, atol=1e-5)


@settings(**HYP)
@given(E=st.integers(1, 6), C=st.sampled_from([8, 65, 128]),
       D=st.sampled_from([32, 96]), W=st.sampled_from([16, 160]),
       dtype=st.sampled_from(["f32", "bf16"]), seed=st.integers(0, 50))
def test_moe_gemm_matches_ref(E, C, D, W, dtype, seed):
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (E, C, D), dt)
    w = jax.random.normal(ks[1], (E, W, D), dt)
    out = grouped_matmul_pallas(x, w[None], 0, jnp.full((E,), C),
                                block_c=64, block_w=64, interpret=True)
    ref = grouped_matmul_ref(x, w)
    tol = 1e-4 if dtype == "f32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * D)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("shape", ["w13", "w2"])
@pytest.mark.parametrize("li", [0, 1, 2])
def test_moe_gemm_reads_layer_of_stack(li, shape, dtype):
    """The kernel (jitted, traced layer index) and the dispatcher's ref
    read layer li of an (L=3, E, W, D) stack: both equal the oracle on
    w_stack[li], the ref exactly, the kernel exactly in f32."""
    from repro.kernels.moe_gemm.ops import grouped_matmul
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    E, C, D, I = 3, 24, 32, 48
    W, Din = (2 * I, D) if shape == "w13" else (D, I)
    ks = jax.random.split(jax.random.PRNGKey(7 * li + len(shape)), 2)
    x = jax.random.normal(ks[0], (E, C, Din), dt)
    w = jax.random.normal(ks[1], (3, E, W, Din), dt)
    full = jnp.full((E,), C, jnp.int32)
    want = np.asarray(grouped_matmul_ref(x, w[li]), np.float32)
    kern = jax.jit(lambda x, w, i, n: grouped_matmul_pallas(
        x, w, i, n, interpret=True))
    out = np.asarray(kern(x, w, jnp.int32(li), full), np.float32)
    ref = np.asarray(grouped_matmul(x, w, jnp.int32(li), full,
                                    backend="ref"), np.float32)
    np.testing.assert_array_equal(ref, want)
    if dtype == "f32":
        np.testing.assert_array_equal(out, want)
    else:
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-2 * Din)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("shape", ["w13", "w2"])
@pytest.mark.parametrize("counts", ["zero", "one", "odd_edge", "full",
                                    "mix"])
def test_moe_gemm_routed_rows(counts, shape, dtype):
    """The kernel (jitted, traced layer index and counts) computes only the
    rows routed to each expert: with rows >= counts[e] of x NaN, its
    output there is finite and exactly zero, and the routed rows equal the
    dispatcher's ref and the oracle, exactly in f32. Row blocks of 24 and
    sub-blocks of 8 (f32) or 16 (bf16) put 19 across a sub-block edge and
    27 across a row-block edge."""
    from repro.kernels.moe_gemm.ops import grouped_matmul
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    E, C, D, I = 5, 40, 32, 24
    W, Din = (2 * I, D) if shape == "w13" else (D, I)
    n = {"zero": [0] * E, "one": [1] * E, "odd_edge": [19] * E,
         "full": [C] * E, "mix": [0, 1, 19, C, 27]}[counts]
    n = jnp.asarray(n, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(len(counts) + len(shape)), 2)
    routed = jnp.arange(C)[None, :, None] < n[:, None, None]
    x = jnp.where(routed, jax.random.normal(ks[0], (E, C, Din), dt),
                  jnp.nan).astype(dt)
    w = jax.random.normal(ks[1], (2, E, W, Din), dt)
    want = np.where(routed, np.asarray(
        grouped_matmul_ref(jnp.where(routed, x, 0), w[1]), np.float32), 0)
    kern = jax.jit(lambda x, w, i, n: grouped_matmul_pallas(
        x, w, i, n, block_c=24, block_sub=8 if dtype == "f32" else 16,
        interpret=True))
    out = np.asarray(kern(x, w, jnp.int32(1), n), np.float32)
    ref = np.asarray(grouped_matmul(x, w, jnp.int32(1), n, backend="ref"),
                     np.float32)
    assert np.isfinite(out).all()
    assert not out[~np.broadcast_to(routed, out.shape)].any()
    np.testing.assert_array_equal(ref, want)
    if dtype == "f32":
        np.testing.assert_array_equal(out, want)
    else:
        np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-2 * Din)


@settings(**HYP)
@given(n=st.integers(1, 8), pages=st.integers(8, 24),
       dtype=st.sampled_from(["f32", "bf16"]), seed=st.integers(0, 50))
def test_kv_pack_matches_ref(n, pages, dtype, seed):
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(ks[0], (pages, 8, 2, 16), dt)
    idx = jax.random.randint(ks[1], (n,), 0, pages)
    g1 = gather_pages_pallas(pool, idx)
    g2 = gather_pages_ref(pool, idx)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    vals = jax.random.normal(ks[2], (n,) + pool.shape[1:], dt)
    # scatter: compare only when idx has no duplicates (both undefined else)
    if len(set(np.asarray(idx).tolist())) == n:
        s1 = scatter_pages_pallas(pool, idx, vals)
        s2 = scatter_pages_ref(pool, idx, vals)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


@settings(**HYP)
@given(E_loc=st.integers(1, 4), I=st.sampled_from([16, 32, 64]),
       D=st.sampled_from([8, 24]), G=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 50))
def test_expert_reshard_kernels(E_loc, I, D, G, seed):
    if I % G:
        return
    w13 = jax.random.normal(jax.random.PRNGKey(seed), (E_loc, 2 * I, D),
                            jnp.float32)
    pk_p = pack_peer_chunks_pallas(w13, G)
    pk_r = pack_peer_chunks_ref(w13, G)
    np.testing.assert_array_equal(np.asarray(pk_p), np.asarray(pk_r))
    il_p = interleave_shards_pallas(pk_p)
    np.testing.assert_array_equal(np.asarray(il_p),
                                  np.asarray(interleave_shards_ref(pk_r)))
    np.testing.assert_array_equal(np.asarray(il_p), np.asarray(w13))
