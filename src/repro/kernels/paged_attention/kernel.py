"""Pallas TPU paged-attention (flash-decoding style).

Grid: (B,). The block table, KV lengths and query offsets are scalar-
prefetched into SMEM; the KV pools stay in HBM (`pl.ANY`) and each chunk of
`page_chunk` pages is DMA'd into a double-buffered VMEM tile, so the paged
indirection happens inside the kernel with no materialised gather. The
chunk loop runs only over chunks that hold a valid position: from the
sliding window's lower bound up to min(kv_len, q_offset + Sq). Online
softmax accumulates in fp32 VMEM scratch.

Layouts: the query block is (K, rep*Sq, dh), one row block per KV head
(row r = rep_index * Sq + query_index), so each head's score tile is one
2-D MXU contraction against its (page_chunk*page, dh) KV slice. With
page=16 and page_chunk=8 the KV axis of the score tile is 128 wide.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(bt_ref, kvlen_ref, qoff_ref, q_ref, rowpos_ref,
                       kpool_ref, vpool_ref, o_ref, kbuf, vbuf, sem,
                       m_sc, l_sc, acc_sc, *, page: int, page_chunk: int,
                       window: int, nchunk: int, Sq: int):
    b = pl.program_id(0)
    K, _, dh = acc_sc.shape
    span = page_chunk * page
    scale = 1.0 / math.sqrt(dh)
    kv_len = kvlen_ref[b]
    q_off = qoff_ref[b]
    # live chunks: every valid position needs kv_pos < kv_len, kv_pos <= the
    # last query position and, with a window, kv_pos > the first query
    # position - window. Chunks outside [lo, hi) are fully masked, so
    # skipping them is the identity on the online-softmax carry.
    bound = jnp.minimum(kv_len, q_off + Sq)
    hi = jnp.minimum(nchunk, (bound + span - 1) // span)
    lo = 0
    if window > 0:
        lo = jnp.minimum(jnp.maximum(q_off - window + 1, 0) // span, hi)

    def copies(j, slot):
        base = b * (nchunk * page_chunk) + j * page_chunk
        out = []
        for i in range(page_chunk):
            pid = bt_ref[base + i]
            dst = pl.ds(i * page, page)
            out.append(pltpu.make_async_copy(
                kpool_ref.at[pid], kbuf.at[slot, dst], sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                vpool_ref.at[pid], vbuf.at[slot, dst], sem.at[1, slot]))
        return out

    m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(hi > lo)
    def _():
        for c in copies(lo, 0):
            c.start()

    q_pos = q_off + rowpos_ref[...]                          # (M, 1)

    def chunk_body(j, carry):
        slot = (j - lo) & 1

        @pl.when(j + 1 < hi)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        kv_pos = j * span + lax.broadcasted_iota(jnp.int32, (1, span), 1)
        ok = (kv_pos < kv_len) & (kv_pos <= q_pos)           # (M, span)
        if window > 0:
            ok = ok & (kv_pos > q_pos - window)
        for h in range(K):
            kh = kbuf[slot, :, pl.ds(h * dh, dh)]            # (span, dh)
            vh = vbuf[slot, :, pl.ds(h * dh, dh)]
            s = lax.dot_general(q_ref[0, h], kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * corr + jnp.dot(
                p.astype(vh.dtype), vh, preferred_element_type=jnp.float32)
            m_sc[h] = m_new
        return carry

    lax.fori_loop(lo, hi, chunk_body, 0)
    for h in range(K):
        o_ref[0, h] = (acc_sc[h] / jnp.maximum(l_sc[h], 1e-30)).astype(
            o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, block_table, kv_lens, *,
                           q_offset, window: int = 0, page_chunk: int = 8,
                           interpret: bool = True) -> jax.Array:
    """Same contract as ref.paged_attention_ref."""
    B, Sq, H, dh = q.shape
    pages, page, K, _ = k_pool.shape
    maxp = block_table.shape[1]
    rep = H // K
    M = rep * Sq
    nchunk = -(-maxp // page_chunk)
    span = page_chunk * page
    bt = jnp.pad(block_table.astype(jnp.int32),
                 ((0, 0), (0, nchunk * page_chunk - maxp))).reshape(-1)
    # (B, Sq, K*rep, dh) -> (B, K, rep*Sq, dh): one row block per KV head
    qk = jnp.moveaxis(q.reshape(B, Sq, K, rep, dh), 1, 3).reshape(B, K, M, dh)
    rowpos = jnp.tile(jnp.arange(Sq, dtype=jnp.int32), rep).reshape(M, 1)
    kern = functools.partial(_paged_attn_kernel, page=page,
                             page_chunk=page_chunk, window=window,
                             nchunk=nchunk, Sq=Sq)
    qspec = pl.BlockSpec((1, K, M, dh), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[qspec,
                      pl.BlockSpec((M, 1), lambda b, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((2, span, K * dh), k_pool.dtype),
                pltpu.VMEM((2, span, K * dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((K, M, 1), jnp.float32),
                pltpu.VMEM((K, M, 1), jnp.float32),
                pltpu.VMEM((K, M, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, M, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(bt, kv_lens.astype(jnp.int32), q_offset.astype(jnp.int32), qk, rowpos,
      k_pool.reshape(pages, page, K * dh), v_pool.reshape(pages, page, K * dh))
    return jnp.moveaxis(out.reshape(B, K, rep, Sq, dh), 3, 1).reshape(
        B, Sq, H, dh)
