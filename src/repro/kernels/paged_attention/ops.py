"""Jit'd dispatcher for paged attention.

Backend policy lives in repro.kernels.dispatch: explicit "ref"/"pallas"/
"interpret", or None = auto (pallas on TPU, ref elsewhere).
"""
from __future__ import annotations

import jax

from repro.kernels import dispatch
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(q, k_pool, v_pool, block_table, kv_lens, *, q_offset,
                    window: int = 0, page_chunk: int = 8,
                    backend: str | None = None) -> jax.Array:
    """q (B,Sq,H,dh); pools (pages,page,K,dh); block_table (B,maxp);
    kv_lens (B,); q_offset (B,). See ref.py for masking semantics."""
    b = dispatch.resolve_backend(backend)
    dispatch.record("paged_attention.paged_attention", b)
    if b == "ref":
        return paged_attention_ref(q, k_pool, v_pool, block_table, kv_lens,
                                   q_offset=q_offset, window=window,
                                   page_chunk=page_chunk)
    return paged_attention_pallas(q, k_pool, v_pool, block_table, kv_lens,
                                  q_offset=q_offset, window=window,
                                  page_chunk=page_chunk,
                                  interpret=(b == "interpret"))
