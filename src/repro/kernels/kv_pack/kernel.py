"""Pallas TPU page-pack kernels: the gather/scatter stages of the KV switch
(paper §4.3, Fig. 8(b)).

The page indices are scalar-prefetched into SMEM; pools, packed chunks and
staged values all stay in HBM (`pl.ANY`) and every page moves with one
HBM->HBM DMA — the 'Direct' row of Table 1, one HBM read per element and
no VMEM round-trip. On real TPU the store side would be a
`make_async_remote_copy` into the peer's slot; portably we pack locally and
let the collective move the chunk.

A row-batched pool (R, pages, M) is viewed as (R, pages, M/128, 128) when
M is lane-aligned, so each page is a whole trailing tile block and the
DMA's dynamic index falls on a major dimension.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pl.BlockSpec(memory_space=pl.ANY)


def _page_view(M: int) -> tuple[int, int]:
    return (M // 128, 128) if M % 128 == 0 else (1, M)


def _copy_rows(n: int, rows: int, src, dst, sem) -> None:
    """For each row r: start the n page DMAs `src(r, i) -> dst(r, i)`, then
    wait for them (at most n copies in flight)."""
    def row(r, carry):
        def start(i, c):
            pltpu.make_async_copy(src(r, i), dst(r, i), sem).start()
            return c

        def wait(i, c):
            pltpu.make_async_copy(src(r, i), dst(r, i), sem).wait()
            return c

        lax.fori_loop(0, n, start, 0)
        lax.fori_loop(0, n, wait, 0)
        return carry

    lax.fori_loop(0, rows, row, 0)


def _gather_rows_kernel(idx_ref, pool_ref, o_ref, sem):
    R, n = o_ref.shape[0], o_ref.shape[1]
    _copy_rows(n, R, lambda r, i: pool_ref.at[r, idx_ref[i]],
               lambda r, i: o_ref.at[r, i], sem)


def gather_pages_rows_pallas(pool: jax.Array, idx: jax.Array, *,
                             interpret: bool = True) -> jax.Array:
    """Row-batched gather: pool (R, pages, M); idx (n,) -> (R, n, M).

    One launch stages every (layer, K/V) row of a chunk's pool view — the
    fused per-chunk mover of the switch staging path.
    """
    R, pages, M = pool.shape
    n = idx.shape[0]
    a, l = _page_view(M)
    out = pl.pallas_call(
        _gather_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,), in_specs=[_ANY],
            out_specs=_ANY, scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((R, n, a, l), pool.dtype),
        interpret=interpret,
        name="kv_gather_pages_rows",
    )(idx.astype(jnp.int32), pool.reshape(R, pages, a, l))
    return out.reshape(R, n, M)


def _scatter_rows_kernel(idx_ref, vals_ref, pool_in_ref, pool_out_ref, sem,
                         *, row0: int):
    del pool_in_ref   # aliased with pool_out_ref
    Rv, n = vals_ref.shape[0], vals_ref.shape[1]
    _copy_rows(n, Rv, lambda r, i: vals_ref.at[r, i],
               lambda r, i: pool_out_ref.at[row0 + r, idx_ref[i]], sem)


def scatter_pages_rows_pallas(pool: jax.Array, idx: jax.Array,
                              vals: jax.Array, *, row0: int = 0,
                              interpret: bool = True) -> jax.Array:
    """Row-batched scatter: pool[row0 + r, idx[i]] = vals[r, i].

    pool (R, pages, M), idx (n,), vals (Rv, n, M) with row0 + Rv <= R.
    Input/output aliased: one in-place HBM pass commits a whole chunk.
    """
    R, pages, M = pool.shape
    Rv, n, _ = vals.shape
    a, l = _page_view(M)
    out = pl.pallas_call(
        partial(_scatter_rows_kernel, row0=row0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,), in_specs=[_ANY, _ANY],
            out_specs=_ANY, scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((R, pages, a, l), pool.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="kv_scatter_pages_rows",
    )(idx.astype(jnp.int32), vals.reshape(Rv, n, a, l),
      pool.reshape(R, pages, a, l))
    return out.reshape(R, pages, M)


def gather_pages_pallas(pool: jax.Array, idx: jax.Array, *,
                        interpret: bool = True) -> jax.Array:
    """pool (pages, page, K, dh); idx (n,) int32 -> (n, page, K, dh)."""
    pages, *page_shape = pool.shape
    out = gather_pages_rows_pallas(pool.reshape(1, pages, -1), idx,
                                   interpret=interpret)
    return out.reshape(idx.shape[0], *page_shape)


def scatter_pages_pallas(pool: jax.Array, idx: jax.Array, vals: jax.Array, *,
                         interpret: bool = True) -> jax.Array:
    """Write vals (n, page, K, dh) into pool at idx (input/output aliased)."""
    pages = pool.shape[0]
    out = scatter_pages_rows_pallas(
        pool.reshape(1, pages, -1), idx, vals.reshape(1, vals.shape[0], -1),
        interpret=interpret)
    return out.reshape(pool.shape)
