"""Pallas TPU grouped expert GEMM (decode MoE hot-spot), driven by the
per-expert row counts.

The dispatch packs the rows routed to expert e at the front of its C
slots, and `counts[e]` says how many there are. Grid (E, C/bc, W/bw) with
the width innermost: one grid step contracts a (bc, D) row block of x with
one (bw, D) weight tile over the whole D axis in VMEM. The row block bc
holds all of C whenever the tiles fit VMEM, so an expert's weights stream
from HBM once per row block that holds a routed row:
sum_e ceil(counts[e] / bc) passes a call (`weight_passes`).

  * An expert with no routed row is never read: the x and weight index
    maps repeat the block that is already resident (a prefetched table of
    "expert whose block is resident"), so the pipeline issues no DMA.
  * Row blocks past ceil(counts[e] / bc) are neither fetched nor computed.
  * Inside a row block the MXU runs over row sub-blocks up to the count,
    so its work follows the routed rows, not C.
  * Output rows >= counts[e] are written as exact zeros whatever x holds
    there: the combine multiplies them by 0, and 0 x garbage can be NaN.

The weights arrive as the whole layer stack (L, E, W, D) with the layer
index as a prefetched scalar: the weight tiles are DMA'd from the layer the
index names, so a layer scan hands the kernel its loop-invariant stack and
no per-layer slice is ever materialised in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one row sub-block: the v5e MXU is 128 rows tall, so a dot over fewer
# rows takes it as long, and smaller sub-blocks only add MXU passes
MXU_ROWS = 128
VMEM_TILES = 96 * 2**20        # of v5e's 128 MiB VMEM, what the tiles hold
W_TILE_BYTES = 4 * 2**20       # target bytes of one weight tile


def _gmm_kernel(li_ref, cnt_ref, we_ref, ri_ref, wj_ref, x_ref, w_ref,
                o_ref, *, bc: int, sb: int):
    # x (1, bc, D), w (1, bw, D) -> o (1, bc, bw); the MXU takes the
    # operands in their own dtype and accumulates in fp32
    del li_ref, we_ref, ri_ref, wj_ref      # consumed by the index maps
    n = cnt_ref[pl.program_id(0)] - pl.program_id(1) * bc   # rows routed
    for s in range(0, bc, sb):
        rows = min(sb, bc - s)

        @pl.when(n > s)
        def _():
            acc = lax.dot_general(
                x_ref[0, s:s + rows], w_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            r = lax.broadcasted_iota(jnp.int32, acc.shape, 0) + s
            o_ref[0, s:s + rows] = jnp.where(r < n, acc, 0).astype(
                o_ref.dtype)

        @pl.when(n <= s)
        def _():
            o_ref[0, s:s + rows] = jnp.zeros((rows, o_ref.shape[2]),
                                             o_ref.dtype)


def _width_block(W: int, cap: int) -> int:
    """The widest tile up to cap that divides W, in multiples of 128 where
    W allows. Padding W instead would copy the whole layer stack; every
    configuration's widths are multiples of 128."""
    step = 128 if W % 128 == 0 else 1
    bw = max(step, min(W, cap) // step * step)
    while W % bw:
        bw -= step
    return bw


def tiles(C: int, D: int, W: int, itemsize: int) -> tuple[int, int]:
    """(bc, bw) for x (E, C, D) @ w (E, W, D)^T: the width tile, then the
    row block, all of C when x's two buffers fit VMEM beside the weight
    and output tiles, else the fewest equal blocks of whole MXU row
    sub-blocks that do."""
    bw = _width_block(W, W_TILE_BYTES // (D * itemsize))
    cap = (VMEM_TILES - 2 * bw * D * itemsize) // (2 * (D + bw) * itemsize)
    if C <= cap:
        return C, bw
    cap = cap // MXU_ROWS * MXU_ROWS
    return pl.cdiv(pl.cdiv(C, pl.cdiv(C, cap)), MXU_ROWS) * MXU_ROWS, bw


def _vmem_bytes(bc: int, bw: int, D: int, itemsize: int) -> int:
    """Double-buffered x, weight and output blocks, the fp32 product of
    one row sub-block, and room for Mosaic's own scratch."""
    blocks = 2 * (bc * D + bw * D + bc * bw) * itemsize
    return blocks + 4 * min(bc, MXU_ROWS) * bw + 16 * 2**20


def _resident(counts: jax.Array, bc: int, nw: int):
    """Per expert e: (we, ri, wj), the expert, row block and width tile
    whose blocks are resident in VMEM when e has no row block left to
    compute. That is the last block fetched: the last width tile of the
    last row block of the nearest expert at or before e with a routed row,
    or, before the first such expert, its first block (fetched anyway)."""
    E = counts.shape[0]
    nt = (counts + bc - 1) // bc
    hit = nt > 0
    last = lax.cummax(jnp.where(hit, jnp.arange(E, dtype=jnp.int32), -1))
    seen = last >= 0
    we = jnp.where(seen, last, jnp.argmax(hit).astype(jnp.int32))
    ri = jnp.where(seen, nt[we] - 1, 0)
    wj = jnp.where(seen, nw - 1, 0)
    return we, ri.astype(jnp.int32), wj.astype(jnp.int32)


def grouped_matmul_pallas(x: jax.Array, w: jax.Array, li: jax.Array,
                          counts: jax.Array, *, block_c: int | None = None,
                          block_w: int | None = None,
                          block_sub: int = MXU_ROWS,
                          interpret: bool = True) -> jax.Array:
    """x (E, C, D), w (L, E, W, D) layer stack, li the layer (int, () or
    (1,)), counts (E,) the rows routed to each expert, packed at the front
    of its C slots -> x @ w[li]: (E, C, W), rows >= counts[e] zero.

    block_c / block_w cap the row block and width tile in place of those
    `tiles` picks; block_sub is the MXU row sub-block."""
    E, C, D = x.shape
    L, W = w.shape[0], w.shape[2]
    bc, bw = tiles(C, D, W, x.dtype.itemsize)
    bc = bc if block_c is None else min(block_c, C)
    bw = bw if block_w is None else _width_block(W, block_w)
    sb = min(block_sub, bc)
    nb = pl.cdiv(C, bc)
    if nb * bc != C:
        x = jnp.pad(x, ((0, 0), (0, nb * bc - C), (0, 0)))
    li = jnp.clip(jnp.reshape(li, (1,)).astype(jnp.int32), 0, L - 1)
    counts = jnp.clip(counts.astype(jnp.int32), 0, C)
    we, ri, wj = _resident(counts, bc, W // bw)

    def live(e, i, cnt):
        return i * bc < cnt[e]

    def x_map(e, i, j, li, cnt, we, ri, wj):
        return we[e], jnp.where(live(e, i, cnt), i, ri[e]), 0

    def w_map(e, i, j, li, cnt, we, ri, wj):
        return li[0], we[e], jnp.where(live(e, i, cnt), j, wj[e]), 0

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bc=bc, sb=sb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(E, nb, W // bw),
            in_specs=[pl.BlockSpec((1, bc, D), x_map),
                      pl.BlockSpec((None, 1, bw, D), w_map)],
            out_specs=pl.BlockSpec((1, bc, bw),
                                   lambda e, i, j, *_: (e, i, j))),
        out_shape=jax.ShapeDtypeStruct((E, nb * bc, W), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(bc, bw, D, x.dtype.itemsize)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(li, counts, we, ri, wj, x, w)
    return out[:, :C] if nb * bc != C else out
