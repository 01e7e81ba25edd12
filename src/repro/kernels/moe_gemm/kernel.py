"""Pallas TPU grouped expert GEMM (decode MoE hot-spot).

Grid (E, C/bc, W/bw): each step computes one (bc, bw) output tile for one
expert by contracting the full D axis in VMEM. Block shapes are chosen so
the MXU contraction dims are 128-aligned; the expert dim rides the grid so
an expert's weight tile is fetched once per (bc) row of tiles — the
memory-boundness the paper exploits (per-rank time tracks tokens-per-rank).

The weights arrive as the whole layer stack (L, E, W, D) with the layer
index as a prefetched scalar: the weight tiles are DMA'd from the layer the
index names, so a layer scan hands the kernel its loop-invariant stack and
no per-layer slice is ever materialised in HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(li_ref, x_ref, w_ref, o_ref):
    # x (1, bc, D), w (1, bw, D) -> o (1, bc, bw); the MXU takes the
    # operands in their own dtype and accumulates in fp32
    del li_ref                      # consumed by the weight index map
    o_ref[0] = jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _width_block(W: int, block_w: int) -> int:
    """The largest width tile up to block_w that divides W. Padding W
    instead would copy the whole layer stack; every configuration's widths
    are multiples of 128, so on the chip this is block_w itself."""
    bw = min(block_w, W)
    while W % bw:
        bw -= 1
    return bw


def grouped_matmul_pallas(x: jax.Array, w: jax.Array, li: jax.Array, *,
                          block_c: int = 128, block_w: int = 128,
                          interpret: bool = True) -> jax.Array:
    """x (E, C, D), w (L, E, W, D) layer stack, li the layer (int, () or
    (1,)) -> x @ w[li]: (E, C, W)."""
    E, C, D = x.shape
    L, W = w.shape[0], w.shape[2]
    bc = min(block_c, C)
    bw = _width_block(W, block_w)
    padc = (-C) % bc
    if padc:
        x = jnp.pad(x, ((0, 0), (0, padc), (0, 0)))
    Cp = C + padc
    li = jnp.clip(jnp.reshape(li, (1,)).astype(jnp.int32), 0, L - 1)
    out = pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, Cp // bc, W // bw),
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda e, i, j, li: (e, i, 0)),
                pl.BlockSpec((None, 1, bw, D),
                             lambda e, i, j, li: (li[0], e, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bc, bw),
                                   lambda e, i, j, li: (e, i, j))),
        out_shape=jax.ShapeDtypeStruct((E, Cp, W), x.dtype),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(li, x, w)
    return out[:, :C] if padc else out
