"""Pallas TPU grouped expert GEMM (decode MoE hot-spot).

Grid (E, C/bc, W/bw): each step computes one (bc, bw) output tile for one
expert by contracting the full D axis in VMEM. Block shapes are chosen so
the MXU contraction dims are 128-aligned; the expert dim rides the grid so
an expert's weight tile is fetched once per (bc) row of tiles — the
memory-boundness the paper exploits (per-rank time tracks tokens-per-rank).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(x_ref, w_ref, o_ref):
    # x (1, bc, D), w (1, bw, D) -> o (1, bc, bw); the MXU takes the
    # operands in their own dtype and accumulates in fp32
    o_ref[0] = jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_matmul_pallas(x: jax.Array, w: jax.Array, *,
                          block_c: int = 128, block_w: int = 128,
                          interpret: bool = True) -> jax.Array:
    """x (E, C, D), w (E, W, D) -> (E, C, W)."""
    E, C, D = x.shape
    W = w.shape[1]
    bc = min(block_c, C)
    bw = min(block_w, W)
    padc = (-C) % bc
    padw = (-W) % bw
    if padc:
        x = jnp.pad(x, ((0, 0), (0, padc), (0, 0)))
    if padw:
        w = jnp.pad(w, ((0, 0), (0, padw), (0, 0)))
    Cp, Wp = C + padc, W + padw
    out = pl.pallas_call(
        _gmm_kernel,
        grid=(E, Cp // bc, Wp // bw),
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, i, j: (e, i, 0)),
            pl.BlockSpec((1, bw, D), lambda e, i, j: (e, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, bw), lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Wp), x.dtype),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(x, w)
    return out[:, :C, :W]
