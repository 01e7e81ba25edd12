"""Jit'd dispatcher for the grouped expert GEMM, and the count of expert
weight passes it makes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import dispatch
from repro.kernels.moe_gemm.kernel import grouped_matmul_pallas, tiles
from repro.kernels.moe_gemm.ref import grouped_matmul_ref


def grouped_matmul(x: jax.Array, w: jax.Array, li, counts: jax.Array, *,
                   backend: str | None = None) -> jax.Array:
    """x (E,C,D) @ w[li] -> (E,C,W), fp32 accumulation per expert.

    w is a layer stack (L, E, W, D) and li the layer (int, () or (1,)):
    the kernel reads its tiles straight from the stack. A caller holding
    one layer's weights passes w[None] and 0. counts (E,) int32 is the
    number of rows routed to each expert, packed at the front of its C
    slots; output rows >= counts[e] are zero in every backend."""
    b = dispatch.resolve_backend(backend)
    dispatch.record("moe_gemm.grouped_matmul", b)
    if b == "ref":
        wl = lax.dynamic_index_in_dim(w, jnp.reshape(li, ()), 0,
                                      keepdims=False)
        rows = jnp.arange(x.shape[1])[None, :, None]
        return jnp.where(rows < counts[:, None, None],
                         grouped_matmul_ref(x, wl), 0)
    return grouped_matmul_pallas(x, w, li, counts,
                                 interpret=(b == "interpret"))


def weight_passes(x: jax.Array, w: jax.Array, counts: jax.Array
                  ) -> jax.Array:
    """(2,) int32 for grouped_matmul(x, w, li, counts): the expert weight
    passes the kernel makes, sum_e ceil(counts[e] / row block), and those
    the dense grid of 128-row tiles made before it, E x ceil(C / 128), a
    trace-time constant."""
    E, C, D = x.shape
    bc, _ = tiles(C, D, w.shape[2], x.dtype.itemsize)
    made = jnp.sum((jnp.clip(counts, 0, C).astype(jnp.int32) + bc - 1) // bc)
    return jnp.stack([made, jnp.int32(E * -(-C // 128))])
