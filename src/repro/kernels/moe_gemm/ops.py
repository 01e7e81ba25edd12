"""Jit'd dispatcher for the grouped expert GEMM."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import dispatch
from repro.kernels.moe_gemm.kernel import grouped_matmul_pallas
from repro.kernels.moe_gemm.ref import grouped_matmul_ref


def grouped_matmul(x: jax.Array, w: jax.Array, li, *,
                   backend: str | None = None) -> jax.Array:
    """x (E,C,D) @ w[li] -> (E,C,W), fp32 accumulation per expert.

    w is a layer stack (L, E, W, D) and li the layer (int, () or (1,)):
    the kernel reads its tiles straight from the stack. A caller holding
    one layer's weights passes w[None] and 0."""
    b = dispatch.resolve_backend(backend)
    dispatch.record("moe_gemm.grouped_matmul", b)
    if b == "ref":
        wl = lax.dynamic_index_in_dim(w, jnp.reshape(li, ()), 0,
                                      keepdims=False)
        return grouped_matmul_ref(x, wl)
    return grouped_matmul_pallas(x, w, li, interpret=(b == "interpret"))
