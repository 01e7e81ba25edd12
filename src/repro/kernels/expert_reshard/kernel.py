"""Pallas TPU expert-permute kernels: the local stage of the weight reshard.

EP->TP runs permute-then-exchange: this kernel packs each rank's complete
experts into per-peer contiguous chunks in ONE pass over HBM (vs. a staged
copy), preserving the gate/up pairing of w13. TP->EP runs the inverse
interleave after the exchange.

Every permute is a handful of rectangular HBM->HBM DMAs with static
offsets — one per (peer, gate/up half) — so no expert ever passes through
VMEM (a whole mixtral-8x7b expert is 224-448 MiB) and the slice offsets
are multiples of I/G, which is tile-aligned at published widths.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pl.BlockSpec(memory_space=pl.ANY)


def _dma_permute(name: str, pairs, x: jax.Array, out_shape, interpret):
    """Run `pairs(src_ref, dst_ref)` -> [(src_window, dst_window), ...] as
    concurrent DMAs from x into a fresh HBM output of `out_shape`."""
    def kernel(x_ref, o_ref, sem):
        copies = [pltpu.make_async_copy(s, d, sem)
                  for s, d in pairs(x_ref, o_ref)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return pl.pallas_call(
        kernel,
        in_specs=[_ANY],
        out_specs=_ANY,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        interpret=interpret,
        name=name,
    )(x)


def pack_peer_chunks_pallas(w13: jax.Array, G: int, *,
                            interpret: bool = True) -> jax.Array:
    """w13 (E_loc, 2I, D) -> (G, E_loc, 2*(I/G), D)."""
    E_loc, W2, D = w13.shape
    I = W2 // 2
    Ig = I // G
    return _dma_permute(
        "expert_pack_peer_chunks",
        lambda x, o: [(x.at[:, pl.ds(h * I + g * Ig, Ig)],
                       o.at[g, :, pl.ds(h * Ig, Ig)])
                      for g in range(G) for h in range(2)],
        w13, (G, E_loc, 2 * Ig, D), interpret)


def pack_width_chunks_pallas(w2: jax.Array, G: int, *,
                             interpret: bool = True) -> jax.Array:
    """w2 (E_loc, D, I) -> (G, E_loc, D, I/G): per-peer down-proj chunks."""
    E_loc, D, I = w2.shape
    Ig = I // G
    return _dma_permute(
        "expert_pack_width_chunks",
        lambda x, o: [(x.at[:, :, pl.ds(g * Ig, Ig)], o.at[g])
                      for g in range(G)],
        w2, (G, E_loc, D, Ig), interpret)


def interleave_width_shards_pallas(chunks: jax.Array, *,
                                   interpret: bool = True) -> jax.Array:
    """chunks (G, E_loc, D, Ic) -> (E_loc, D, G*Ic): inverse of pack_width."""
    G, E_loc, D, Ic = chunks.shape
    return _dma_permute(
        "expert_interleave_width_shards",
        lambda x, o: [(x.at[g], o.at[:, :, pl.ds(g * Ic, Ic)])
                      for g in range(G)],
        chunks, (E_loc, D, G * Ic), interpret)


def interleave_shards_pallas(chunks: jax.Array, *,
                             interpret: bool = True) -> jax.Array:
    """chunks (G, E_loc, 2*(I/G), D) -> (E_loc, 2I, D)."""
    G, E_loc, Wl, D = chunks.shape
    half = Wl // 2
    I = G * half
    return _dma_permute(
        "expert_interleave_shards",
        lambda x, o: [(x.at[g, :, pl.ds(h * half, half)],
                       o.at[:, pl.ds(h * I + g * half, half)])
                      for g in range(G) for h in range(2)],
        chunks, (E_loc, 2 * I, D), interpret)
