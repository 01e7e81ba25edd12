"""MoE substrate: routing, rank-major expert layouts, capacity dispatch.

Layout model (generalizes the paper's EP/TP to arbitrary mesh group size G):

  Expert weights are stored **rank-major**: w13 (G, E_loc, W_loc, D) where
  rank r = ep_idx * tp_inner + tp_idx owns experts [ep_idx*E_loc : ...] and
  width slice [tp_idx*W_loc : ...].

    TP layout: ep=1,        tp_inner=G  -> (G, E,     2I/G, D)
    EP layout: ep=gcd(E,G), tp_inner=G/ep -> (G, E/ep, 2I/tp, D)

  Pure EP (paper's case, G | E) has tp_inner == 1. When E < G or E % G != 0
  the EP layout degrades gracefully to an EP x TP hybrid — each expert is
  width-split over tp_inner consecutive ranks. Both layouts are views of the
  same global (E, 2I, D) tensor; a switch only changes rank ownership, which
  is exactly the paper's key insight.

Two compute paths:
  * `moe_ffn_global` — global math with GShard-style capacity dispatch
    (train/prefill; GSPMD shards it from the rank-major weight sharding).
  * `moe_decode_ep` / `moe_decode_tp` — explicit per-rank paths for the
    decode step under shard_map (paper §2.1 semantics, all_to_all dispatch
    vs replicated-batch + psum).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.moe_gemm.ops import grouped_matmul, weight_passes
from repro.models.common import ModelConfig, dense_init, split_keys


# ---------------------------------------------------------------------------
# Expert layouts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExpertLayout:
    """How the expert dimension and width are split over a G-rank group."""
    G: int
    ep: int          # expert-parallel degree
    tp_inner: int    # width split within an expert group (G = ep * tp_inner)

    @property
    def is_pure_ep(self) -> bool:
        return self.tp_inner == 1


def make_expert_layout(num_experts: int, G: int, layout: str) -> ExpertLayout:
    if layout == "tp" or num_experts == 0:
        return ExpertLayout(G=G, ep=1, tp_inner=G)
    ep = math.gcd(num_experts, G)
    return ExpertLayout(G=G, ep=ep, tp_inner=G // ep)


def pack_experts(w: jax.Array, lay: ExpertLayout, width_axis: int) -> jax.Array:
    """(E, ..., W, ...) global -> (G, E_loc, ..., W_loc, ...) rank-major.

    width_axis indexes the *global* tensor's width dim (e.g. 1 for (E,2I,D)).
    """
    E = w.shape[0]
    W = w.shape[width_axis]
    e_loc, w_loc = E // lay.ep, W // lay.tp_inner
    # split E -> (ep, E_loc), W -> (tp, W_loc)
    shp = list(w.shape)
    shp[0:1] = [lay.ep, e_loc]
    wa = width_axis + 1
    shp[wa:wa + 1] = [lay.tp_inner, w_loc]
    w = w.reshape(shp)
    # bring (ep, tp) to front and merge
    w = jnp.moveaxis(w, wa, 1)
    out_shape = (lay.G, e_loc) + tuple(w.shape[3:])
    return w.reshape(out_shape)


def pack_w13(w: jax.Array, lay: ExpertLayout) -> jax.Array:
    """(E, 2I, D) -> (G, E_loc, 2*I/tp, D). The width shard takes matching
    gate/up halves (shards the (2, I) view on I), so a rank-local split-in-
    half of the intermediate stays valid under any tp_inner."""
    E, W2, D = w.shape
    p = pack_experts(w.reshape(E, 2, W2 // 2, D), lay, width_axis=2)
    return p.reshape(p.shape[0], p.shape[1], -1, D)


def unpack_w13(w: jax.Array, lay: ExpertLayout, E: int) -> jax.Array:
    """Inverse of pack_w13 -> (E, 2I, D)."""
    G, E_loc, Wl, D = w.shape
    u = unpack_experts(w.reshape(G, E_loc, 2, Wl // 2, D), lay,
                       width_axis=2, E=E)
    return u.reshape(E, -1, D)


def unpack_experts(w: jax.Array, lay: ExpertLayout, width_axis: int,
                   E: int) -> jax.Array:
    """Inverse of pack_experts -> global (E, ..., W, ...)."""
    e_loc = E // lay.ep
    w = w.reshape((lay.ep, lay.tp_inner, e_loc) + tuple(w.shape[2:]))
    # after removing tp (dim 1), w_loc sits at index width_axis + 1; insert tp
    # immediately before it so [tp, w_loc] merge back into the global width
    wa = width_axis + 1
    w = jnp.moveaxis(w, 1, wa)          # (ep, E_loc, ..., tp, W_loc, ...)
    shp = list(w.shape)
    shp[wa:wa + 2] = [shp[wa] * shp[wa + 1]]
    shp[0:2] = [E]
    return w.reshape(shp)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key, layers: int | None = None) -> dict:
    """Global-layout expert params (packing to rank-major happens in core/layouts)."""
    L = () if layers is None else (layers,)
    D, E, I = cfg.d_model, cfg.num_experts, cfg.d_expert
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], L + (D, E), D, jnp.float32),
        "w13": dense_init(ks[1], L + (E, 2 * I, D), D, cfg.param_dtype),
        "w2": dense_init(ks[2], L + (E, D, I), I, cfg.param_dtype),
    }
    if cfg.num_shared_experts:
        F = cfg.num_shared_experts * I
        kg, ku, kd, kk = split_keys(ks[3], 4)
        p["shared_wg"] = dense_init(kg, L + (F, D), D, cfg.param_dtype)
        p["shared_wu"] = dense_init(ku, L + (F, D), D, cfg.param_dtype)
        p["shared_w2"] = dense_init(kd, L + (D, F), F, cfg.param_dtype)
        p["shared_gate"] = dense_init(kk, L + (D,), D, cfg.param_dtype)
    return p


def capacity(T: int, cfg: ModelConfig, factor: float | None = None) -> int:
    f = cfg.capacity_factor if factor is None else factor
    c = int(math.ceil(T * cfg.top_k / cfg.num_experts * f))
    return max(4, min(T, -(-c // 4) * 4))   # mult of 4, <= T


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """x (T, D) -> gates (T, k) fp32, expert_ids (T, k) int32, probs (T, E)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)   # renormalized top-k
    return gates, eids.astype(jnp.int32), probs


def load_balance_loss(probs: jax.Array, eids: jax.Array, E: int) -> jax.Array:
    """Switch-style aux loss: E * mean(frac_tokens) . mean(router_prob)."""
    khot = jnp.sum(jax.nn.one_hot(eids, E, dtype=jnp.float32), axis=-2)
    frac = jnp.mean(khot, axis=0)
    pmean = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * pmean) / eids.shape[-1]


# ---------------------------------------------------------------------------
# Global capacity-dispatch MoE (train / prefill path; GSPMD-shardable)
# ---------------------------------------------------------------------------

def _dispatch_tensors(khot: jax.Array, counts: jax.Array, C: int):
    """khot (Tc, E) in {0,1} -> (dispatch (Tc,E,C), new_counts)."""
    pos = counts[None, :] + jnp.cumsum(khot, axis=0) - khot
    keep = (pos < C) & (khot > 0)
    disp = jax.nn.one_hot(jnp.where(keep, pos, -1), C, dtype=khot.dtype)
    return disp * keep[..., None].astype(khot.dtype), counts + khot.sum(0)


def moe_ffn_global(cfg: ModelConfig, p: dict, x: jax.Array,
                   lay: ExpertLayout, *, cap_factor: float | None = None,
                   token_chunk: int = 1024):
    """x (T, D) -> (T, D). p holds rank-major w13/w2 (G, E_loc, ., .) + router.

    Capacity-based: tokens over capacity are dropped (contribute 0 for that
    expert). Deterministic in token order.
    """
    T, D = x.shape
    E, k, I = cfg.num_experts, cfg.top_k, cfg.d_expert
    G, ep, tp = lay.G, lay.ep, lay.tp_inner
    E_loc, W13_loc = E // ep, 2 * I // tp
    C = capacity(T, cfg, cap_factor)
    gates, eids, _ = route(cfg, p["router"], x)
    khot = jnp.sum(jax.nn.one_hot(eids, E, dtype=jnp.float32), axis=1)  # (T,E)
    gate_full = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], eids].add(gates)

    nchunk = max(1, -(-T // token_chunk))
    Tc = -(-T // nchunk)
    padT = nchunk * Tc - T
    xp = jnp.pad(x, ((0, padT), (0, 0)))
    khot_p = jnp.pad(khot, ((0, padT), (0, 0)))
    x_ch = xp.reshape(nchunk, Tc, D)
    kh_ch = khot_p.reshape(nchunk, Tc, E)

    def disp_body(carry, inp):
        counts, xd = carry
        xc, khc = inp
        disp, counts = _dispatch_tensors(khc, counts, C)
        xd = xd + jnp.einsum("tec,td->ecd", disp,
                             xc.astype(jnp.float32)).astype(cfg.compute_dtype)
        return (counts, xd), None

    xd0 = jnp.zeros((E, C, D), cfg.compute_dtype)
    (counts_final, Xd), _ = lax.scan(
        disp_body, (jnp.zeros((E,), jnp.float32), xd0), (x_ch, kh_ch))

    # --- expert compute on rank-major weights ---
    # Xd (E, C, D) -> (ep, E_loc, C, D) -> broadcast over tp -> (G, E_loc, C, D)
    Xr = Xd.reshape(ep, E_loc, C, D)
    Xr = jnp.broadcast_to(Xr[:, None], (ep, tp, E_loc, C, D)).reshape(
        G, E_loc, C, D)
    w13, w2 = p["w13"], p["w2"]
    if w13.ndim == 3:                     # global (E, 2I, D): pack on the fly
        w13 = pack_w13(w13, lay)
        w2 = pack_experts(w2, lay, width_axis=2)
    # w13 (G, E_loc, W13_loc, D); w2 (G, E_loc, D, W2_loc)
    h = jnp.einsum("gecd,gewd->gecw", Xr, w13,
                   preferred_element_type=jnp.float32)
    hg, hu = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(hg) * hu).astype(cfg.compute_dtype)   # (G,E_loc,C,I/tp)
    y = jnp.einsum("gecw,gedw->gecd", h, w2,
                   preferred_element_type=jnp.float32)      # partial over tp
    y = y.reshape(ep, tp, E_loc, C, D).sum(axis=1)          # (ep,E_loc,C,D)
    Y = y.reshape(E, C, D).astype(cfg.compute_dtype)

    # --- combine ---
    gates_p = jnp.pad(gate_full, ((0, padT), (0, 0)))
    g_ch = gates_p.reshape(nchunk, Tc, E)

    def comb_body(counts, inp):
        khc, gc = inp
        disp, counts = _dispatch_tensors(khc, counts, C)
        outc = jnp.einsum("tec,ecd->td", disp * gc[..., None],
                          Y.astype(jnp.float32))
        return counts, outc.astype(cfg.compute_dtype)

    _, outs = lax.scan(comb_body, jnp.zeros((E,), jnp.float32), (kh_ch, g_ch))
    out = outs.reshape(nchunk * Tc, D)[:T]

    if cfg.num_shared_experts:
        out = out + shared_expert_forward(cfg, p, x)
    return out.astype(x.dtype)


def shared_expert_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Works on global weights or on width-sharded local slices (gate/up/down
    are separate tensors, all sharded on F, so local math stays consistent —
    a width-sharded call yields a partial sum the caller must psum)."""
    hg = x @ p["shared_wg"].T
    hu = x @ p["shared_wu"].T
    y = (jax.nn.silu(hg.astype(jnp.float32)) * hu.astype(jnp.float32))
    y = y.astype(x.dtype) @ p["shared_w2"].T
    g = jax.nn.sigmoid((x @ p["shared_gate"]).astype(jnp.float32))
    return (y.astype(jnp.float32) * g[..., None]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Explicit per-rank decode paths (inside shard_map over `axis`)
# ---------------------------------------------------------------------------

def _grouped_ffn_local(cfg: ModelConfig, w13, w2, li, xd, counts, *,
                       backend: str | None = None):
    """xd (E_loc, C, D) with counts (E_loc,) routed rows at the front of
    each expert's C slots; w13 (L, E_loc, W13_loc, D), w2 (L, E_loc, D,
    W2_loc): the layer stacks, computed at layer li. Returns the (E_loc,
    C, D) fp32 output and the (2,) weight passes of both GEMMs
    (`weight_passes`: the kernel's, the dense grid's).

    Both GEMMs route through kernels/moe_gemm.grouped_matmul, which reads
    layer li's tiles straight from the stack (no per-layer copy) and only
    the experts and rows the counts name; w2 stores its width axis last,
    so the same (E,C,D)x(E,W,D)->(E,C,W) contraction fits both.  With fp32
    compute_dtype the ref backend is bit-identical to the old inline
    einsums; sub-fp32 compute pays one fp32->compute round-trip per GEMM
    on the kernel path (tolerance policy: DESIGN.md §14).
    """
    h = grouped_matmul(xd, w13, li, counts,
                       backend=backend).astype(jnp.float32)
    hg, hu = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(hg) * hu).astype(cfg.compute_dtype)
    y = grouped_matmul(h, w2, li, counts, backend=backend)
    passes = weight_passes(xd, w13, counts) + weight_passes(h, w2, counts)
    return y.astype(jnp.float32), passes


def moe_decode_tp(cfg: ModelConfig, p: dict, x: jax.Array, axis: str | None,
                  *, li, cap_factor: float | None = None,
                  moe_backend: str | None = None, passes: list | None = None):
    """TP decode: x (T, D) replicated over `axis`; w13/w2 are this rank's
    (L, E, W_loc) layer stacks (G dim already squeezed), used at layer li.
    Output is a *partial* sum — caller psums together with attention output.
    A `passes` list receives this rank's (2,) expert weight passes
    (`_grouped_ffn_local`).
    """
    T, D = x.shape
    E = cfg.num_experts
    C = capacity(T, cfg, cap_factor)
    gates, eids, _ = route(cfg, p["router"], x)
    khot = jnp.sum(jax.nn.one_hot(eids, E, dtype=jnp.float32), axis=1)
    gate_full = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], eids].add(gates)
    disp, _ = _dispatch_tensors(khot, jnp.zeros((E,), jnp.float32), C)
    xd = jnp.einsum("tec,td->ecd", disp,
                    x.astype(jnp.float32)).astype(cfg.compute_dtype)
    counts = jnp.minimum(khot.sum(0), C).astype(jnp.int32)
    y, ps = _grouped_ffn_local(cfg, p["w13"], p["w2"], li, xd, counts,
                               backend=moe_backend)          # partial over axis
    if passes is not None:
        passes.append(ps)
    out = jnp.einsum("tec,ecd->td", disp * gate_full[..., None], y)
    out = out.astype(cfg.compute_dtype)
    if cfg.num_shared_experts:
        # shared experts are width-sharded over the group in TP -> partial too
        out = out + shared_expert_forward(cfg, p, x).astype(cfg.compute_dtype)
    return out   # caller: lax.psum(out, axis)


def moe_decode_ep(cfg: ModelConfig, p: dict, x: jax.Array, axis: str,
                  lay: ExpertLayout, *, li, cap_factor: float | None = None,
                  moe_backend: str | None = None, passes: list | None = None):
    """EP decode under shard_map: x (T_loc, D) is this rank's token slice;
    w13/w2 are this rank's (L, E_loc, ...) layer stacks, used at layer li.

    Dispatch entries (token, k, tp-replica) -> per-dest buffers -> all_to_all
    -> local grouped FFN -> inverse all_to_all -> gate-weighted combine.
    Pure EP when lay.tp_inner == 1; hybrid otherwise (partials sum in combine).
    A `passes` list receives this rank's (2,) expert weight passes
    (`_grouped_ffn_local`).
    """
    T, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    G, ep, tp = lay.G, lay.ep, lay.tp_inner
    E_loc = E // ep
    # per-destination capacity (worst case bounded by T*k entries to one dest)
    f = cfg.capacity_factor if cap_factor is None else cap_factor
    Cd = int(math.ceil(T * k / ep * f))
    Cd = max(4, min(T * k, -(-Cd // 4) * 4))

    gates, eids, _ = route(cfg, p["router"], x)               # (T,k)
    # entries: (T, k, tp) -> destination rank = (eids // E_loc) * tp + j
    dest = (eids // E_loc)[:, :, None] * tp + jnp.arange(tp)[None, None, :]
    dest = dest.reshape(T, k * tp)                            # (T, kt)
    e_entry = jnp.repeat(eids, tp, axis=1)                    # (T, kt) global id
    g_entry = jnp.repeat(gates, tp, axis=1)                   # (T, kt)

    dhot = jax.nn.one_hot(dest, G, dtype=jnp.float32)         # (T, kt, G)
    flat_hot = dhot.reshape(T * k * tp, G)
    pos = jnp.cumsum(flat_hot, axis=0) - flat_hot
    pos = jnp.sum(pos * flat_hot, axis=1).reshape(T, k * tp)  # slot per entry
    keep = pos < Cd
    slot_hot = jax.nn.one_hot(jnp.where(keep, pos, -1), Cd,
                              dtype=jnp.float32)              # (T,kt,Cd)
    # send buffer: payload = [x | e_local+1] so zero-fill decodes to id -1
    e_loc_id = (e_entry % E_loc).astype(jnp.float32) + 1.0
    payload = jnp.concatenate(
        [jnp.broadcast_to(x.astype(jnp.float32)[:, None], (T, k * tp, D)),
         e_loc_id[..., None]], axis=-1)                       # (T,kt,D+1)
    send = jnp.einsum("tkg,tkc,tkd->gcd", dhot,
                      slot_hot * keep[..., None], payload)    # (G,Cd,D+1)
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
    recv = recv.reshape(G, Cd, D + 1)
    rx, rid = recv[..., :D], recv[..., D]
    el = jnp.round(rid).astype(jnp.int32) - 1                 # -1 = empty
    ehot = jax.nn.one_hot(el, E_loc, dtype=jnp.float32)       # (G,Cd,E_loc)
    # local grouped compute over received tokens: dispatch to (E_loc, C2)
    C2 = Cd * G
    ehot_f = ehot.reshape(G * Cd, E_loc)
    pos2 = jnp.cumsum(ehot_f, axis=0) - ehot_f
    pos2 = jnp.sum(pos2 * ehot_f, axis=1)
    slot2 = jax.nn.one_hot(jnp.where(el.reshape(-1) >= 0, pos2, -1), C2,
                           dtype=jnp.float32)                 # (G*Cd, C2)
    xd = jnp.einsum("te,tc,td->ecd", ehot_f, slot2,
                    rx.reshape(G * Cd, D)).astype(cfg.compute_dtype)
    counts = ehot_f.sum(0).astype(jnp.int32)
    y, ps = _grouped_ffn_local(cfg, p["w13"], p["w2"], li, xd, counts,
                               backend=moe_backend)           # (E_loc,C2,D)
    if passes is not None:
        passes.append(ps)
    y_back = jnp.einsum("te,tc,ecd->td", ehot_f, slot2,
                        y.astype(jnp.float32)).reshape(G, Cd, D)
    y_ret = lax.all_to_all(y_back, axis, split_axis=0, concat_axis=0,
                           tiled=True).reshape(G, Cd, D)
    out = jnp.einsum("tkg,tkc,gcd->td", dhot,
                     slot_hot * (keep * g_entry)[..., None], y_ret)
    out = out.astype(cfg.compute_dtype)
    if cfg.num_shared_experts:
        out = out + shared_expert_forward(cfg, p, x).astype(cfg.compute_dtype)
    return out
