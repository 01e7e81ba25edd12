"""Serve steps for the non-transformer families (ssm / hybrid / encdec).

Same layout semantics as serving/steps.py, adapted per family (DESIGN.md
§Arch-applicability):
  * ssm (Mamba2): no KV cache — the switchable state is the SSD recurrent
    state + conv tail. "EP" = DP (batch over model axis, weights replicated);
    TP shards inner channels/heads, with explicit psums for the gated
    RMSNorm (sum-of-squares over the sharded d_inner) and out_proj.
  * hybrid (Zamba2): mamba state machinery + a shared attention block with
    paged KV at every attn_every-th layer.
  * encdec (Whisper): decoder self-attn uses the paged pool; cross-attention
    reads a per-slot dense cross-KV cache computed at admission.

Mixed-row contract (DESIGN.md §10): rows carry `(start_pos, n_tokens)` just
like steps.build_mixed_step. The encdec step generalizes to Sq > 1, so a
batch may mix decode rows (n_tokens == 1) with decoder prefill chunks
(teacher-forced transcript prefixes) in one dispatch. The recurrent-state
families (ssm / hybrid) keep Sq == 1 — the SSD recurrence advances one
token per dispatch, so their rows degenerate to n_tokens ∈ {0, 1}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.layouts import (EP, TP, attn_rank_major, get_layout,
                                group_info)
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.common import ModelConfig, apply_norm, rope_cos_sin
from repro.models.ssm import ssd_decode_step
from repro.serving.kvcache import CacheConfig
from repro.serving.steps import (_embed_lookup, _project_heads, _sample,
                                 _write_pages)


# ---------------------------------------------------------------------------
# SSM decode layer (rank-local math + explicit collectives)
# ---------------------------------------------------------------------------

def _ssm_decode_layer(cfg: ModelConfig, lp, x, conv_st, ssm_st, layout, m):
    """x (bs, D) one token; conv_st (bs, 3, K-1, C...) packed; returns
    (y (bs, D), new states). Weights are rank-local slices (TP) or full (EP).
    """
    Kc = cfg.ssm_conv
    P_ = cfg.ssm_head_dim
    N = cfg.ssm_state
    z = x @ lp["wz"]                      # (bs, Din_loc)
    xs = x @ lp["wx"]
    Bp = x @ lp["wB"]                     # replicated (bs, G*N)
    Cp = x @ lp["wC"]
    dt = jax.nn.softplus((x @ lp["wdt"]).astype(jnp.float32)
                         + lp["dt_bias"][None])
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    def conv1(v, w, st):                  # st (bs, K-1, C); v (bs, C)
        full = jnp.concatenate([st, v[:, None]], axis=1)
        y = sum(full[:, i] * w[i] for i in range(Kc))
        return jax.nn.silu(y.astype(jnp.float32)).astype(v.dtype), \
            full[:, 1:]
    cx, cB, cC = conv_st
    xs, cx = conv1(xs, lp["conv_x"], cx)
    Bp, cB = conv1(Bp, lp["conv_B"], cB)
    Cp, cC = conv1(Cp, lp["conv_C"], cC)

    H_loc = xs.shape[-1] // P_
    xh = xs.reshape(-1, H_loc, P_)
    Bh = Bp.reshape(-1, cfg.ssm_groups, N)
    Ch = Cp.reshape(-1, cfg.ssm_groups, N)
    # groups are replicated; heads local -> feed local heads only
    y, new_ssm = ssd_decode_step(ssm_st, xh, dt, A, Bh, Ch)
    y = y + xh.astype(jnp.float32) * lp["Dskip"][None, :, None]
    y = y.reshape(-1, H_loc * P_)
    zf = jax.nn.silu(z.astype(jnp.float32))
    g = y * zf
    # gated RMSNorm over the FULL d_inner (psum of sum-of-squares under TP)
    ss = jnp.sum(g * g, axis=-1, keepdims=True)
    if layout == TP:
        ss = lax.psum(ss, m)
    g = g * lax.rsqrt(ss / cfg.d_inner + 1e-6)
    g = (g * lp["norm"].astype(jnp.float32)[None]).astype(x.dtype)
    out = g @ lp["out_proj"]              # partial under TP
    if layout == TP:
        out = lax.psum(out, m)
    return out, (cx, cB, cC), new_ssm


def ssm_pack_specs(cfg: ModelConfig, layout: str, m: str = "model"):
    tp = get_layout(layout).base is TP
    def sp(*s):
        return P(*s) if tp else P()
    layer = {
        "wz": sp(None, None, m), "wx": sp(None, None, m),
        "wB": P(), "wC": P(),
        "wdt": sp(None, None, m),
        "A_log": sp(None, m), "Dskip": sp(None, m), "dt_bias": sp(None, m),
        "conv_x": sp(None, None, m), "conv_B": P(), "conv_C": P(),
        "norm": sp(None, m),
        "out_proj": sp(None, m, None),
    }
    return layer


def build_ssm_serve_step(cfg: ModelConfig, mesh, layout: str, Bslot: int, *,
                         temperature: float = 0.0, data_axes=("data",),
                         model_axis: str = "model", donate: bool = True):
    """Decode step for the pure-SSM LM. State pytree replaces the KV pool:
      conv: (Dd, B, L, 3, K-1, C) packed [x|B|C] tails (C = max channel dim)
      ssm:  (Dd, B, L, H, P, N)
    TP shards conv x-channels / heads; EP(DP) shards the batch dim."""
    layout = get_layout(layout).base   # sized specs ("tp@4") dispatch as base
    m, da = model_axis, data_axes
    G = mesh.shape[m]
    L = cfg.num_layers
    bs = Bslot // G if layout == EP else Bslot
    bspec2 = P(da, m) if layout == EP else P(da, None)
    bspec3 = P(da, m, None) if layout == EP else P(da, None, None)
    # state specs; conv_B/C carry the (replicated) group channels -> never
    # channel-sharded under TP
    if layout == EP:
        conv_x_spec = P(da, m, None, None, None)
        ssm_spec = P(da, m, None, None, None, None)
        head_spec = conv_x_spec
    else:
        conv_x_spec = P(da, None, None, None, m)
        ssm_spec = P(da, None, None, m, None, None)
        head_spec = P(da, None, None, None, None)
    vocab_spec = P(m, None) if layout == TP else P()
    lspec = ssm_pack_specs(cfg, layout, m)

    def body(pack, conv_x, conv_B, conv_C, ssm_st, tokens, valid, key):
        tokens = tokens.reshape(bs)
        key = jax.random.wrap_key_data(key)
        x = _embed_lookup(cfg, pack, tokens, layout, m)

        def layer_fn(h, xs):
            lp, cx, cB, cC, st = xs
            hn = apply_norm(cfg, h, lp["norm_in"])
            y, (ncx, ncB, ncC), nst = _ssm_decode_layer(
                cfg, lp["ssm"], hn, (cx, cB, cC), st, layout, m)
            return h + y.astype(h.dtype), (ncx, ncB, ncC, nst)

        lp_all = {"ssm": pack["layers"]["ssm"],
                  "norm_in": pack["layers"]["norm"]}
        # scan over layers: states are (bs, L, ...) -> move L first
        mv = lambda a: jnp.moveaxis(a.reshape((bs,) + a.shape[2:]), 1, 0)
        x, sts = lax.scan(
            lambda h, xs: layer_fn(h, xs), x,
            ({"ssm": jax.tree.map(lambda v: v, lp_all["ssm"]),
              "norm_in": lp_all["norm_in"]},
             mv(conv_x), mv(conv_B), mv(conv_C), mv(ssm_st)))
        ncx, ncB, ncC, nst = sts
        x = apply_norm(cfg, x, pack["final_norm"])
        nxt = _sample(cfg, pack, x, layout, m, key, temperature, 0)
        back = lambda a, proto: jnp.moveaxis(a, 0, 1).reshape(proto.shape)
        return (nxt.reshape(1, bs), back(ncx, conv_x), back(ncB, conv_B),
                back(ncC, conv_C), back(nst, ssm_st))

    pspecs = {
        "embed": vocab_spec, "lm_head": vocab_spec,
        "final_norm": {"scale": P()},
        "layers": {"norm": {"scale": P()}, "ssm": lspec},
    }
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, conv_x_spec, head_spec, head_spec, ssm_spec,
                  bspec3, bspec2, P()),
        out_specs=(bspec2, conv_x_spec, head_spec, head_spec, ssm_spec),
        check_vma=False)
    return jax.jit(smapped, donate_argnums=(1, 2, 3, 4) if donate else ())


def ssm_state_shapes(cfg: ModelConfig, Dd: int, Bslot: int):
    L, Kc = cfg.num_layers, cfg.ssm_conv
    GN = cfg.ssm_groups * cfg.ssm_state
    return {
        "conv_x": (Dd, Bslot, L, Kc - 1, cfg.d_inner),
        "conv_B": (Dd, Bslot, L, Kc - 1, GN),
        "conv_C": (Dd, Bslot, L, Kc - 1, GN),
        "ssm": (Dd, Bslot, L, cfg.ssm_heads, cfg.ssm_head_dim,
                cfg.ssm_state),
    }


# ---------------------------------------------------------------------------
# Hybrid (Zamba2) decode: mamba layers + shared attention sites
# ---------------------------------------------------------------------------

def build_hybrid_serve_step(cfg: ModelConfig, mesh, layout: str,
                            cc: CacheConfig, Bslot: int, *,
                            temperature: float = 0.0, data_axes=("data",),
                            model_axis: str = "model", donate: bool = True,
                            attn_backend: str | None = None):
    """Decode step for the hybrid family. KV pool covers the attn sites
    (Lk = num_layers // attn_every); ssm/conv states cover mamba layers.
    TP: mamba channels + attn heads sharded. EP: full DP (batch sharded,
    weights replicated) — the attention stack replication of the paper's EP.
    """
    layout = get_layout(layout).base   # sized specs ("tp@4") dispatch as base
    m, da = model_axis, data_axes
    G = mesh.shape[m]
    L, k_every = cfg.num_layers, cfg.attn_every
    groups = L // k_every
    page = cc.page_size
    maxp = cc.max_pages_per_req
    view = cc.view_shape(cfg, G, layout)
    bs = Bslot // G if layout == EP else Bslot
    bspec2 = P(da, m) if layout == EP else P(da, None)
    bspec3 = P(da, m, None) if layout == EP else P(da, None, None)
    flat_spec = P(da, m)
    tp = layout == TP
    if layout == EP:
        conv_spec = P(da, m, None, None, None)
        ssm_spec = P(da, m, None, None, None, None)
        conv_x_spec = conv_spec
    else:
        conv_x_spec = P(da, None, None, None, m)
        conv_spec = P(da, None, None, None, None)
        ssm_spec = P(da, None, None, m, None, None)
    lspec = ssm_pack_specs(cfg, layout, m)

    def body(pack, kv_flat, conv_x, conv_B, conv_C, ssm_st,
             tokens, positions, valid, block_table, key):
        tokens = tokens.reshape(bs)
        positions = positions.reshape(bs)
        bt = block_table.reshape(bs, maxp)
        pool = kv_flat.reshape(view)                  # (Lk,2,pages,...)
        key = jax.random.wrap_key_data(key)
        x = _embed_lookup(cfg, pack, tokens, layout, m)
        pos_mat = positions[:, None]
        pidx = jnp.clip(pos_mat // page, 0, maxp - 1)
        page_ids = jnp.where(valid.reshape(bs, 1) > 0,
                             jnp.take_along_axis(bt, pidx, axis=1), 0)
        slots = pos_mat % page
        kv_total = positions + 1
        # rope tables are attention-site-invariant: compute once
        cos, sin = rope_cos_sin(pos_mat, cfg.dh, cfg.rope_theta)

        mv = lambda a: jnp.moveaxis(
            a.reshape((bs,) + a.shape[2:]), 1, 0)     # (L, bs, ...)
        cxs, cBs, cCs, sts = mv(conv_x), mv(conv_B), mv(conv_C), mv(ssm_st)
        sp = pack["shared_attn"]
        if tp:   # squeeze the rank-major G dim (local 1) off attention
            sp = dict(sp)
            sp["attn"] = {k: v.squeeze(0) for k, v in sp["attn"].items()}
        new_states = []
        new_pool = []
        for g in range(groups):
            def mamba_layer(h, xs):
                lp, cx, cB, cC, st = xs
                hn = apply_norm(cfg, h, lp["norm_in"])
                y, ncs, nst = _ssm_decode_layer(cfg, lp["ssm"], hn,
                                                (cx, cB, cC), st, layout, m)
                return h + y.astype(h.dtype), ncs + (nst,)
            sl = slice(g * k_every, (g + 1) * k_every)
            lp_g = jax.tree.map(lambda v: v[sl], pack["layers"]["ssm"])
            nrm_g = jax.tree.map(lambda v: v[sl], pack["layers"]["norm"])
            x, outs = lax.scan(mamba_layer, x,
                               ({"ssm": lp_g, "norm_in": nrm_g},
                                cxs[sl], cBs[sl], cCs[sl], sts[sl]))
            new_states.append(outs)
            # shared attention site g
            hn = apply_norm(cfg, x[:, None], sp["attn_norm"])
            q, kk, vv = _project_heads(cfg, sp["attn"], hn, cos, sin)
            pool_g = _write_pages(pool[g], kk, vv, page_ids, slots)
            at = paged_attention(q, pool_g[0], pool_g[1], bt, kv_total,
                                 q_offset=positions, window=0,
                                 backend=attn_backend)
            at = at.reshape(bs, -1) @ sp["attn"]["wo"]
            if tp:
                at = lax.psum(at, m)
            x = x + at.astype(x.dtype)
            hn = apply_norm(cfg, x, sp["mlp_norm"])
            hh = jax.nn.gelu(hn @ sp["mlp"]["w_up"])
            y = hh @ sp["mlp"]["w_down"]
            if tp:
                y = lax.psum(y, m)
            x = x + y.astype(x.dtype)
            new_pool.append(pool_g)
        x = apply_norm(cfg, x, pack["final_norm"])
        nxt = _sample(cfg, pack, x, layout, m, key, temperature, 0)
        ncx = jnp.concatenate([ns[0] for ns in new_states], 0)
        ncB = jnp.concatenate([ns[1] for ns in new_states], 0)
        ncC = jnp.concatenate([ns[2] for ns in new_states], 0)
        nst = jnp.concatenate([ns[3] for ns in new_states], 0)
        back = lambda a, proto: jnp.moveaxis(a, 0, 1).reshape(proto.shape)
        return (nxt.reshape(1, bs), jnp.stack(new_pool, 0).reshape(kv_flat.shape),
                back(ncx, conv_x), back(ncB, conv_B), back(ncC, conv_C),
                back(nst, ssm_st))

    vocab_spec = P(m, None) if tp else P()
    attn_w = ({k: P(*([m] + [None] * 2)) if k in ("wq", "wk", "wv", "wo")
               else P(m, None) for k in ("wq", "wk", "wv", "wo")}
              if tp else {k: P() for k in ("wq", "wk", "wv", "wo")})
    pspecs = {
        "embed": vocab_spec, "lm_head": vocab_spec,
        "final_norm": {"scale": P()},
        "layers": {"norm": {"scale": P()}, "ssm": lspec},
        "shared_attn": {
            "attn_norm": {"scale": P()},
            "mlp_norm": {"scale": P()},
            "attn": attn_w,
            "mlp": {"w_up": P(None, m) if tp else P(),
                    "w_down": P(m, None) if tp else P()},
        },
    }
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, flat_spec, conv_x_spec, conv_spec, conv_spec,
                  ssm_spec, bspec3, bspec2, bspec2, bspec3, P()),
        out_specs=(bspec2, flat_spec, conv_x_spec, conv_spec, conv_spec,
                   ssm_spec),
        check_vma=False)
    return jax.jit(smapped, donate_argnums=(1, 2, 3, 4, 5) if donate else ())


def hybrid_decode_pack(cfg: ModelConfig, params: dict, layout: str, G: int):
    """Hybrid stored params -> decode pack (rank-major shared attention)."""
    sp = dict(params["shared_attn"])
    if get_layout(layout).base is TP:
        sp = dict(sp)
        sp["attn"] = attn_rank_major(cfg, params["shared_attn"]["attn"], G)
    pack = {
        "embed": params["embed"], "lm_head": params["lm_head"],
        "final_norm": params["final_norm"],
        "layers": params["ssm_layers"],
        "shared_attn": sp,
    }
    return pack


# ---------------------------------------------------------------------------
# Encoder-decoder (Whisper) decode
# ---------------------------------------------------------------------------

def build_encdec_serve_step(cfg: ModelConfig, mesh, layout: str,
                            cc: CacheConfig, Bslot: int, T_enc: int,
                            Sq: int = 1, *,
                            temperature: float = 0.0, data_axes=("data",),
                            model_axis: str = "model", donate: bool = True,
                            attn_backend: str | None = None):
    """Decoder serve step. cross_kv (Dd, Bslot, L, 2, T_enc, K, dh) is the
    per-slot cross-attention cache (computed once per request at admission).

    Mixed-row contract as steps.build_mixed_step: tokens (Dd, Bslot, Sq),
    `positions` = each row's start position, `valid` = n_tokens valid this
    dispatch (1 for decode rows, 0 = dead slot). Invalid tail tokens write
    their self-attn KV to the null page 0; cross-attention is non-causal
    over the full encoder cache, so chunking needs no extra mask there.
    Sq == 1 is the classic decode step.
    """
    layout = get_layout(layout).base   # sized specs ("tp@4") dispatch as base
    m, da = model_axis, data_axes
    G = mesh.shape[m]
    gi = group_info(cfg, G)
    L = cfg.num_layers
    page = cc.page_size
    maxp = cc.max_pages_per_req
    view = cc.view_shape(cfg, G, layout)
    bs = Bslot // G if layout == EP else Bslot
    tp = layout == TP
    bspec2 = P(da, m) if layout == EP else P(da, None)
    bspec3 = P(da, m, None) if layout == EP else P(da, None, None)
    flat_spec = P(da, m)
    xkv_spec = (P(da, m, None, None, None, None, None) if layout == EP
                else P(da, None, None, None, None, m, None))

    def body(pack, kv_flat, cross_kv, tokens, positions, valid,
             block_table, key):
        tokens = tokens.reshape(bs, Sq)
        positions = positions.reshape(bs)
        valid = valid.reshape(bs)
        bt = block_table.reshape(bs, maxp)
        pool = kv_flat.reshape(view)
        xkv = cross_kv.reshape((bs,) + cross_kv.shape[2:])  # (bs,L,2,T,Kl,dh)
        key = jax.random.wrap_key_data(key)
        pos_mat = positions[:, None] + jnp.arange(Sq)[None, :]   # (bs,Sq)
        x = _embed_lookup(cfg, pack, tokens.reshape(-1), layout, m)
        x = x.reshape(bs, Sq, -1)
        x = x + pack["dec_pos"][
            jnp.clip(pos_mat, 0, cfg.max_positions - 1)].astype(x.dtype)
        # zero dead slots (garbage hiddens poison shared einsums: NaN*0==NaN)
        x = x * (valid > 0).astype(x.dtype)[:, None, None]
        pidx = jnp.clip(pos_mat // page, 0, maxp - 1)
        in_chunk = jnp.arange(Sq)[None, :] < valid[:, None]
        page_ids = jnp.where(in_chunk,
                             jnp.take_along_axis(bt, pidx, axis=1), 0)
        slots = pos_mat % page
        kv_total = positions + valid
        # rope tables are layer-invariant: compute once, not per layer
        cos, sin = rope_cos_sin(pos_mat, cfg.dh, cfg.rope_theta)

        def layer_fn(h, xs):
            lp, pool_l, xkv_l = xs                    # xkv_l (bs,2,T,Kl,dh)
            if tp:   # squeeze rank-major G dim off per-layer attn slices
                lp = dict(lp)
                lp["attn"] = {k: v.squeeze(0) for k, v in lp["attn"].items()}
                lp["xattn"] = {k: v.squeeze(0)
                               for k, v in lp["xattn"].items()}
            hn = apply_norm(cfg, h, lp["attn_norm"])
            q, kk, vv = _project_heads(cfg, lp["attn"], hn, cos, sin)
            pool_l = _write_pages(pool_l, kk, vv, page_ids, slots)
            at = paged_attention(q, pool_l[0], pool_l[1], bt, kv_total,
                                 q_offset=positions, window=0,
                                 backend=attn_backend)
            at = at.reshape(bs, Sq, -1) @ lp["attn"]["wo"]
            if tp:
                at = lax.psum(at, m)
            h = h + at.astype(h.dtype)
            # cross attention over the per-slot dense cache (non-causal:
            # every query row attends to the whole encoder sequence, so
            # chunk rows need no extra masking here)
            hn = apply_norm(cfg, h, lp["xattn_norm"])
            dh_ = cfg.dh
            qx = (hn @ lp["xattn"]["wq"]).reshape(bs, Sq, -1, dh_)
            from repro.models.common import flash_attention
            xat = flash_attention(qx, xkv_l[:, 0], xkv_l[:, 1], causal=False)
            xat = xat.reshape(bs, Sq, -1) @ lp["xattn"]["wo"]
            if tp:
                xat = lax.psum(xat, m)
            h = h + xat.astype(h.dtype)
            hn = apply_norm(cfg, h, lp["mlp_norm"])
            hh = jax.nn.gelu(hn @ lp["mlp"]["w_up"])
            y = hh @ lp["mlp"]["w_down"]
            if tp:
                y = lax.psum(y, m)
            return h + y.astype(h.dtype), pool_l

        x, new_pool = lax.scan(layer_fn, x,
                               (pack["decoder"], pool,
                                jnp.moveaxis(xkv, 1, 0)))
        x = apply_norm(cfg, x, pack["final_norm"])
        # sample at the last valid position of each row
        last = jnp.clip(valid - 1, 0, Sq - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        nxt = _sample(cfg, pack, xl, layout, m, key, temperature, 0)
        return nxt.reshape(1, bs), new_pool.reshape(kv_flat.shape)

    norm = lambda: jax.tree.map(lambda _: P(), {"scale": 0, "bias": 0}) \
        if cfg.norm_type == "layernorm" else {"scale": P()}
    def normspec():
        base = {"scale": P()}
        if cfg.norm_type == "layernorm":
            base["bias"] = P()
        return base
    attn_spec = ({k: P(None, m, None, None) for k in ("wq", "wk", "wv", "wo")}
                 if tp else {k: P() for k in ("wq", "wk", "wv", "wo")})
    vocab_spec = P(m, None) if tp else P()
    pspecs = {
        "embed": vocab_spec,
        "dec_pos": P(),
        "final_norm": normspec(),
        "decoder": {
            "attn_norm": normspec(), "xattn_norm": normspec(),
            "mlp_norm": normspec(),
            "attn": dict(attn_spec),
            "xattn": dict(attn_spec),
            "mlp": {"w_up": P(None, None, m) if tp else P(),
                    "w_down": P(None, m, None) if tp else P()},
        },
    }
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, flat_spec, xkv_spec, bspec3, bspec2, bspec2,
                  bspec3, P()),
        out_specs=(bspec2, flat_spec), check_vma=False)
    return jax.jit(smapped, donate_argnums=(1,) if donate else ())


def encdec_decode_pack(cfg: ModelConfig, params: dict, layout: str, G: int):
    dec = dict(params["decoder"])
    if get_layout(layout).base is TP:
        dec["attn"] = attn_rank_major(cfg, params["decoder"]["attn"], G)
        dec["xattn"] = attn_rank_major(cfg, params["decoder"]["xattn"], G)
    return {
        "embed": params["embed"], "dec_pos": params["dec_pos"],
        "final_norm": params["final_norm"], "decoder": dec,
    }
