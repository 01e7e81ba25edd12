"""Layout-aware serve steps (mixed decode + prefill-chunk rows) under
shard_map.

These are the per-layout runtimes the paper keeps resident (§4.4): each is
AOT-compiled against fixed avals/shardings for a ladder of batch-slot
sizes. `build_mixed_step` is the ONE step function: rows carry per-row
`(start_pos, n_tokens)`, so a batch may mix single-token decode rows with
prefill chunks under a single dispatch (DESIGN.md §10).

Transformer families (dense / moe / vlm). Batch geometry per layout:
  TP: batch slots replicated over the model axis; heads sharded (rank-major
      attention weights; wo pre-scaled for replicated head blocks).
  EP: batch slots sharded over the model axis (slot s lives on rank
      s // (Bslot/G)); attention weights replicated; experts rank-local with
      all_to_all dispatch.

KV pool: the unified flat buffer's layout view (serving/kvcache.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.layouts import LayoutSpec, attn_rank_major, get_layout
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.common import (ModelConfig, apply_norm, apply_rope,
                                 rmsnorm, rope_cos_sin)
from repro.models.moe import moe_decode_ep, moe_decode_tp
from repro.serving.kvcache import CacheConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Decode param packs (per-layout stored forms + shard_map specs)
# ---------------------------------------------------------------------------

def build_decode_pack(cfg: ModelConfig, params: dict, layout: str, G: int):
    """Stored layout params (from core.layouts.pack_params) -> decode pack.

    TP expands attention to rank-major (the paper's dual-mode attention
    buffer); EP keeps global attention weights replicated.
    """
    spec = get_layout(layout)
    lp = params["layers"]
    pack = {"embed": params["embed"], "final_norm": params["final_norm"]}
    if "lm_head" in params:
        pack["lm_head"] = params["lm_head"]
    lpack = {"attn_norm": lp["attn_norm"], "mlp_norm": lp["mlp_norm"]}
    if spec.dense_tp:
        lpack["attn"] = attn_rank_major(cfg, lp["attn"], G)   # (L, G, ...)
    else:
        lpack["attn"] = lp["attn"]
    if cfg.is_moe:
        lpack["moe"] = lp["moe"]
    else:
        lpack["mlp"] = lp["mlp"]
    pack["layers"] = lpack
    return pack


def decode_pack_specs(cfg: ModelConfig, pack, layout: str,
                      m: str = "model", ep_axes=None):
    """PartitionSpec pytree matching a decode pack (works on shapes).
    ep_axes: expert-sharding axes (full-mesh layouts: data x model)."""
    spec = get_layout(layout)
    exp_ax = ep_axes if (spec.expert_full_mesh and ep_axes) else m
    vocab_spec = P(m, None) if spec.dense_tp else P()
    specs = {"embed": vocab_spec,
             "final_norm": jax.tree.map(lambda _: P(), pack["final_norm"])}
    if "lm_head" in pack:
        specs["lm_head"] = vocab_spec
    lp = pack["layers"]
    lspec = {"attn_norm": jax.tree.map(lambda _: P(), lp["attn_norm"]),
             "mlp_norm": jax.tree.map(lambda _: P(), lp["mlp_norm"])}
    if spec.dense_tp:
        lspec["attn"] = {k: P(*([None, m] + [None] * (v.ndim - 2)))
                         for k, v in lp["attn"].items()}
    else:
        lspec["attn"] = jax.tree.map(lambda _: P(), lp["attn"])
    if cfg.is_moe:
        # shared experts follow the expert compute path: width-sharded under
        # the TP expert rule (partial-psum), replicated under EP dispatch
        shared_tp = spec.expert_kind == "tp"
        ms: dict = {"router": P(),
                    "w13": P(None, exp_ax, None, None, None),
                    "w2": P(None, exp_ax, None, None, None)}
        for k in ("shared_wg", "shared_wu", "shared_w2", "shared_gate"):
            if k in lp["moe"]:
                if shared_tp and k in ("shared_wg", "shared_wu"):
                    ms[k] = P(None, m, None)
                elif shared_tp and k == "shared_w2":
                    ms[k] = P(None, None, m)
                else:
                    ms[k] = P()
        lspec["moe"] = ms
    else:
        lspec["mlp"] = {k: (P(None, None, m) if k in ("w_gate", "w_up")
                            else P(None, m, None))
                        for k in lp["mlp"]}
    specs["layers"] = lspec
    return specs


# ---------------------------------------------------------------------------
# Per-rank building blocks (inside shard_map)
# ---------------------------------------------------------------------------

def _embed_lookup(cfg, pack, tokens, spec: LayoutSpec, m: str,
                  scale: bool | None = None):
    """tokens (bs,) -> x (bs, D). TP-like: vocab-sharded gather + psum.
    The sqrt(D) embed scale applies only to families whose reference
    forward scales (transformer lm_forward); ssm/hybrid/encdec do not."""
    emb = pack["embed"]
    if scale is None:
        scale = cfg.family in ("dense", "moe", "vlm")
    sc = (jnp.sqrt(jnp.float32(cfg.d_model)).astype(cfg.compute_dtype)
          if scale else jnp.ones((), cfg.compute_dtype))
    if not spec.dense_tp:
        return emb[tokens].astype(cfg.compute_dtype) * sc
    Vloc = emb.shape[0]
    r = lax.axis_index(m)
    local = tokens - r * Vloc
    ok = (local >= 0) & (local < Vloc)
    x = jnp.where(ok[:, None], emb[jnp.clip(local, 0, Vloc - 1)], 0)
    return lax.psum(x.astype(cfg.compute_dtype), m) * sc


def _project_heads(cfg, ap, x, cos, sin):
    """x (bs, S, D) -> q (bs,S,hl,dh), k/v (bs,S,kl,dh) with rope+qknorm.
    ap: TP rank-major local slices (L-dim and G-dim already consumed).
    cos/sin: rope tables for the chunk's positions, computed ONCE per step
    (they are layer-invariant) and threaded through the layer scan."""
    bs, S, D = x.shape
    dh = cfg.dh
    q = (x @ ap["wq"])
    k = (x @ ap["wk"])
    v = (x @ ap["wv"])
    hl = q.shape[-1] // dh
    kl = k.shape[-1] // dh
    q = q.reshape(bs, S, hl, dh)
    k = k.reshape(bs, S, kl, dh)
    v = v.reshape(bs, S, kl, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, ap["q_norm"])
        k = rmsnorm(k, ap["k_norm"])
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _write_pages(pool_l, k, v, page_ids, slots):
    """pool_l (2, pages, page, Kh, dh); k/v (bs, S, Kh, dh);
    page_ids/slots (bs, S) -> updated pool."""
    bs, S = page_ids.shape
    pid = page_ids.reshape(-1)
    sl = slots.reshape(-1)
    kv = jnp.stack([k.reshape(bs * S, *k.shape[2:]),
                    v.reshape(bs * S, *v.shape[2:])], axis=0)
    return pool_l.at[:, pid, sl].set(kv.astype(pool_l.dtype))


def _ffn(cfg, lpk, h_flat, spec: LayoutSpec, m, lay_exp, cap_factor,
         ep_axes=None, moe_backend=None, *, li):
    """h_flat (T, D) -> ((T, D) ffn output, (2,) int32 expert weight passes
    of this rank: the grouped GEMM's and the dense grid's, 0 for a dense
    MLP); TP-style paths return AFTER psum.
    MoE: lpk["moe"]'s w13/w2 are the layer stacks, used at layer li."""
    if cfg.is_moe:
        passes: list = []
        if spec.expert_kind == "tp":
            part = moe_decode_tp(cfg, lpk["moe"], h_flat, m, li=li,
                                 cap_factor=cap_factor,
                                 moe_backend=moe_backend, passes=passes)
            return lax.psum(part, m), passes[0]
        if spec.expert_full_mesh:
            # TP attention feeds a replicated batch; each model rank owns
            # its 1/G token slice and dispatches over the FULL mesh
            r = lax.axis_index(m)
            T = h_flat.shape[0]
            Gm = jax.lax.psum(1, m)
            Tl = T // Gm
            mine = lax.dynamic_slice_in_dim(h_flat, r * Tl, Tl, 0)
            y = moe_decode_ep(cfg, lpk["moe"], mine, ep_axes, lay_exp,
                              li=li, cap_factor=cap_factor,
                              moe_backend=moe_backend, passes=passes)
            return lax.all_gather(y, m, axis=0, tiled=True), passes[0]
        y = moe_decode_ep(cfg, lpk["moe"], h_flat, m, lay_exp, li=li,
                          cap_factor=cap_factor, moe_backend=moe_backend,
                          passes=passes)
        return y, passes[0]
    none = jnp.zeros((2,), jnp.int32)
    mlp = lpk["mlp"]
    if spec.dense_tp:
        if cfg.mlp_type == "swiglu":
            hh = jax.nn.silu(h_flat @ mlp["w_gate"]) * (h_flat @ mlp["w_up"])
        else:
            hh = jax.nn.gelu(h_flat @ mlp["w_up"])
        return lax.psum(hh @ mlp["w_down"], m), none
    # DP dense: DP attention + TP MLP -> all_gather tokens, width-local MLP,
    # reduce_scatter back (same per-layer volume as TP's all-reduce)
    full = lax.all_gather(h_flat, m, axis=0, tiled=True)       # (T*G, D)
    if cfg.mlp_type == "swiglu":
        hh = jax.nn.silu(full @ mlp["w_gate"]) * (full @ mlp["w_up"])
    else:
        hh = jax.nn.gelu(full @ mlp["w_up"])
    out = hh @ mlp["w_down"]
    return lax.psum_scatter(out, m, scatter_dimension=0, tiled=True), none


def _sample(cfg, pack, x, spec: LayoutSpec, m, key, temperature, slot0):
    """x (bs, D) -> sampled tokens (bs,) int32 (Gumbel-max; exact)."""
    head = pack["embed"] if cfg.tie_embeddings else pack["lm_head"]
    logits = (x @ head.T.astype(x.dtype)).astype(jnp.float32)
    V = cfg.vocab_size
    bs = x.shape[0]
    r = lax.axis_index(m) if spec.dense_tp else None
    if spec.dense_tp:
        Vloc = head.shape[0]
        col0 = r * Vloc
        cols = col0 + jnp.arange(Vloc)
        logits = jnp.where(cols[None, :] < V, logits, NEG_INF)
        if temperature > 0:
            kr = jax.random.fold_in(key, r)
            g = -jnp.log(-jnp.log(jax.random.uniform(
                kr, logits.shape, jnp.float32, 1e-20, 1.0)))
            logits = logits / temperature + g
        loc_arg = jnp.argmax(logits, axis=-1)
        loc_val = jnp.max(logits, axis=-1)
        vals = lax.all_gather(loc_val, m)              # (G, bs)
        args = lax.all_gather(col0 + loc_arg, m)       # (G, bs)
        win = jnp.argmax(vals, axis=0)                 # (bs,)
        return jnp.take_along_axis(args, win[None], axis=0)[0].astype(jnp.int32)
    cols = jnp.arange(head.shape[0])
    logits = jnp.where(cols[None, :] < V, logits, NEG_INF)
    if temperature > 0:
        kr = jax.random.fold_in(key, lax.axis_index(m))
        g = -jnp.log(-jnp.log(jax.random.uniform(
            kr, logits.shape, jnp.float32, 1e-20, 1.0)))
        logits = logits / temperature + g
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def _squeeze_pack(cfg, spec: LayoutSpec, pack: dict) -> dict:
    """Squeeze the rank-major G dim (local size 1) out of per-rank tensors."""
    layers = dict(pack["layers"])
    if spec.dense_tp:
        layers["attn"] = {k: v.squeeze(1)
                          for k, v in layers["attn"].items()}
    if cfg.is_moe:
        mo = dict(layers["moe"])
        mo["w13"] = mo["w13"].squeeze(1)
        mo["w2"] = mo["w2"].squeeze(1)
        layers["moe"] = mo
    pack = dict(pack)
    pack["layers"] = layers
    return pack


def _chunk_core(cfg, spec: LayoutSpec, pack, pool, tokens, positions,
                valid_len, bt, key, *, m, lay_exp, ep_axes, attn_backend,
                moe_backend, temperature, page, maxp, Sq):
    """One Sq-token step on squeezed per-rank params (inside shard_map).

    tokens (bs, Sq); positions/valid_len (bs,); bt (bs, maxp); pool = the
    layout's KV view. Returns (next_token (bs,), new_pool, last_hidden,
    passes): passes (2,) int32 sums this rank's expert weight passes over
    layers and both GEMMs, the grouped GEMM's and the dense grid's (`_ffn`).
    Shared verbatim by the single-step builder and the fused decode loop so
    both paths run byte-identical math.
    """
    bs = tokens.shape[0]
    x = _embed_lookup(cfg, pack, tokens.reshape(-1), spec, m)
    x = x.reshape(bs, Sq, cfg.d_model)
    # zero dead slots: garbage hiddens would otherwise contaminate
    # shared dispatch einsums (NaN*0 == NaN)
    x = x * (valid_len > 0).astype(x.dtype)[:, None, None]
    pos_mat = positions[:, None] + jnp.arange(Sq)[None, :]   # (bs,Sq)
    # page targets for the chunk's K/V (invalid tail -> null page 0)
    pidx = jnp.clip(pos_mat // page, 0, maxp - 1)
    in_chunk = jnp.arange(Sq)[None, :] < valid_len[:, None]
    page_ids = jnp.where(in_chunk,
                         jnp.take_along_axis(bt, pidx, axis=1), 0)
    slots = pos_mat % page
    kv_total = positions + valid_len                   # (bs,)
    # rope tables are layer-invariant: compute once, thread into the scan
    cos, sin = rope_cos_sin(pos_mat, cfg.dh, cfg.rope_theta)
    # the expert stacks stay out of the scan's xs: the grouped GEMM reads
    # layer li's tiles straight from them, where a per-layer slice would
    # be copied into a fresh buffer for the kernel on every layer and step
    layers, experts = pack["layers"], {}
    if cfg.is_moe:
        moe = dict(layers["moe"])
        experts = {k: moe.pop(k) for k in ("w13", "w2")}
        layers = {**layers, "moe": moe}

    def layer_fn(carry, xs):
        h, pool, passes = carry
        lpk, li = xs
        if experts:
            lpk = dict(lpk, moe=dict(lpk["moe"], **experts))
        # the pool rides the CARRY (dynamic per-layer slice update) rather
        # than the scan's xs/ys: emitting a stacked new pool per step would
        # materialize a full pool copy per call — per *substep* in the
        # fused loop — which XLA can elide for an in-place carry update
        pool_l = lax.dynamic_index_in_dim(pool, li, axis=0, keepdims=False)
        hn = apply_norm(cfg, h, lpk["attn_norm"])
        q, k, v = _project_heads(cfg, lpk["attn"], hn, cos, sin)
        pool_l = _write_pages(pool_l, k, v, page_ids, slots)
        attn = paged_attention(
            q, pool_l[0], pool_l[1], bt, kv_total,
            q_offset=positions, window=cfg.sliding_window,
            backend=attn_backend)
        attn = attn.reshape(bs, Sq, -1) @ lpk["attn"]["wo"]
        if spec.dense_tp:       # heads are sharded -> partial outputs
            attn = lax.psum(attn, m)
        h = h + attn.astype(h.dtype)
        hn = apply_norm(cfg, h, lpk["mlp_norm"])
        y, ps = _ffn(cfg, lpk, hn.reshape(bs * Sq, -1), spec, m, lay_exp,
                     cap_factor=None, ep_axes=ep_axes,
                     moe_backend=moe_backend, li=li)
        h = h + y.reshape(bs, Sq, -1).astype(h.dtype)
        pool = lax.dynamic_update_index_in_dim(pool, pool_l, li, axis=0)
        return (h, pool, passes + ps), None

    L = pool.shape[0]
    (x, new_pool, passes), _ = lax.scan(
        layer_fn, (x, pool, jnp.zeros((2,), jnp.int32)),
        (layers, jnp.arange(L)))
    x = apply_norm(cfg, x, pack["final_norm"])
    # sample at the last valid position of each slot
    last = jnp.clip(valid_len - 1, 0, Sq - 1)
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    nxt = _sample(cfg, pack, xl, spec, m, key, temperature, 0)
    return nxt, new_pool, xl, passes


def _row_spec(spec: LayoutSpec, m, da) -> P:
    """Spec of a (Dd, Bslot) per-row array: slots split over the model
    axis where the layout shards them, else replicated over it."""
    return P(da, m) if spec.slots_sharded else P(da, None)


def carry_sharding(mesh, layout, *, data_axes=("data",),
                   model_axis: str = "model") -> NamedSharding:
    """Sharding of the serve step's carried token buffer in `layout`."""
    return NamedSharding(mesh, _row_spec(get_layout(layout), model_axis,
                                         tuple(data_axes)))


def _layout_geometry(cfg, mesh, layout, cc, Bslot, m, da):
    """Shared builder geometry: spec, shard specs, expert layout, KV view."""
    spec = get_layout(layout)
    G = mesh.shape[m]
    ep_axes = tuple(da) + (m,)
    chips = int(np.prod([mesh.shape[a] for a in ep_axes]))
    geo = dict(
        spec=spec, G=G, ep_axes=ep_axes,
        G_exp=spec.expert_group(G, chips),
        lay_exp=spec.expert_layout(cfg, G, chips),
        page=cc.page_size, maxp=cc.max_pages_per_req,
        view=cc.view_shape(cfg, G, spec),      # (L,2,pages,page,Kh,dh)
        bs=Bslot // G if spec.slots_sharded else Bslot,
        bspec2=_row_spec(spec, m, da),
        bspec3=P(da, m, None) if spec.slots_sharded else P(da, None, None),
        flat_spec=P(da, m))
    return geo


def _pack_specs_for(cfg, layout, G, G_exp, m, ep_axes):
    pack_shapes = jax.eval_shape(
        lambda p: build_decode_pack(cfg, p, layout, G),
        _params_like(cfg, layout, G, G_exp))
    return decode_pack_specs(cfg, pack_shapes, layout, m, ep_axes=ep_axes)


def build_mixed_step(cfg: ModelConfig, mesh, layout: str, cc: CacheConfig,
                     Bslot: int, Sq: int = 1, *, temperature: float = 0.0,
                     data_axes=("data",), model_axis: str = "model",
                     attn_backend: str | None = None,
                     moe_backend: str | None = None,
                     return_logits: bool = False, donate: bool = True):
    """Build THE jitted serve step: one dispatch whose rows each carry a
    per-row `(start_pos, n_tokens)`, so decode rows (n_tokens == 1) and
    prefill-chunk rows (1 <= n_tokens <= Sq) share `_chunk_core` — the
    same attention mask, KV write path, and sampling — under a single
    compiled executable (DESIGN.md §10). Sq == 1 specializes it to the
    classic decode step; a pure prefill batch is just every row carrying
    a chunk. There is no separate prefill or decode step function.

    Global signature:
      pack, kv_flat (Dd, G, *rank_shape), tokens (Dd, Bslot, Sq), positions (Dd, Bslot),
      valid_len (Dd, Bslot), block_table (Dd, Bslot, maxp), key,
      carry (Dd, Btop), src (Dd, Bslot)
      -> (next_token (Dd, Bslot), kv_flat', passes (Dd, G, 2), carry')
    `carry` is the carried token buffer: the previous dispatch's next
    tokens, each rank's at the front of its own block, so a decode row
    whose input token is still on the device takes it from there. `src`
    is the row's index into its rank's block of `carry` (-1: the host
    staged the token in `tokens`). `carry'` holds this step's next tokens
    the same way. Its shape is the top rung's whatever `Bslot` is, so a
    change of rung needs no other executable.
    `passes[d, r]` = rank r's expert weight passes of the step, summed
    over layers and both expert GEMMs: the grouped GEMM's, then the dense
    grid's (`_chunk_core`); zeros for a dense model.
    `positions` = global KV position of tokens[:, :, 0] (a decode row's
    kv_len - 1, a prefill row's prefill_pos);
    `valid_len` = #valid tokens in the row (1 for decode; 0 = dead slot).
    Invalid tail tokens of a short row write their KV to the reserved
    null page 0 and are masked out of attention; each row samples at its
    last valid position.
    """
    m, da = model_axis, data_axes
    g = _layout_geometry(cfg, mesh, layout, cc, Bslot, m, da)
    spec, bs, maxp = g["spec"], g["bs"], g["maxp"]
    bspec2, bspec3, flat_spec = g["bspec2"], g["bspec3"], g["flat_spec"]

    def body(pack, kv_flat, tokens, positions, valid_len, block_table, key,
             carry, src):
        carry = carry.reshape(-1)
        src = src.reshape(bs)
        tokens = tokens.reshape(bs, Sq)
        fed = jnp.where(src >= 0, carry[jnp.maximum(src, 0)], tokens[:, 0])
        tokens = tokens.at[:, 0].set(fed)
        positions = positions.reshape(bs)
        valid_len = valid_len.reshape(bs)
        bt = block_table.reshape(bs, maxp)
        pool = kv_flat.reshape(g["view"])                  # (L,2,pages,...)
        key = jax.random.wrap_key_data(key)
        pack = _squeeze_pack(cfg, spec, pack)
        nxt, new_pool, xl, passes = _chunk_core(
            cfg, spec, pack, pool, tokens, positions, valid_len, bt, key,
            m=m, lay_exp=g["lay_exp"], ep_axes=g["ep_axes"],
            attn_backend=attn_backend, moe_backend=moe_backend,
            temperature=temperature, page=g["page"], maxp=maxp, Sq=Sq)
        out = (nxt.reshape(1, bs), new_pool.reshape(kv_flat.shape),
               passes.reshape(1, 1, 2), carry.at[:bs].set(nxt)[None])
        if return_logits:
            head = pack["embed"] if cfg.tie_embeddings else pack["lm_head"]
            lg = (xl @ head.T.astype(xl.dtype)).astype(jnp.float32)
            if spec.dense_tp:
                lg = lax.all_gather(lg, m, axis=1, tiled=True)  # (bs, Vp)
            out = out + (lg.reshape(1, bs, -1),)
        return out

    pspecs = _pack_specs_for(cfg, layout, g["G"], g["G_exp"], m, g["ep_axes"])
    out_specs = (bspec2, flat_spec, P(da, m, None), bspec2)
    if return_logits:
        out_specs = out_specs + ((P(da, m, None) if spec.slots_sharded
                                  else P(da, None, None)),)
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, flat_spec, bspec3, bspec2, bspec2, bspec3, P(),
                  bspec2, bspec2),
        out_specs=out_specs, check_vma=False)
    donate_args = (1,) if donate else ()
    return jax.jit(smapped, donate_argnums=donate_args)


def build_decode_loop(cfg: ModelConfig, mesh, layout: str, cc: CacheConfig,
                      Bslot: int, steps: int, *, temperature: float = 0.0,
                      data_axes=("data",), model_axis: str = "model",
                      attn_backend: str | None = None,
                      moe_backend: str | None = None, donate: bool = True):
    """Fuse `steps` decode substeps under ONE dispatch (DESIGN.md §5).

    A `lax.fori_loop` over the single-step body: the sampled token is fed
    straight back as the next input on device, positions and page slots
    advance on device, and slots whose remaining-token budget hits zero are
    masked out (their KV writes land on the null page, their outputs are 0).

    Global signature:
      pack, kv_flat (Dd, G, *rank_shape), tokens (Dd, B), positions (Dd, B),
      budgets (Dd, B), block_table (Dd, B, maxp), key
      -> (out_tokens (Dd, B, steps), kv_flat',
          tokens' (Dd, B), positions' (Dd, B), budgets' (Dd, B),
          passes (Dd, G, 2))

    `tokens` = last generated token per slot (its KV is written at
    `positions` on the first substep, mirroring the single-step feed).
    `budgets` = remaining tokens each slot may generate, decremented per
    substep on device; substep i of a slot with budget b is active iff
    i < b. out_tokens[:, :, i] is substep i's sample (0 when inactive).
    At temperature 0 (greedy) the fused loop is byte-identical to `steps`
    single-step calls; with sampling the key is folded per substep, which
    is a different stream than the engine's per-step fold. `passes` sums
    the single step's expert weight passes over the substeps.
    """
    m, da = model_axis, data_axes
    g = _layout_geometry(cfg, mesh, layout, cc, Bslot, m, da)
    spec, bs, maxp = g["spec"], g["bs"], g["maxp"]
    bspec2, bspec3, flat_spec = g["bspec2"], g["bspec3"], g["flat_spec"]

    def body(pack, kv_flat, tokens, positions, budgets, block_table, key):
        tokens = tokens.reshape(bs)
        positions = positions.reshape(bs)
        budgets = budgets.reshape(bs)
        bt = block_table.reshape(bs, maxp)
        pool = kv_flat.reshape(g["view"])
        key = jax.random.wrap_key_data(key)
        pack = _squeeze_pack(cfg, spec, pack)     # hoisted out of the loop

        def substep(i, carry):
            pool, tok, pos, bud, out, passes = carry
            active = (bud > 0).astype(jnp.int32)
            nxt, pool, _, ps = _chunk_core(
                cfg, spec, pack, pool, tok[:, None], pos, active, bt,
                jax.random.fold_in(key, i),
                m=m, lay_exp=g["lay_exp"], ep_axes=g["ep_axes"],
                attn_backend=attn_backend, moe_backend=moe_backend,
                temperature=temperature, page=g["page"], maxp=maxp, Sq=1)
            live = active > 0
            out = out.at[:, i].set(jnp.where(live, nxt, 0))
            return (pool, jnp.where(live, nxt, tok), pos + active,
                    bud - active, out, passes + ps)

        out0 = jnp.zeros((bs, steps), jnp.int32)
        pool, tok, pos, bud, out, passes = lax.fori_loop(
            0, steps, substep, (pool, tokens, positions, budgets, out0,
                                jnp.zeros((2,), jnp.int32)))
        return (out.reshape(1, bs, steps), pool.reshape(kv_flat.shape),
                tok.reshape(1, bs), pos.reshape(1, bs), bud.reshape(1, bs),
                passes.reshape(1, 1, 2))

    pspecs = _pack_specs_for(cfg, layout, g["G"], g["G_exp"], m, g["ep_axes"])
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, flat_spec, bspec2, bspec2, bspec2, bspec3, P()),
        out_specs=(bspec3, flat_spec, bspec2, bspec2, bspec2,
                   P(da, m, None)),
        check_vma=False)
    donate_args = (1,) if donate else ()
    return jax.jit(smapped, donate_argnums=donate_args)


_PARAMS_CACHE: dict = {}


def _params_like(cfg: ModelConfig, layout: str, G: int,
                 expert_G: int | None = None):
    """Shape-only *stored-form* param template (pack_params applied)."""
    key = (cfg.name, cfg.num_layers, cfg.d_model, cfg.vocab_size, layout, G,
           expert_G)
    if key not in _PARAMS_CACHE:
        from repro.core.layouts import pack_params
        from repro.models.registry import init_params
        import jax.random as jr
        _PARAMS_CACHE[key] = jax.eval_shape(
            lambda: pack_params(cfg, init_params(cfg, jr.PRNGKey(0)),
                                layout, G, expert_G))
    return _PARAMS_CACHE[key]
