"""Serve step for the state-space family (ssm, Mamba2).

Same layout semantics as serving/steps.py, adapted (DESIGN.md
§Arch-applicability): no KV cache — the switchable state is the SSD
recurrent state + conv tail. "EP" = DP (batch over model axis, weights
replicated); TP shards inner channels/heads, with explicit psums for the
gated RMSNorm (sum-of-squares over the sharded d_inner) and out_proj.

Mixed-row contract (DESIGN.md §10): the SSD recurrence advances one token
per dispatch, so the step keeps Sq == 1 and its rows degenerate to
n_tokens ∈ {0, 1}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.layouts import EP, TP, get_layout
from repro.models.common import ModelConfig, apply_norm
from repro.models.ssm import ssd_decode_step
from repro.serving.steps import _embed_lookup, _sample


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
