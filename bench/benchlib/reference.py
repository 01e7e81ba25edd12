"""Plain reference of the served model, independent of the program.

`make_weights` draws the weights from the seed by the initialisation recipe
the program states (every matrix normal with std 1/sqrt(fan_in), router in
float32, norms at 1, keys split in the same order), in the served dtype, in
one jitted call; stacked layers can be spread over several chips.
`logits` runs the forward pass of the configuration file in float32 at
`highest` matmul precision, layer by layer and in blocks, over packed
sequences: each sequence is a prompt followed by the tokens it was served,
and attention never crosses sequences. Experts are computed densely, every
expert for every token, weighted by the renormalised top-k gates.

With `quant="fp8"` every bf16 matmul instead takes float8_e4m3 inputs
(per-row and per-column absmax scales, float32 accumulation): the control,
one precision step below the configuration's.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchlib.flops import Dims

HIGHEST = lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
F8_MAX = 448.0


def _normal(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _init(conf: dict, key) -> dict:
    m = Dims.of(conf)
    dt = DTYPES[conf["torch_dtype"]]
    L, D, H, K, dh = m.L, m.D, m.H, m.K, m.dh
    ks = list(jax.random.split(key, 8))
    w = {"embed": _normal(ks[0], (m.V, D), D, dt),
         "final_norm": jnp.ones((D,), dt)}
    if not conf["tie_word_embeddings"]:
        w["lm_head"] = _normal(ks[1], (m.V, D), D, dt)
    ka = list(jax.random.split(ks[2], 4))
    km = list(jax.random.split(ks[3], 5))
    lay = {"attn_norm": jnp.ones((L, D), dt), "mlp_norm": jnp.ones((L, D), dt),
           "wq": _normal(ka[0], (L, D, H * dh), D, dt),
           "wk": _normal(ka[1], (L, D, K * dh), D, dt),
           "wv": _normal(ka[2], (L, D, K * dh), D, dt),
           "wo": _normal(ka[3], (L, H * dh, D), H * dh, dt),
           "router": _normal(km[0], (L, D, m.E), D, jnp.float32),
           "w13": _normal(km[1], (L, m.E, 2 * m.I, D), D, dt),
           "w2": _normal(km[2], (L, m.E, D, m.I), m.I, dt)}
    if conf.get("qk_norm"):
        lay["q_norm"] = jnp.ones((L, dh), dt)
        lay["k_norm"] = jnp.ones((L, dh), dt)
    w["layers"] = lay
    return w


def make_weights(conf: dict, seed: int, devices) -> dict:
    """Weights on `devices`: stacked layer tensors split along the layer
    axis (over as many of the devices as divide the layer count), the rest
    on every device used."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    L = conf["num_hidden_layers"]
    n = max(d for d in range(1, len(devices) + 1) if L % d == 0)
    mesh = Mesh(np.array(devices[:n]), ("l",))
    shapes = jax.eval_shape(partial(_init, conf), jax.random.PRNGKey(0))
    sh = {k: NamedSharding(mesh, P()) for k in shapes if k != "layers"}
    sh["layers"] = {k: NamedSharding(mesh, P("l"))
                    for k in shapes["layers"]}
    return jax.jit(partial(_init, conf), out_shardings=sh)(
        jax.random.PRNGKey(seed))


def _q8(x, axis):
    """float8_e4m3 round trip with absmax scaling along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant):
    """a (..., n) @ b (n, m) in float32; under fp8 both inputs round trip
    through float8_e4m3 with scales along the contraction."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, dh/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@partial(jax.jit, static_argnames=("conf_key", "quant", "qblock"))
def _layer(x, lw, li, pos, seg, *, conf_key, quant, qblock=256):
    conf = dict(conf_key)
    m = Dims.of(conf)
    eps = conf["rms_norm_eps"]
    S = x.shape[0]
    g = {k: lax.dynamic_index_in_dim(v, li, keepdims=False)
         for k, v in lw.items() if k not in ("w13", "w2")}
    h = _rms(x, g["attn_norm"], eps)
    q = _mm(h, g["wq"], quant).reshape(S, m.H, m.dh)
    k = _mm(h, g["wk"], quant).reshape(S, m.K, m.dh)
    v = _mm(h, g["wv"], quant).reshape(S, m.K, m.dh)
    if "q_norm" in g:
        q = _rms(q, g["q_norm"], eps)
        k = _rms(k, g["k_norm"], eps)
    q = _rope(q, pos, conf["rope_theta"]) / math.sqrt(m.dh)
    k = _rope(k, pos, conf["rope_theta"])
    rep = m.H // m.K
    k = jnp.repeat(k, rep, axis=1)                          # (S, H, dh)
    v = jnp.repeat(v, rep, axis=1)
    if quant == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    idx = jnp.arange(S)

    def attn_block(i, out):
        qb = lax.dynamic_slice_in_dim(q, i * qblock, qblock, 0)
        iq = i * qblock + jnp.arange(qblock)
        sq = lax.dynamic_slice_in_dim(seg, i * qblock, qblock, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        ok = (sq[:, None] == seg[None, :]) & (idx[None, :] <= iq[:, None])
        if m.window:
            pq = lax.dynamic_slice_in_dim(pos, i * qblock, qblock, 0)
            ok &= pos[None, :] > pq[:, None] - m.window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)                 # padding rows
        if quant == "fp8":
            p = _q8(p, -1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        return lax.dynamic_update_slice_in_dim(out, o, i * qblock, 0)

    att = lax.fori_loop(0, S // qblock, attn_block,
                        jnp.zeros((S, m.H, m.dh), jnp.float32))
    x = x + _mm(att.reshape(S, m.H * m.dh), g["wo"], quant)
    h = _rms(x, g["mlp_norm"], eps)
    logits = jnp.matmul(h, g["router"].astype(jnp.float32), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top, eids = lax.top_k(probs, m.k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros((S, m.E), jnp.float32).at[
        jnp.arange(S)[:, None], eids].add(top)

    def expert(e, acc):
        w13 = lw["w13"][li, e]                              # (2I, D)
        w2 = lw["w2"][li, e]                                # (D, I)
        hu = _mm(h, w13.T, quant)
        a = jax.nn.silu(hu[:, :m.I]) * hu[:, m.I:]
        ge = lax.dynamic_slice_in_dim(gates, e, 1, axis=1)
        return acc + ge * _mm(a, w2.T, quant)

    return x + lax.fori_loop(0, m.E, expert, jnp.zeros_like(x))


@partial(jax.jit, static_argnames=("conf_key", "quant", "vblock"))
def _head(x, w, want, *, conf_key, quant, vblock=16384):
    """Per row: the best logit, the logit of token `want`, and the token
    ranked first, computed a block of the vocabulary at a time."""
    conf = dict(conf_key)
    x = _rms(x, w["final_norm"], conf["rms_norm_eps"])
    head = w["embed"] if conf["tie_word_embeddings"] else w["lm_head"]
    n = x.shape[0]
    best = jnp.full((n,), -jnp.inf, jnp.float32)
    arg = jnp.zeros((n,), jnp.int32)
    picked = jnp.zeros((n,), jnp.float32)
    for i in range(0, head.shape[0], vblock):
        lg = _mm(x, head[i:i + vblock].T, quant)
        bi, ai = lg.max(axis=-1), lg.argmax(axis=-1).astype(jnp.int32) + i
        arg = jnp.where(bi > best, ai, arg)
        best = jnp.maximum(best, bi)
        inb = (want >= i) & (want < i + lg.shape[1])
        got = jnp.take_along_axis(
            lg, jnp.clip(want - i, 0, lg.shape[1] - 1)[:, None], axis=1)[:, 0]
        picked = jnp.where(inb, got, picked)
    return best, picked, arg


def _key(conf: dict) -> tuple:
    """The numbers the reference computes with: the file's, with the
    program's stated departures from the source (`departures`) applied."""
    conf = {**conf, **conf.get("departures", {})}
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_local_experts",
            "num_experts", "num_experts_per_tok", "intermediate_size",
            "moe_intermediate_size", "vocab_size", "sliding_window",
            "rope_theta", "rms_norm_eps", "torch_dtype",
            "tie_word_embeddings", "qk_norm", "embed_scale_sqrt_hidden")
    return tuple(sorted((k, conf.get(k)) for k in keep))


def _shard_of(arr, li: int):
    """(local array, local layer index) of the device holding layer li."""
    for s in arr.addressable_shards:
        sl = s.index[0]
        lo = sl.start or 0
        hi = sl.stop if sl.stop is not None else arr.shape[0]
        if lo <= li < hi:
            return s.data, li - lo, s.device
    raise IndexError(li)


def hidden(conf: dict, w: dict, tokens, seg, out_idx,
           quant: str | None = None):
    """Final hidden states (before the last norm) at positions `out_idx`
    of one packed sequence: tokens (S,), segment ids (S,) (-1 = padding;
    each segment starts at position 0)."""
    key = _key(conf)
    tokens = np.asarray(tokens, np.int32)
    seg = np.asarray(seg, np.int32)
    pos = np.zeros_like(seg)
    for s in np.unique(seg):
        at = np.nonzero(seg == s)[0]
        pos[at] = np.arange(len(at))
    dev0 = _shard_of(w["layers"]["w13"], 0)[2]
    emb = w["embed"].addressable_shards[0].data
    x = jnp.take(jax.device_put(emb, dev0), jax.device_put(tokens, dev0),
                 axis=0).astype(jnp.float32)
    if conf.get("embed_scale_sqrt_hidden"):
        x = x * jnp.sqrt(jnp.float32(conf["hidden_size"]))
    for li in range(conf["num_hidden_layers"]):
        parts = {k: _shard_of(v, li) for k, v in w["layers"].items()}
        dev = parts["w13"][2]
        lw = {k: p[0] for k, p in parts.items()}
        x = _layer(jax.device_put(x, dev), lw, parts["w13"][1],
                   jax.device_put(pos, dev), jax.device_put(seg, dev),
                   conf_key=key, quant=quant)
    return x[jnp.asarray(out_idx)]


def head(conf: dict, w: dict, xo, want, quant: str | None = None):
    """(best logit, logit of `want`, token ranked first) per row of the
    hidden states `xo`, as numpy arrays."""
    dev = list(xo.devices())[0]
    top = {k: jax.device_put(w[k].addressable_shards[0].data, dev)
           for k in ("embed", "lm_head", "final_norm") if k in w}
    out = _head(xo, top, jax.device_put(np.asarray(want, np.int32), dev),
                conf_key=_key(conf), quant=quant)
    return tuple(np.asarray(a) for a in out)
