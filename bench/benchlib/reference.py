"""Building blocks of the plain references, independent of the program.

Each architecture's module under `bench/arch/` writes its reference from
these: the weight recipe's normal draws and their placement on the chips,
float32 matmuls at `highest` precision (or, under `quant="fp8"`, with
float8_e4m3 inputs: the control, one precision step below the
configuration's), RMSNorm, RoPE, causal attention over packed sequences,
the decoder stack's layer loop and the LM head. Nothing here knows a block.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
F8_MAX = 448.0


def normal(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def place(init, conf: dict, seed: int, devices) -> dict:
    """`init(conf, key)` run in one jitted call on `devices`: the stacked
    layer tensors under "layers" split along the layer axis (over as many
    of the devices as divide the layer count), the rest on every device
    used."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    L = conf["num_hidden_layers"]
    n = max(d for d in range(1, len(devices) + 1) if L % d == 0)
    mesh = Mesh(np.array(devices[:n]), ("l",))
    shapes = jax.eval_shape(partial(init, conf), jax.random.PRNGKey(0))
    sh = {k: NamedSharding(mesh, P()) for k in shapes if k != "layers"}
    sh["layers"] = {k: NamedSharding(mesh, P("l"))
                    for k in shapes["layers"]}
    return jax.jit(partial(init, conf), out_shardings=sh)(
        jax.random.PRNGKey(seed))


def q8(x, axis):
    """float8_e4m3 round trip with absmax scaling along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, quant):
    """a (..., n) @ b (n, m) in float32; under fp8 both inputs round trip
    through float8_e4m3 with scales along the contraction."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = q8(a, -1), q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def rms(x, w, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, dh/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attend(q, k, v, pos, seg, window: int, quant, qblock: int):
    """Causal attention within each packed sequence, a block of queries at
    a time: q, k, v (S, H, dh), q already scaled; `window` > 0 keeps the
    last `window` positions. Padding rows (seg -1) give 0."""
    S, H, dh = q.shape
    if quant == "fp8":
        q, k, v = q8(q, -1), q8(k, -1), q8(v, 0)
    idx = jnp.arange(S)

    def block(i, out):
        qb = lax.dynamic_slice_in_dim(q, i * qblock, qblock, 0)
        iq = i * qblock + jnp.arange(qblock)
        sq = lax.dynamic_slice_in_dim(seg, i * qblock, qblock, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        ok = (sq[:, None] == seg[None, :]) & (idx[None, :] <= iq[:, None])
        if window:
            pq = lax.dynamic_slice_in_dim(pos, i * qblock, qblock, 0)
            ok &= pos[None, :] > pq[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)                 # padding rows
        if quant == "fp8":
            p = q8(p, -1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        return lax.dynamic_update_slice_in_dim(out, o, i * qblock, 0)

    return lax.fori_loop(0, S // qblock, block,
                         jnp.zeros((S, H, dh), jnp.float32))


def shard_of(arr, li: int):
    """(local array, local layer index, device) of the shard that holds
    layer li of a layer-stacked array."""
    for s in arr.addressable_shards:
        sl = s.index[0]
        lo = sl.start or 0
        hi = sl.stop if sl.stop is not None else arr.shape[0]
        if lo <= li < hi:
            return s.data, li - lo, s.device
    raise IndexError(li)


def stack(conf: dict, w: dict, tokens, seg, out_idx, layer):
    """Final hidden states (before the last norm) at positions `out_idx`
    of one packed sequence: tokens (S,), segment ids (S,) (-1 = padding;
    each segment starts at position 0). Embeds (times sqrt(hidden_size)
    where the file sets `embed_scale_sqrt_hidden`), then runs
    `layer(x, lw, li, pos, seg)` on the device that holds each layer,
    with `lw` that device's shard of `w["layers"]`."""
    tokens = np.asarray(tokens, np.int32)
    seg = np.asarray(seg, np.int32)
    pos = np.zeros_like(seg)
    for s in np.unique(seg):
        at = np.nonzero(seg == s)[0]
        pos[at] = np.arange(len(at))
    dev0 = shard_of(next(iter(w["layers"].values())), 0)[2]
    emb = w["embed"].addressable_shards[0].data
    x = jnp.take(jax.device_put(emb, dev0), jax.device_put(tokens, dev0),
                 axis=0).astype(jnp.float32)
    if conf.get("embed_scale_sqrt_hidden"):
        x = x * jnp.sqrt(jnp.float32(conf["hidden_size"]))
    for li in range(conf["num_hidden_layers"]):
        parts = {k: shard_of(v, li) for k, v in w["layers"].items()}
        _, at, dev = next(iter(parts.values()))
        lw = {k: p[0] for k, p in parts.items()}
        x = layer(jax.device_put(x, dev), lw, at,
                  jax.device_put(pos, dev), jax.device_put(seg, dev))
    return x[jnp.asarray(out_idx)]


@partial(jax.jit, static_argnames=("eps", "quant", "vblock"))
def _head(x, norm, table, want, *, eps, quant, vblock=16384):
    x = rms(x, norm, eps)
    n = x.shape[0]
    best = jnp.full((n,), -jnp.inf, jnp.float32)
    arg = jnp.zeros((n,), jnp.int32)
    picked = jnp.zeros((n,), jnp.float32)
    for i in range(0, table.shape[0], vblock):
        lg = mm(x, table[i:i + vblock].T, quant)
        bi, ai = lg.max(axis=-1), lg.argmax(axis=-1).astype(jnp.int32) + i
        arg = jnp.where(bi > best, ai, arg)
        best = jnp.maximum(best, bi)
        inb = (want >= i) & (want < i + lg.shape[1])
        got = jnp.take_along_axis(
            lg, jnp.clip(want - i, 0, lg.shape[1] - 1)[:, None], axis=1)[:, 0]
        picked = jnp.where(inb, got, picked)
    return best, picked, arg


def lm_head(xo, norm, table, want, eps: float, quant=None):
    """(best logit, logit of `want`, token ranked first) per row of the
    hidden states `xo`, as numpy arrays: the final RMSNorm (weight `norm`)
    and the output `table` (V, D), a block of the vocabulary at a time."""
    dev = list(xo.devices())[0]
    norm, table = (jax.device_put(a.addressable_shards[0].data, dev)
                   for a in (norm, table))
    out = _head(xo, norm, table,
                jax.device_put(np.asarray(want, np.int32), dev),
                eps=eps, quant=quant)
    return tuple(np.asarray(a) for a in out)
