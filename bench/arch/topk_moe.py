"""Architecture `topk_moe`: a decoder whose every layer is GQA attention
(full, or one sliding window for all layers; optional per-head q/k RMSNorm)
and a routed expert layer (softmax router, top-k gates renormalised, SwiGLU
experts), with RMSNorm before each and an untied or tied LM head. Mixtral
and Qwen3-MoE are of this kind.

An architecture module is the one place that knows its block. The harness
finds it as `bench/arch/<arch>.py`, where `<arch>` is the configuration
file's `"arch"`, and reads these names:

- `model_config(conf)`: the program's `ModelConfig` for the file.
- `dims(conf)`: the sizes the work counts need.
- `forward(dims, rows)`: the useful forward FLOPs of one step.
- `costs`: kernel trace name -> `f(dims, rows) -> (flops, bytes)`, the
  useful work of that kernel in one step, read by the kernel's roofline.
- `make_weights(conf, seed, devices)`: the plain reference's weights,
  drawn from the seed by the program's recipe.
- `hidden(conf, w, tokens, seg, out_idx, quant)`: the reference's final
  hidden states at `out_idx` of one packed sequence.
- `head(conf, w, xo, want, quant)`: (best logit, logit of `want`, token
  ranked first) per row of those hidden states.

A step is a list of rows (kind, start, n, prompt_len), as
`benchlib/flops.py` says. Counts use the model's shapes and each row's
tokens, never a kernel's grid, padding or capacity.

The reference runs in float32 at `highest` matmul precision, layer by layer
and a block of queries at a time; experts are computed densely, every
expert for every token, weighted by the renormalised top-k gates. It uses
the numbers of the file with the program's stated departures from the
source (`departures`) applied.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchlib import flops
from benchlib import reference as ref


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file (Hugging Face
    key names). Dropless expert capacity: capacity_factor = E / k."""
    from repro.models.common import ModelConfig
    dtype = ref.DTYPES[conf["torch_dtype"]]
    E = conf.get("num_local_experts") or conf.get("num_experts") or 0
    k = conf["num_experts_per_tok"]
    if not conf.get("norm_topk_prob", True):
        raise ValueError("the program renormalises the top-k gates; a "
                         "configuration without norm_topk_prob cannot run")
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or 0,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        num_experts=E, top_k=k,
        d_expert=conf.get("moe_intermediate_size")
        or conf["intermediate_size"],
        capacity_factor=E / k, qk_norm=bool(conf.get("qk_norm")),
        sliding_window=conf.get("sliding_window") or 0,
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        param_dtype=dtype, compute_dtype=dtype)


# --- work counts -----------------------------------------------------------

@dataclass(frozen=True)
class Dims:
    L: int          # layers
    D: int          # hidden
    H: int          # query heads
    K: int          # KV heads
    dh: int         # head size
    E: int          # routed experts
    k: int          # experts per token
    I: int          # expert width
    V: int          # vocabulary
    window: int     # 0 = full attention
    wbytes: int     # bytes per weight / activation element


def dims(conf: dict) -> Dims:
    H = conf["num_attention_heads"]
    return Dims(
        L=conf["num_hidden_layers"], D=conf["hidden_size"], H=H,
        K=conf["num_key_value_heads"],
        dh=conf.get("head_dim") or conf["hidden_size"] // H,
        E=conf.get("num_local_experts") or conf.get("num_experts"),
        k=conf["num_experts_per_tok"],
        I=conf.get("moe_intermediate_size") or conf["intermediate_size"],
        V=conf["vocab_size"], window=conf.get("sliding_window") or 0,
        wbytes=flops.BYTES[conf["torch_dtype"]])


def moe_gemm(m: Dims, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of the expert GEMMs over all layers: each token
    through its k experts (gate/up D->2I, down I->D); bytes are the weights
    of the experts hit plus each routed row's activations in and out."""
    T = flops.tokens(rows)
    f = 6.0 * T * m.k * m.D * m.I
    w = flops.expected_experts(m.E, m.k, T) * 3 * m.D * m.I
    act = T * m.k * (m.D + 2 * m.I + m.I + m.D)
    return m.L * f, m.L * (w + act) * m.wbytes


def paged_attention(m: Dims, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of attention over the paged cache, all layers:
    QK^T and PV over the keys each token sees; bytes are the K and V each
    row reads, its queries and its outputs."""
    f = sum(4.0 * m.H * m.dh * flops.keys_seen(m.window, s, n)
            for _, s, n, _ in rows)
    kv = sum(2 * m.K * m.dh * flops.keys_read(m.window, s, n)
             for _, s, n, _ in rows)
    qo = 2 * m.H * m.dh * flops.tokens(rows)
    return m.L * f, m.L * (kv + qo) * m.wbytes


def forward(m: Dims, rows) -> float:
    """Useful forward FLOPs of one step: projections, attention over the
    real context, the router, the top-k experts, and the LM head on rows
    whose sample is used. Norms, RoPE and softmax are left out."""
    T = flops.tokens(rows)
    proj = 2.0 * m.D * (m.H * m.dh + 2 * m.K * m.dh) + 2.0 * m.H * m.dh * m.D
    router = 2.0 * m.D * m.E
    per_layer = T * (proj + router) + paged_attention(m, rows)[0] / m.L
    moe = moe_gemm(m, rows)[0]
    head_ = 2.0 * m.D * m.V * flops.sampled_rows(rows)
    return m.L * per_layer + moe + head_


costs = {"moe_grouped_matmul": moe_gemm, "paged_attention": paged_attention}


# --- the plain reference ---------------------------------------------------

def init(conf: dict, key) -> dict:
    """The program's recipe: every matrix normal with std 1/sqrt(fan_in),
    router in float32, norms at 1, keys split in the program's order."""
    m = dims(conf)
    dt = ref.DTYPES[conf["torch_dtype"]]
    L, D, H, K, dh = m.L, m.D, m.H, m.K, m.dh
    ks = list(jax.random.split(key, 8))
    w = {"embed": ref.normal(ks[0], (m.V, D), D, dt),
         "final_norm": jnp.ones((D,), dt)}
    if not conf["tie_word_embeddings"]:
        w["lm_head"] = ref.normal(ks[1], (m.V, D), D, dt)
    ka = list(jax.random.split(ks[2], 4))
    km = list(jax.random.split(ks[3], 5))
    lay = {"attn_norm": jnp.ones((L, D), dt), "mlp_norm": jnp.ones((L, D), dt),
           "wq": ref.normal(ka[0], (L, D, H * dh), D, dt),
           "wk": ref.normal(ka[1], (L, D, K * dh), D, dt),
           "wv": ref.normal(ka[2], (L, D, K * dh), D, dt),
           "wo": ref.normal(ka[3], (L, H * dh, D), H * dh, dt),
           "router": ref.normal(km[0], (L, D, m.E), D, jnp.float32),
           "w13": ref.normal(km[1], (L, m.E, 2 * m.I, D), D, dt),
           "w2": ref.normal(km[2], (L, m.E, D, m.I), m.I, dt)}
    if conf.get("qk_norm"):
        lay["q_norm"] = jnp.ones((L, dh), dt)
        lay["k_norm"] = jnp.ones((L, dh), dt)
    w["layers"] = lay
    return w


def make_weights(conf: dict, seed: int, devices) -> dict:
    return ref.place(init, conf, seed, devices)


def attention(x, g, pos, seg, conf: dict, m: Dims, quant, qblock: int):
    """The attention half of a layer, residual not added: RMSNorm, q/k/v
    projections, optional q/k norm, RoPE, GQA, output projection. `g`
    holds the layer's own weights."""
    eps = conf["rms_norm_eps"]
    S = x.shape[0]
    h = ref.rms(x, g["attn_norm"], eps)
    q = ref.mm(h, g["wq"], quant).reshape(S, m.H, m.dh)
    k = ref.mm(h, g["wk"], quant).reshape(S, m.K, m.dh)
    v = ref.mm(h, g["wv"], quant).reshape(S, m.K, m.dh)
    if "q_norm" in g:
        q = ref.rms(q, g["q_norm"], eps)
        k = ref.rms(k, g["k_norm"], eps)
    q = ref.rope(q, pos, conf["rope_theta"]) / math.sqrt(m.dh)
    k = ref.rope(k, pos, conf["rope_theta"])
    rep = m.H // m.K
    k = jnp.repeat(k, rep, axis=1)                          # (S, H, dh)
    v = jnp.repeat(v, rep, axis=1)
    att = ref.attend(q, k, v, pos, seg, m.window, quant, qblock)
    return ref.mm(att.reshape(S, m.H * m.dh), g["wo"], quant)


def routed(h, g, lw, li, m: Dims, quant):
    """The routed experts' output for the normed input h (S, D): softmax
    router, top-k gates renormalised, every expert computed densely and
    weighted by its gate."""
    S = h.shape[0]
    logits = jnp.matmul(h, g["router"].astype(jnp.float32),
                        precision=ref.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top, eids = lax.top_k(probs, m.k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros((S, m.E), jnp.float32).at[
        jnp.arange(S)[:, None], eids].add(top)

    def expert(e, acc):
        w13 = lw["w13"][li, e]                              # (2I, D)
        w2 = lw["w2"][li, e]                                # (D, I)
        hu = ref.mm(h, w13.T, quant)
        a = jax.nn.silu(hu[:, :m.I]) * hu[:, m.I:]
        ge = lax.dynamic_slice_in_dim(gates, e, 1, axis=1)
        return acc + ge * ref.mm(a, w2.T, quant)

    return lax.fori_loop(0, m.E, expert, jnp.zeros_like(h))


def own(lw, li):
    """Layer li's slice of every stacked weight but the experts', which
    the expert loop indexes itself."""
    return {k: lax.dynamic_index_in_dim(v, li, keepdims=False)
            for k, v in lw.items() if k not in ("w13", "w2")}


@partial(jax.jit, static_argnames=("conf_key", "quant", "qblock"))
def _layer(x, lw, li, pos, seg, *, conf_key, quant, qblock=256):
    conf = dict(conf_key)
    m = dims(conf)
    g = own(lw, li)
    x = x + attention(x, g, pos, seg, conf, m, quant, qblock)
    h = ref.rms(x, g["mlp_norm"], conf["rms_norm_eps"])
    return x + routed(h, g, lw, li, m, quant)


KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_local_experts",
        "num_experts", "num_experts_per_tok", "intermediate_size",
        "moe_intermediate_size", "vocab_size", "sliding_window",
        "rope_theta", "rms_norm_eps", "torch_dtype",
        "tie_word_embeddings", "qk_norm", "embed_scale_sqrt_hidden")


def key(conf: dict, keys=KEYS) -> tuple:
    """The numbers the reference computes with: the file's, with the
    program's stated departures from the source (`departures`) applied."""
    conf = {**conf, **conf.get("departures", {})}
    return tuple(sorted((k, conf.get(k)) for k in keys))


def hidden(conf: dict, w: dict, tokens, seg, out_idx, quant=None):
    return ref.stack(conf, w, tokens, seg, out_idx, partial(
        _layer, conf_key=key(conf), quant=quant))


def head(conf: dict, w: dict, xo, want, quant=None):
    table = w["embed"] if conf["tie_word_embeddings"] else w["lm_head"]
    return ref.lm_head(xo, w["final_norm"], table, want,
                       dict(key(conf))["rms_norm_eps"], quant)
