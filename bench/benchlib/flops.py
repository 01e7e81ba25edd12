"""Operations and bytes each step needs, from the model's shapes and the
step's composition alone: never from a kernel's grid, padding or capacity.
So they count the same useful work whatever implements the step.

A step is a list of rows (kind, start, n, prompt_len): a row feeds `n`
tokens whose KV positions are start .. start+n-1. A decode row has n == 1.
A row's sample is used when it is a decode row or the chunk that completes
its prompt (start + n == prompt_len); only those rows count the LM head.
"""
from __future__ import annotations

from dataclasses import dataclass

BYTES = {"bfloat16": 2, "float32": 4}


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

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        H = conf["num_attention_heads"]
        return cls(
            L=conf["num_hidden_layers"], D=conf["hidden_size"], H=H,
            K=conf["num_key_value_heads"],
            dh=conf.get("head_dim") or conf["hidden_size"] // H,
            E=conf.get("num_local_experts") or conf.get("num_experts"),
            k=conf["num_experts_per_tok"],
            I=conf.get("moe_intermediate_size") or conf["intermediate_size"],
            V=conf["vocab_size"], window=conf.get("sliding_window") or 0,
            wbytes=BYTES[conf["torch_dtype"]])


def keys_seen(m: Dims, start: int, n: int) -> int:
    """Sum over the row's tokens of the keys each attends to (causal,
    within the sliding window if any)."""
    if not m.window:
        # tokens at positions p = start .. start+n-1 see p + 1 keys
        return n * start + n * (n + 1) // 2
    return sum(min(p + 1, m.window) for p in range(start, start + n))


def keys_read(m: Dims, start: int, n: int) -> int:
    """Distinct KV positions the row reads."""
    end = start + n
    first = max(0, start - m.window + 1) if m.window else 0
    return end - first


def tokens(rows) -> int:
    return sum(n for _, _, n, _ in rows)


def sampled_rows(rows) -> int:
    return sum(1 for kind, s, n, plen in rows
               if kind == "decode" or s + n == plen)


def expected_experts(m: Dims, T: int) -> float:
    """Distinct experts hit by T tokens each routed to k of E, expected
    under uniform routing (random weights route near uniformly). Never more
    than E, and exactly k for one token."""
    if T <= 0:
        return 0.0
    return m.E * (1.0 - (1.0 - m.k / m.E) ** T)


def moe_gemm(m: Dims, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of the expert GEMMs over all layers: each token
    through its k experts (gate/up D->2I, down I->D); bytes are the weights
    of the experts hit plus each routed row's activations in and out."""
    T = tokens(rows)
    flops = 6.0 * T * m.k * m.D * m.I
    w = expected_experts(m, T) * 3 * m.D * m.I
    act = T * m.k * (m.D + 2 * m.I + m.I + m.D)
    return m.L * flops, m.L * (w + act) * m.wbytes


def paged_attention(m: Dims, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of attention over the paged cache, all layers:
    QK^T and PV over the keys each token sees; bytes are the K and V each
    row reads, its queries and its outputs."""
    flops = sum(4.0 * m.H * m.dh * keys_seen(m, s, n) for _, s, n, _ in rows)
    kv = sum(2 * m.K * m.dh * keys_read(m, s, n) for _, s, n, _ in rows)
    qo = 2 * m.H * m.dh * tokens(rows)
    return m.L * flops, m.L * (kv + qo) * m.wbytes


def forward(m: Dims, rows) -> float:
    """Useful forward FLOPs of one step: projections, attention over the
    real context, the router, the top-k experts, and the LM head on rows
    whose sample is used. Norms, RoPE and softmax are left out."""
    T = tokens(rows)
    proj = 2.0 * m.D * (m.H * m.dh + 2 * m.K * m.dh) + 2.0 * m.H * m.dh * m.D
    router = 2.0 * m.D * m.E
    per_layer = T * (proj + router) + paged_attention(m, rows)[0] / m.L
    moe = moe_gemm(m, rows)[0]
    head = 2.0 * m.D * m.V * sampled_rows(rows)
    return m.L * per_layer + moe + head
