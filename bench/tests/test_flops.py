"""The operation and byte counts of the `topk_moe` architecture: a hand
count at a tiny configuration, and the same counts whatever capacity the
grouped GEMM runs at."""
import json

import pytest

import run as bench_run
import tiny
from benchlib import flops

A = bench_run.load_arch("topk_moe")
M = A.Dims(L=2, D=64, H=4, K=2, dh=16, E=8, k=2, I=32, V=512, window=0,
           wbytes=2)
# a decode row at position 9, and a 4-token chunk that completes a 4-token
# prompt; a 3-token chunk that does not complete its 10-token prompt
ROWS = (("decode", 9, 1, 5), ("prefill", 0, 4, 4), ("prefill", 2, 3, 10))


def test_keys_seen_by_hand():
    # decode at 9 sees 10 keys; chunk 0..3 sees 1+2+3+4; chunk 2..4 sees 3+4+5
    assert [flops.keys_seen(0, s, n) for _, s, n, _ in ROWS] == [10, 10, 12]
    assert flops.keys_seen(3, 2, 3) == 3 + 3 + 3
    assert flops.keys_read(3, 2, 3) == 5 - 0
    assert flops.keys_read(3, 9, 1) == 10 - 7


def test_moe_gemm_by_hand():
    T = 1 + 4 + 3
    f, b = A.moe_gemm(M, ROWS)
    # per layer: 8 tokens x 2 experts x (64x64 gate/up + 32x64 down) x 2
    assert f == 2 * (8 * 2 * (2 * 64 * 64 + 2 * 32 * 64))
    hit = 8 * (1 - (1 - 2 / 8) ** T)
    weights = hit * 3 * 64 * 32
    acts = T * 2 * (64 + 64 + 32 + 64)
    assert b == pytest.approx(2 * (weights + acts) * 2)
    assert flops.expected_experts(8, 2, 1) == pytest.approx(2)
    assert flops.expected_experts(8, 2, 10**6) == pytest.approx(8)
    assert A.costs["moe_grouped_matmul"] is A.moe_gemm


def test_paged_attention_by_hand():
    f, b = A.paged_attention(M, ROWS)
    assert f == 2 * 4 * 4 * 16 * (10 + 10 + 12)
    kv = 2 * 2 * 16 * (10 + 4 + 5)
    qo = 2 * 4 * 16 * 8
    assert b == 2 * (kv + qo) * 2


def test_forward_by_hand():
    proj = 2 * 64 * (64 + 2 * 32) + 2 * 64 * 64     # q, k, v; o
    router = 2 * 64 * 8
    attn = 4 * 4 * 16 * 32
    moe = 8 * 2 * (2 * 64 * 64 + 2 * 32 * 64)
    head = 2 * 64 * 512 * 2           # decode row + the completing chunk
    assert A.forward(M, ROWS) == 2 * (8 * (proj + router) + attn + moe) \
        + head


def test_dims_of_config_files():
    for name in ("mixtral-8x7b-l4", "qwen3-235b-a22b-l1"):
        conf = json.loads((tiny.BENCH / "configs" / f"{name}.json")
                          .read_text())
        m = A.dims(conf)
        assert (m.D, m.dh, m.wbytes) == (4096, 128, 2)
    q = A.dims(json.loads(
        (tiny.BENCH / "configs" / "qwen3-235b-a22b-l1.json").read_text()))
    assert (q.E, q.k, q.I, q.H, q.K, q.V) == (128, 8, 1536, 64, 4, 151936)


def _rows_served(monkeypatch, capacity):
    """Dispatch compositions of tiny chat traffic, served to the end, at
    one expert capacity."""
    from benchlib import serve, traffic
    from repro.serving.frontend import AsyncEngine
    real = A.model_config
    monkeypatch.setattr(A, "model_config",
                        lambda c: real(c).replace(capacity_factor=capacity))
    conf = tiny.conf("mixtral-8x7b-l4", torch_dtype="float32")
    eng = serve.build(A, conf, 3)
    log = serve.DispatchLog(eng.ex)
    fe = AsyncEngine(eng)
    for q in traffic.generate(tiny.mix("chat"), 1.0, 3, conf["vocab_size"]):
        fe.generate(q.prompt.tolist(), max_new_tokens=q.output,
                    forced_len=q.output, rid=q.rid)
    while eng.sched.has_work():
        eng.step()
    return conf, log.take()


def test_counts_ignore_expert_capacity(monkeypatch):
    conf, a = _rows_served(monkeypatch, 2.0)     # dropless: E/k = 4
    _, b = _rows_served(monkeypatch, 8.0)
    m = A.dims(conf)
    assert [rows for *_, rows in a] == [rows for *_, rows in b]
    assert sum(A.forward(m, r) for *_, r in a) == \
        sum(A.forward(m, r) for *_, r in b)
    assert sum(A.moe_gemm(m, r)[0] for *_, r in a) > 0
