"""The grouped expert GEMM's weight-pass counter, end to end on a tiny MoE
engine: `ServeMetrics.moe_weight_passes` is what the kernel's pass rule
gives for the routing of every dispatched step, never more than the dense
grid's `moe_weight_passes_dense`, and equal to it when every expert's
routed rows fill its row tiles."""
import jax
import numpy as np
import pytest

from repro.core.policy import PolicyConfig
from repro.kernels.moe_gemm.kernel import tiles
from repro.launch.mesh import make_mesh
from repro.models.moe import capacity, route
from repro.serving import steps
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request

DENSE_ROWS = 128            # the row tile of the grid before the counts


def _serve_recording_routes(cfg, monkeypatch, token_budget=0):
    """Serve five requests; return the engine and, per MoE call, the
    step's routed expert ids and the call's expert capacity C."""
    seen = []
    orig = steps.moe_decode_tp

    def moe_tp(cfg, p, x, axis, **kw):
        _, eids, _ = route(cfg, p["router"], x)
        C = capacity(x.shape[0], cfg)
        jax.debug.callback(lambda e: seen.append((np.asarray(e), C)), eids)
        return orig(cfg, p, x, axis, **kw)

    monkeypatch.setattr(steps, "moe_decode_tp", moe_tp)
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(
        cfg, make_mesh((1, 1), ("data", "model")),
        CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=16),
        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8), prefill_chunk=8,
                          temperature=0.0, policy=pol,
                          token_budget=token_budget))
    rng = np.random.default_rng(3)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 5 + 4 * i)),
                           max_new_tokens=5, forced_len=5, arrival_s=0.0))
    for _ in range(200):
        if not (eng.pending or eng.waiting or eng.prefilling or eng.running):
            break
        eng.step()
    jax.effects_barrier()
    return eng, seen


def _host_passes(cfg, seen):
    """(kernel, dense) passes over both expert GEMMs of every recorded
    call: sum_e ceil(rows routed to e / row block) and E x ceil(C / 128)."""
    E, D, I = cfg.num_experts, cfg.d_model, cfg.d_expert
    item = np.dtype(cfg.compute_dtype).itemsize
    made = dense = 0
    for eids, C in seen:
        counts = np.minimum(np.bincount(eids.ravel(), minlength=E), C)
        for Dc, W in ((D, 2 * I), (I, D)):
            bc = tiles(C, Dc, W, item)[0]
            made += int(np.sum(-(-counts // bc)))
            dense += E * -(-C // DENSE_ROWS)
    return made, dense


@pytest.mark.parametrize("token_budget", [0, 32])
def test_weight_passes_follow_routing(tiny_moe, monkeypatch, token_budget):
    eng, seen = _serve_recording_routes(tiny_moe, monkeypatch, token_budget)
    m = eng.metrics
    assert seen and m.dispatches > 0
    assert (m.moe_weight_passes, m.moe_weight_passes_dense) == \
        _host_passes(tiny_moe, seen)
    # top-2 of 8 experts over a handful of rows leaves experts unread
    assert 0 < m.moe_weight_passes < m.moe_weight_passes_dense


def test_weight_passes_equal_dense_when_tiles_are_full(tiny_moe,
                                                       monkeypatch):
    """Every token routed to both of two experts: each expert's count is
    the step's C, which fills the one row tile of either grid."""
    cfg = tiny_moe.replace(num_experts=2, top_k=2, capacity_factor=1.0)
    eng, seen = _serve_recording_routes(cfg, monkeypatch)
    m = eng.metrics
    assert all(C <= DENSE_ROWS for _, C in seen)
    assert m.moe_weight_passes == m.moe_weight_passes_dense > 0
    assert (m.moe_weight_passes, m.moe_weight_passes_dense) == \
        _host_passes(cfg, seen)
