"""Live layout switches on a real SPMD mesh (subprocesses with 8 host
devices): monolithic and chunked switches mid-stream keep every
request's output byte-identical to a never-switched run, with either MoE
kernel backend.
"""
import pytest

from tests.helpers import COMMON, run_multidevice

pytestmark = pytest.mark.multidevice


def test_live_switch_preserves_outputs():
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def make_reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200,
            int(rng.integers(3, 10)))), max_new_tokens=int(rng.integers(4, 12)),
            arrival_s=0.0) for i in range(6)]
def run(switch_at=None, start=TP):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, ladder=(4, 8), prefill_chunk=8,
        temperature=0.0, policy=pol, seed=0))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP if eng.active == TP else TP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}
base = run(None, TP)
assert run(None, EP) == base, "static EP != static TP"
for at in (2, 5, 9):
    assert run(at, TP) == base, f"TP->EP@{at}"
    assert run(at, EP) == base, f"EP->TP@{at}"
print("OK")
""", timeout=1200)


def test_chunked_switch_preserves_outputs_and_shrinks_pause():
    """Overlapped layer-chunked switch (EngineConfig.chunk_layers > 0):
    outputs must match the static baseline exactly, pause_s must be
    recorded strictly below total_s once the movers are warm."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def make_reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200,
            int(rng.integers(3, 10)))), max_new_tokens=int(rng.integers(4, 12)),
            arrival_s=0.0) for i in range(6)]
def run(switch_at=None, start=TP, chunk=0):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, ladder=(4, 8), prefill_chunk=8,
        temperature=0.0, policy=pol, seed=0, chunk_layers=chunk))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP if eng.active == TP else TP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}, eng
base, _ = run(None, TP)
for at in (2, 5, 9):
    for start in (TP, EP):
        out, eng = run(at, start, chunk=1)
        assert out == base, (at, start)
        r = eng.switch_records[-1]
        assert r.chunks == 2 and r.pause_s <= r.total_s, vars(r)
        assert eng.metrics.switch_events, "switch not recorded in metrics"
# warm movers inside one engine: pause strictly below total
pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
    start_layout=TP, ladder=(4, 8), prefill_chunk=8, temperature=0.0,
    policy=pol, seed=0, chunk_layers=1))
for r in make_reqs(): eng.submit(r)
for i in range(6): eng.step()
for target in (EP, TP, EP, TP):
    eng.execute_switch(target)
    eng.step()
warm = eng.switch_records[-2:]
assert all(r.pause_s < r.total_s for r in warm), \
    [(r.pause_s, r.total_s) for r in warm]
print("OK")
""", timeout=1200)


def test_moe_backend_parity_across_live_switch():
    """moe_backend="interpret" must reproduce the einsum
    decode path token-for-token on the real (2, 4) mesh, including across
    a live tp->ep chunked switch (DESIGN.md §14 acceptance)."""
    run_multidevice(COMMON + """
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
def make_reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200,
            int(rng.integers(3, 10)))), max_new_tokens=int(rng.integers(4, 12)),
            arrival_s=0.0) for i in range(6)]
def run(backend, switch_at=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=TP, ladder=(4, 8), prefill_chunk=8, temperature=0.0,
        policy=pol, seed=0, chunk_layers=1, moe_backend=backend))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(EP)
        eng.step(); i += 1
        assert i < 500
    return {r.rid: r.output for r in eng.finished}
for at in (None, 4):
    ref = run("ref", at)
    ker = run("interpret", at)
    assert ker == ref, f"kernel MoE diverged on mesh (switch_at={at})"
print("OK")
""", timeout=1200)
