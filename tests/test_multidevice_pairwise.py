"""Every ordered pair of registered layouts on a real SPMD mesh
(subprocesses with 8 host devices): live switches keep outputs
byte-identical, and the two expert reshard paths agree.
"""
import pytest

from tests.helpers import COMMON, run_multidevice

pytestmark = pytest.mark.multidevice


LAYOUT_NAMES = ("tp", "ep", "tpep")
ORDERED_PAIRS = [(a, b) for a in LAYOUT_NAMES for b in LAYOUT_NAMES
                 if a != b]


@pytest.mark.parametrize("src,dst", ORDERED_PAIRS,
                         ids=[f"{a}_to_{b}" for a, b in ORDERED_PAIRS])
def test_pairwise_switch_preserves_outputs(src, dst):
    """N-layout acceptance: for EVERY ordered pair of registered layouts
    (including the hybrid tpep), serving statically on the source and
    live-switching source -> destination mid-flight must both be
    byte-identical to a never-switched baseline."""
    run_multidevice(COMMON + f"""
src, dst = {src!r}, {dst!r}
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
def run(start, switch_at=None, target=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, layouts=("tp", "ep", "tpep"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0))
    for r in make_reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if switch_at is not None and i == switch_at:
            eng.execute_switch(target)
        eng.step(); i += 1
        assert i < 500
    return {{r.rid: r.output for r in eng.finished}}
base = run("tp")                          # never-switched baseline
assert run(src) == base, f"static {{src}} != baseline"
assert run(src, 4, dst) == base, f"{{src}}->{{dst}} diverged"
print("OK")
""", timeout=1200)


def test_reshard_paths_agree():
    run_multidevice(COMMON + """
from repro.core.switch import (make_reshard_experts,
                               make_reshard_experts_direct)
from repro.models.moe import make_expert_layout, pack_w13, pack_experts
E, I, D, L, G = 8, 32, 32, 2, 4
key = jr.PRNGKey(0)
w13 = jr.normal(key, (L, E, 2*I, D), jnp.float32)
w2 = jr.normal(jr.fold_in(key, 1), (L, E, D, I), jnp.float32)
lay_tp = make_expert_layout(E, G, "tp"); lay_ep = make_expert_layout(E, G, "ep")
pk13 = lambda w, lay: jax.vmap(lambda x: pack_w13(x, lay))(w)
pk2 = lambda w, lay: jax.vmap(lambda x: pack_experts(x, lay, 2))(w)
w13_ep, w2_ep = pk13(w13, lay_ep), pk2(w2, lay_ep)
w13_tp, w2_tp = pk13(w13, lay_tp), pk2(w2, lay_tp)
moe = {"w13": w13_ep, "w2": w2_ep}
sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), moe)
xla = make_reshard_experts(cfg, mesh, "ep", "tp", donate=False)(sds)(moe)
d13, d2 = make_reshard_experts_direct(cfg, mesh, "ep_to_tp")(w13_ep, w2_ep)
assert np.array_equal(np.asarray(xla["w13"]), np.asarray(w13_tp))
assert np.array_equal(np.asarray(d13), np.asarray(w13_tp))
assert np.array_equal(np.asarray(d2), np.asarray(w2_tp))
b13, b2 = make_reshard_experts_direct(cfg, mesh, "tp_to_ep")(d13, d2)
assert np.array_equal(np.asarray(b13), np.asarray(w13_ep))
print("OK")
""")
