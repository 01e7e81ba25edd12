"""Elastic world-size multidevice tests (DESIGN.md §13).

Live cross-world switches on a real SPMD mesh: shrink 4->2 devices and
grow back, via the chunked host-bounce migration path, with the full
generated text of every request byte-identical to a never-resized
baseline — plus rank failures injected BEFORE / DURING (each chunk
boundary aborts + rolls back) / AFTER the shrink.
"""
import pytest

from tests.helpers import COMMON, run_multidevice

pytestmark = pytest.mark.multidevice


def test_elastic_resize_preserves_outputs():
    """Shrink tp -> tp@2 and grow back at several engine steps, shrink
    out of ep (layout AND world change in one switch), and grow under
    load from a tp@2 start: every run's outputs must match the static
    full-world baseline exactly, with zero dropped requests and clean
    page accounting."""
    run_multidevice(COMMON + """
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
def run(script=(), start="tp"):
    pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout=start, layouts=("tp", "ep", "tp@2"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        chunk_layers=1))
    for r in make_reqs(): eng.submit(r)
    sched = dict(script)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        if i in sched:
            eng.execute_switch(sched[i])
        eng.step(); i += 1
        assert i < 800
    return {r.rid: r.output for r in eng.finished}, eng
base, _ = run()
for at in (2, 5, 9):
    out, eng = run(((at, "tp@2"), (at + 3, "tp")))
    assert out == base, ("resize", at)
    s = eng.metrics.summary()
    assert s["switches"] == 2 and s["cross_world_switches"] == 2
    assert s["switch_aborts"] == 0
    assert len(eng.finished) == 6, "request dropped"
    assert str(eng.active) == "tp" and eng.sched.G == 4
    # the grow went through the chunked path (2 layer chunks)
    assert eng.switch_records[-1].chunks == 2
    for al in eng.alloc:
        al.check()
# world change COMPOSED with a layout change: ep(4) -> tp@2 -> ep(4)
out, eng = run(((2, "ep"), (6, "tp@2"), (10, "ep")))
assert out == base, "ep->tp@2->ep"
assert eng.metrics.summary()["cross_world_switches"] == 2
assert str(eng.active) == "ep" and eng.sched.G == 4
# start SMALL and grow under load: the autoscaler's burst response
out, eng = run(((4, "tp"),), start="tp@2")
assert out == base, "grow from tp@2 start"
ls = eng.layouts_summary()
assert ls["world"] == 4 and ls["launch_world"] == 4
assert {l["name"]: l["world"] for l in ls["layouts"]} == \
    {"tp": 4, "ep": 4, "tp@2": 2}
print("OK")
""", timeout=1200)


RESIZE_PHASES = ("before", "chunk0", "chunk1", "after")


@pytest.mark.parametrize("phase", RESIZE_PHASES)
def test_rank_failure_around_elastic_shrink(phase):
    """Fault interplay (DESIGN.md §12 + §13): a rank failure BEFORE the
    cross-world shrink (recovery, then the shrink commits), AT each
    chunk boundary DURING it (the staged destination world is dropped,
    the source layout stays live — abort/rollback), and AFTER it
    commits (the failure hits the 2-device world, recovery re-prefills
    there, then the engine grows back) — in every phase the generated
    text of every request is byte-identical to a never-faulted,
    never-resized baseline."""
    run_multidevice(COMMON + f"""
phase = {phase!r}
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.faults import Fault, FaultPlan
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cc = CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16)
P = 6                                    # original prompt length
def reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=list(rng.integers(5, 200, P)),
                    max_new_tokens=10, arrival_s=0.0) for i in range(6)]
PLANS = {{
    # failure on the full world first; the later shrink still commits
    "before": (Fault("rank_fail", at_step=3, data_group=0, rank=1),
               Fault("switch", at_step=8, target="tp@2")),
    # failure at a chunk boundary of the in-flight shrink: the staged
    # tp@2 buffers/pages are dropped, tp stays live (source never moved)
    "chunk0": (Fault("switch", at_step=4, target="tp@2"),
               Fault("rank_fail", switch_chunk=0, switch_index=0,
                     data_group=0, rank=1)),
    "chunk1": (Fault("switch", at_step=4, target="tp@2"),
               Fault("rank_fail", switch_chunk=1, switch_index=0,
                     data_group=0, rank=1)),
    # failure INSIDE the shrunken world, then grow back out of it
    "after": (Fault("switch", at_step=4, target="tp@2"),
              Fault("rank_fail", at_step=12, data_group=0, rank=1),
              Fault("switch", at_step=20, target="tp")),
}}
def run(plan=None):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    eng = MoebiusEngine(cfg, mesh, cc, ecfg=EngineConfig(
        start_layout="tp", layouts=("tp", "ep", "tp@2"), ladder=(4, 8),
        prefill_chunk=8, temperature=0.0, policy=pol, seed=0,
        chunk_layers=1, faults=None if plan is None else FaultPlan(plan)))
    for r in reqs(): eng.submit(r)
    i = 0
    while eng.pending or eng.waiting or eng.prefilling or eng.running:
        eng.step(); i += 1
        assert i < 800
    return eng, {{r.rid: list(r.prompt[P:]) + list(r.output)
                  for r in eng.finished}}
_, base = run(None)                      # never-faulted, never-resized
eng, out = run(PLANS[phase])
assert out == base, (phase, out, base)
s = eng.metrics.summary()
assert s["rank_failures"] == 1 and eng._faults.done
assert len(eng.finished) == 6, "request dropped"
if phase in ("chunk0", "chunk1"):
    # abort/rollback: the source world never moved
    assert str(eng.active) == "tp" and eng.sched.G == 4
    assert s["switches"] == 0 and s["cross_world_switches"] == 0
    assert s["switch_aborts"] == 1 and eng.coord.backoff_mult > 1.0
elif phase == "before":
    assert str(eng.active) == "tp@2" and eng.sched.G == 2
    assert s["switches"] == 1 and s["cross_world_switches"] == 1
    assert s["switch_aborts"] == 0
else:                                    # after: shrink, fail, grow
    assert str(eng.active) == "tp" and eng.sched.G == 4
    assert s["switches"] == 2 and s["cross_world_switches"] == 2
    assert s["switch_aborts"] == 0
assert not eng.sched.dead_pools
for al in eng.alloc:
    al.check()
print("OK")
""", timeout=1200)
