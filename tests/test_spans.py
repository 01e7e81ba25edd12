"""Host spans of the engine step (repro/tracing.py) in a profiler trace
recorded on the CPU: which phases each step opens, in what order, with
which arguments; the request stamps; the fused-decode and switch spans.
"""
import glob

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.core.policy import PolicyConfig
from repro.launch.mesh import make_mesh
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
from repro.tracing import PREFIX, span
from tests.helpers import run_multidevice

PHASES = ["sched.plan", "exec.stage", "exec.launch", "exec.fetch",
          "sched.commit"]
MAX_SPANS_PER_STEP = 16


def program_spans(logdir) -> list:
    """[(name without the prefix, start_ns, end_ns, args)], parents first."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name[len(PREFIX):], s,
                                s + int(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def by_step(spans) -> list:
    """[(step span, [spans nested in it])]."""
    steps = [e for e in spans if e[0] == "step"]
    return [(st, [e for e in spans if e is not st and st[1] <= e[1]
                  and e[2] <= st[2]]) for st in steps]


def _engine(cfg, **kw):
    pol = PolicyConfig(t_high=10**9, t_low=-1, cooldown_s=10**9)
    return MoebiusEngine(
        cfg, make_mesh((1, 1), ("data", "model")),
        CacheConfig(page_size=4, pages_ep=64, max_pages_per_req=16),
        ecfg=EngineConfig(start_layout="tp", ladder=(4, 8), prefill_chunk=8,
                          temperature=0.0, policy=pol, **kw))


def _traced(eng, tmp_path, n_reqs=5):
    """Serve `n_reqs` prompts to completion under the profiler; returns the
    program spans and [(B, Sq, decode rows, prefill tokens)] per step."""
    rng = np.random.default_rng(0)
    for i in range(n_reqs):
        eng.submit(Request(rid=i, prompt=list(rng.integers(
            5, 200, int(rng.integers(3, 20)))), max_new_tokens=6))
    per_step, now = [], []
    orig = eng.ex.run_mixed

    def run_mixed(plan, step_i):
        now.append((plan.B, plan.Sq,
                    sum(1 for r in plan.rows if r.kind == "decode"),
                    sum(r.n_tokens for r in plan.rows
                        if r.kind != "decode")))
        return orig(plan, step_i)
    eng.ex.run_mixed = run_mixed
    eng.warmup()
    jax.profiler.start_trace(str(tmp_path))
    while eng.sched.has_work():
        eng.step()
        per_step.append(list(now))
        now.clear()
    jax.profiler.stop_trace()
    return program_spans(tmp_path), per_step


def test_mixed_step_phases_order_and_arguments(tiny_moe, tmp_path):
    """A step plans, stages and launches its dispatch, then fetches and
    commits the one launched before it; the last step launches nothing
    and lands the last dispatch inside `exec.drain`."""
    eng = _engine(tiny_moe)
    spans, logged = _traced(eng, tmp_path)
    steps = by_step(spans)
    assert len(steps) == len(logged) > 5
    for i, ((st, kids), dispatches) in enumerate(zip(steps, logged)):
        assert len(kids) + 1 <= MAX_SPANS_PER_STEP
        names = [k[0] for k in kids]
        assert names[:2] == ["sched.admit", "policy"]
        assert names[-1] == "account"
        phases = [n for n in names if n in PHASES]
        if not dispatches:
            assert i == len(steps) - 1
            assert phases == ["sched.plan", "exec.fetch", "sched.commit"]
            assert "exec.drain" in names and st[3]["B"] == 0
            continue
        (B, Sq, dec, pre), = dispatches
        assert phases == (PHASES if i else PHASES[:3])
        assert st[3]["overlap"] == (1 if i else 0)
        assert {k: st[3][k] for k in ("B", "Sq", "dec", "pre")} == \
            {"B": B, "Sq": Sq, "dec": dec, "pre": pre}
        stage, = [k for k in kids if k[0] == "exec.stage"]
        assert stage[3] == {"B": B, "Sq": Sq, "dec": dec, "pre": pre,
                            "slots": B * Sq}
    assert [st[3]["step"] for st, _ in steps] == \
        list(range(steps[0][0][3]["step"], steps[0][0][3]["step"]
                   + len(steps)))
    # steps follow one another
    for (a, _), (b, _) in zip(steps, steps[1:]):
        assert a[2] <= b[1]


def test_pipeline_counters_account_for_every_dispatch(tiny_moe):
    """Each mixed dispatch is launched while the one before it is in
    flight, or is the last before a drain: a cancel drains under its own
    cause, the step that finds nothing to launch under `idle`."""
    eng = _engine(tiny_moe)
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 9)),
                           max_new_tokens=8))
    for _ in range(6):
        eng.step()
    assert eng._in_flight is not None
    assert eng.cancel(5)
    eng.run()
    m = eng.metrics
    assert eng._in_flight is None
    assert m.dispatches == m.overlapped_dispatches + sum(
        m.pipeline_drains.values())
    assert m.pipeline_drains == {"cancel": 1, "idle": 1}
    assert m.overlapped_dispatches >= 0.8 * m.dispatches


def test_step_overlap_and_drain_spans(tiny_moe, tmp_path):
    """`moebius.step` carries `overlap`, and each drain opens
    `moebius.exec.drain {cause}` over the fetch and commit it makes."""
    eng = _engine(tiny_moe)
    spans, logged = _traced(eng, tmp_path)
    steps = [st for st, _ in by_step(spans)]
    m = eng.metrics
    assert sum(st[3]["overlap"] for st in steps) == \
        m.overlapped_dispatches > 0
    drains = [e for e in spans if e[0] == "exec.drain"]
    assert [d[3]["cause"] for d in drains] == list(m.pipeline_drains) \
        == ["idle"]
    for d in drains:
        inner = [e[0] for e in spans if d[1] <= e[1] and e[2] <= d[2]
                 and e is not d]
        assert inner == ["exec.fetch", "sched.commit"]


def test_request_stamps_prefill_start(tiny_moe, tmp_path):
    eng = _engine(tiny_moe)
    _traced(eng, tmp_path)
    assert len(eng.finished) == 5
    for r in eng.finished:
        assert r.arrival_s <= r.prefill_start_s <= r.first_token_s \
            <= r.finish_s


def test_fused_decode_span(tiny_moe, tmp_path):
    eng = _engine(tiny_moe, decode_steps=2)
    spans, _ = _traced(eng, tmp_path, n_reqs=2)
    fused = [e for e in spans if e[0] == "exec.fused"]
    assert fused
    steps = by_step(spans)
    assert all(any(st[1] <= f[1] and f[2] <= st[2] for st, _ in steps)
               for f in fused)


def test_span_records_nothing_outside_a_session():
    with span("step", step=1) as sp:
        sp.set_metadata(B=1)
        assert not sp.is_enabled()


def test_switch_spans_match_pause():
    """On four CPU devices: a live chunked switch each way opens
    `moebius.switch {direction, bytes_sent}` over one `switch.plan`, one
    `switch.chunk {i}` per chunk and one `switch.commit`, and plan +
    commit is the switch's recorded pause."""
    out = run_multidevice("""
import glob, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro.configs import get_config
from repro.compat import make_mesh
from repro.core.layouts import EP, TP
from repro.core.policy import PolicyConfig
from repro.serving.engine import EngineConfig, MoebiusEngine
from repro.serving.kvcache import CacheConfig
from repro.serving.request import Request
cfg = get_config("mixtral-8x7b").reduced(
    num_heads=8, num_kv_heads=2, head_dim=8, d_model=32, num_layers=2,
    num_experts=8, top_k=2, d_expert=32, vocab_size=256, capacity_factor=8.0,
    param_dtype=jnp.float32, compute_dtype=jnp.float32)
pol = PolicyConfig(t_high=10**9, t_low=-1, window=1, cooldown_s=10**9)
eng = MoebiusEngine(cfg, make_mesh((1, 4), ("data", "model")),
    CacheConfig(page_size=4, pages_ep=32, max_pages_per_req=16),
    ecfg=EngineConfig(start_layout=TP, ladder=(4, 8), prefill_chunk=8,
                      temperature=0.0, policy=pol, seed=0, chunk_layers=1,
                      warm_switches=True))
eng.warmup()
rng = np.random.default_rng(0)
for i in range(6):
    eng.submit(Request(rid=i, prompt=list(rng.integers(5, 200, 12)),
                       max_new_tokens=20))
for _ in range(4):
    eng.step()
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
for target in (EP, TP):
    eng.execute_switch(target)
    eng.step()
jax.profiler.stop_trace()
(path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
ev = sorted(((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
              dict(e.stats)) for p in ProfileData.from_file(path).planes
             for l in p.lines for e in l.events
             if e.name.startswith("moebius.switch")), key=lambda e: e[1])
sw = [e for e in ev if e[0] == "moebius.switch"]
assert [e[3]["direction"] for e in sw] == ["tp_to_ep", "ep_to_tp"], sw
for s, rec in zip(sw, eng.switch_records[-2:]):
    kids = [e for e in ev if s[1] <= e[1] and e[2] <= s[2] and e is not s]
    names = [k[0] for k in kids]
    assert names[0] == "moebius.switch.plan", names
    assert names[-1] == "moebius.switch.commit", names
    chunks = [k for k in kids if k[0] == "moebius.switch.chunk"]
    assert [c[3]["i"] for c in chunks] == list(range(rec.chunks))
    assert s[3]["bytes_sent"] == rec.bytes_sent > 0, (s[3], rec)
    pc = sum(k[2] - k[1] for k in kids if k[0] in (
        "moebius.switch.plan", "moebius.switch.commit")) * 1e-9
    print("pause", rec.pause_s, "plan+commit", pc)
    assert abs(pc - rec.pause_s) <= 0.05 * rec.pause_s, (pc, rec.pause_s)
print("OK")
""", devices=4, timeout=600)
    assert "OK" in out
