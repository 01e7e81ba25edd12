"""Build the engine under test from a configuration file and drive its
timed window from the client side.

The harness owns the clock. Each request is sent when its due time comes on
the host clock; every latency is measured from that due time. The harness
calls `MoebiusEngine.step()` itself and reads each request's tokens through
its `AsyncEngine` stream (`drain_available`) after every step, stamping them
with the host clock. Host spans (`bench.step`, `bench.send`, `bench.read`,
`bench.wait`) go into the profiler's trace when one is recorded.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field


def build(arch, conf: dict, seed: int, backend: str | None = None):
    """Engine under test for the configuration file `conf`, built through
    the launcher's construction path; `arch` is the file's architecture
    module (bench/arch), which gives the program's ModelConfig."""
    from repro.launch.serve import build_engine
    from repro.serving.kvcache import CacheConfig
    e = conf["engine"]
    cc = CacheConfig(page_size=e["page_size"], pages_ep=e["pages_ep"],
                     max_pages_per_req=e["max_pages_per_req"])
    return build_engine(
        arch.model_config(conf), mesh=conf["mesh"], layouts=e["layouts"],
        policy=e["policy"], t_high=e.get("t_high"), cache=cc,
        ladder=tuple(e["ladder"]), prefill_chunk=e["prefill_chunk"],
        token_budget=e["token_budget"], chunk_layers=e["chunk_layers"],
        warm_switches=e["warm_switches"], prefix_cache=e["prefix_cache"],
        qos=e["qos"], idle_skip=e["idle_skip"], temperature=0.0,
        seed=seed, attn_backend=backend, moe_backend=backend,
        switch_backend=backend)


@dataclass
class Sent:
    """Client-side record of one request."""
    rid: int
    due: float                       # host clock
    prompt_len: int
    target: int
    sent: float = 0.0
    left_wait: float | None = None   # start of the step it left `waiting`
    times: list = field(default_factory=list)   # host stamp per token
    req: object = None               # the engine's Request


@dataclass
class Step:
    t0: float
    t1: float
    dispatches: list                 # (B, Sq, rows) per device dispatch


class DispatchLog:
    """Records the composition of every device dispatch: each
    `Executor.run_mixed` plan's rung B, chunk width Sq and rows
    (kind, start position, tokens, prompt length)."""

    def __init__(self, ex):
        self.now: list = []
        orig = ex.run_mixed

        def run_mixed(plan, step_i):
            self.now.append((plan.B, plan.Sq, tuple(
                (r.kind, r.start_pos, r.n_tokens, r.req.prompt_len)
                for r in plan.rows)))
            return orig(plan, step_i)
        ex.run_mixed = run_mixed

    def take(self) -> list:
        out, self.now = self.now, []
        return out


COUNTERS = ("dispatches", "mixed_dispatches", "decode_tokens",
            "prefill_tokens", "preemptions", "prefix_hits",
            "prefix_tokens_saved", "truncations")


@dataclass
class Window:
    t_open: float
    t_close: float                   # t_open + seconds
    t_stop: float                    # end of the drain
    sent: list
    steps: list
    counters: dict
    switches: list
    compiles: int
    late_s: list                     # send time - due time, per request


def run_window(eng, reqs, seconds: float, drain: str, *, counter=None,
               trace: bool = False, clock=time.perf_counter) -> Window:
    """Serve `reqs` (traffic.Req, due seconds after the window opens) for
    `seconds`; then, with drain == "first_token", keep stepping, sending
    nothing new, until every sent request has its first token."""
    from repro.serving.frontend import AsyncEngine
    from repro.serving.request import State
    if trace:
        import jax
        span = jax.profiler.TraceAnnotation
    else:
        def span(_name):
            return contextlib.nullcontext()
    fe = AsyncEngine(eng)
    log = DispatchLog(eng.ex)
    m = eng.metrics
    c0 = {k: getattr(m, k) for k in COUNTERS}
    sw0 = len(eng.switch_records)
    n0 = counter.n if counter is not None else 0
    queue = deque(reqs)
    sent, live, steps, late = [], [], [], []
    streams = {}
    t_open = clock()
    t_close = t_open + seconds
    while True:
        now = clock()
        open_ = now < t_close
        # every request due inside the window is sent, late if a step
        # held the client past its due time or past the close
        if queue and t_open + queue[0].due <= now:
            with span("bench.send"):
                while queue and t_open + queue[0].due <= now:
                    q = queue.popleft()
                    s = fe.generate(q.prompt.tolist(),
                                    max_new_tokens=q.output,
                                    forced_len=q.output, rid=q.rid)
                    rec = Sent(q.rid, t_open + q.due, len(q.prompt),
                               q.output, sent=clock(), req=s.req)
                    late.append(rec.sent - rec.due)
                    sent.append(rec)
                    live.append(rec)
                    streams[q.rid] = s
        if not open_ and not queue:
            if drain != "first_token" or all(r.times for r in sent):
                break
        if not eng.sched.has_work():
            if not open_:
                break
            nxt = t_open + queue[0].due if queue else t_close
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_close) - clock()))
            continue
        with span("bench.step"):
            t0 = clock()
            eng.step()
            t1 = clock()
        steps.append(Step(t0, t1, log.take()))
        with span("bench.read"):
            still = []
            for rec in live:
                if (rec.left_wait is None
                        and rec.req.state is not State.WAITING):
                    rec.left_wait = t0
                n = len(streams[rec.rid].drain_available())
                rec.times.extend([t1] * n)
                if rec.req.state is not State.FINISHED:
                    still.append(rec)
            live = still
    t_stop = clock()
    return Window(
        t_open=t_open, t_close=t_close, t_stop=t_stop, sent=sent,
        steps=steps,
        counters={k: getattr(m, k) - c0[k] for k in COUNTERS},
        switches=[dict(direction=r.direction, pause_s=r.pause_s,
                       total_s=r.total_s, kv_pages=r.kv_pages,
                       chunks=r.chunks, live_requests=r.live_requests)
                  for r in eng.switch_records[sw0:]],
        compiles=(counter.n - n0) if counter is not None else 0,
        late_s=late)
