"""The program's spans as the benchmark reads them: a traced window of the
tiny chat cell on the CPU (nesting, order, arguments against the dispatch
log, request stamps), the readers by hand on made-up spans, and the
reductions on the traces recorded on a v5e chip."""
import gzip
import json
import time
from pathlib import Path

import pytest

import run as bench_run
import span_report
import tiny
from benchlib import spans
from benchlib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "trace_v5e_chat.json.gz"
RECORDED_SPANS = DATA / "trace_v5e_chat_spans.json.gz"
EXEC = ["moebius.sched.plan", "moebius.exec.stage", "moebius.exec.launch",
        "moebius.exec.fetch", "moebius.sched.commit"]


@pytest.fixture(scope="module")
def chat_window(tmp_path_factory):
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    cell = bench_run.load_cell("mixtral-l4.chat", True)
    cell.conf = tiny.conf("mixtral-8x7b-l4", torch_dtype="float32")
    cell.mix = tiny.mix("chat")
    return span_report.traced_window(cell, 2**31 + 17, 2.0, chip=False)


def test_step_spans_nest_and_match_dispatches(chat_window):
    win, trace, program = chat_window
    steps = spans.per_step(program)
    assert len(steps) == len(win.steps) > 5
    bench_steps = [s for s in trace.spans if s[0] == "bench.step"]
    for (st, kids), b, logged in zip(steps, bench_steps, win.steps):
        assert b[1] <= st[1] and st[2] <= b[2]
        assert [k[0] for k in kids if k[0] in EXEC] == EXEC
        assert len(kids) + 1 <= 16
        (B, Sq, rows), = logged.dispatches
        a = st[3]
        assert (a["B"], a["Sq"]) == (B, Sq)
        assert a["dec"] == sum(1 for r in rows if r[0] == "decode")
        assert a["pre"] == sum(r[2] for r in rows if r[0] != "decode")
        stage, = spans.named(kids, "exec.stage")
        assert {k: stage[3][k] for k in ("B", "Sq", "dec", "pre")} == \
            {k: a[k] for k in ("B", "Sq", "dec", "pre")}
        assert stage[3]["slots"] == B * Sq


def test_report_of_the_tiny_window(chat_window, tmp_path):
    win, trace, program = chat_window
    rep = span_report.report(win, trace, program)
    path = tmp_path / "t.json.gz"
    span_report.save(trace, program, str(path), ms=1e6)
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    assert tr.Trace.from_json(d).spans == trace.spans
    assert [tuple(e) for e in d["program"]] == program
    assert rep["token_fill_pct"] == pytest.approx(
        rep["token_fill_pct_dispatch_log"])
    assert 0 < rep["host_ms_per_step"] < rep["step_ms"]
    n_dec = rep["decode_steps"]
    mean = ((rep["decode_step_ms"] or 0) * n_dec + rep["mixed_step_ms"]
            * (rep["steps"] - n_dec)) / rep["steps"]
    assert mean == pytest.approx(rep["step_ms"], rel=0.05)
    assert rep["spans_per_step_max"] <= 16
    assert "moebius.exec.stage" in rep["self_ms_per_step"]


def test_request_stamps(chat_window):
    win, _, _ = chat_window
    served = [r.req for r in win.sent if r.req.first_token_s is not None]
    assert served
    for q in served:
        assert q.arrival_s <= q.prefill_start_s <= q.first_token_s
    run = bench_run.Run(conf={}, arch=None, dims=None, peaks={}, chips=1,
                        setup_s=0.0, window=win, trace=None)
    assert spans.prefill_ms_p95(run) > 0


MADE = tr.Trace(
    ops={0: [("fusion", 10, 30), ("moe_grouped_matmul", 40, 50),
             ("fusion", 80, 95)]},
    spans=[("bench.step", 0, 60), ("bench.read", 60, 70),
           ("bench.step", 70, 100)])
PROGRAM = [
    ("moebius.step", 2, 58, {"step": 1, "B": 4, "Sq": 8, "dec": 1,
                             "pre": 7}),
    ("moebius.sched.plan", 4, 8, {}),
    ("moebius.exec.stage", 8, 20, {"B": 4, "Sq": 8, "dec": 1, "pre": 7,
                                   "slots": 32}),
    ("moebius.exec.launch", 20, 22, {}),
    ("moebius.exec.fetch", 22, 52, {}),
    ("moebius.sched.commit", 52, 56, {}),
    ("moebius.step", 72, 98, {"step": 2, "B": 4, "Sq": 1, "dec": 2,
                              "pre": 0}),
    ("moebius.exec.stage", 74, 78, {"B": 4, "Sq": 1, "dec": 2, "pre": 0,
                                    "slots": 4}),
    ("moebius.exec.fetch", 78, 96, {}),
]


def test_readers_by_hand():
    assert spans.host_ms_per_step(PROGRAM) == pytest.approx(
        ((56 - 30) + (26 - 18)) / 2 * 1e-6)
    assert spans.step_ms(PROGRAM, prefill=True) == pytest.approx(56e-6)
    assert spans.step_ms(PROGRAM, prefill=False) == pytest.approx(26e-6)
    assert spans.token_fill_pct(PROGRAM) == pytest.approx(10 / 36 * 100)
    # self time: step 1 keeps [2,4) + [56,58), its children their own
    own = spans.self_ms_per_step(PROGRAM)
    assert own["moebius.step"] == pytest.approx((4 + 4) / 2 * 1e-6)
    assert own["moebius.exec.fetch"] == pytest.approx((30 + 18) / 2 * 1e-6)
    # chip idle: [0,10) [30,40) [50,80) [95,100), each part to the
    # innermost span open over it
    gaps = spans.idle_by_span(MADE, PROGRAM)
    want = {"bench.step": 2 + 2 + 2 + 2, "moebius.step": 2 + 2 + 2 + 2,
            "moebius.sched.plan": 4, "moebius.exec.stage": 2 + 4,
            "moebius.exec.fetch": 10 + 2 + 2 + 1, "moebius.sched.commit": 4,
            "bench.read": 10}
    assert gaps == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(gaps.values()) == pytest.approx(
        MADE.window_s - tr.busy_s(MADE))
    idle, share = spans.step_idle_labelled(gaps)
    assert idle == pytest.approx(45e-9)
    assert share == pytest.approx(37 / 45)
    # without program spans the split is by the harness's spans alone
    assert spans.idle_by_span(MADE, []) == pytest.approx(
        {"bench.step": 45e-9, "bench.read": 10e-9})
    assert spans.host_ms_per_step([]) is None
    assert spans.token_fill_pct([]) is None


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace")
def test_recorded_reductions_unchanged():
    """The harness's reductions on the first recorded v5e trace read what
    they read when it was recorded."""
    t = tr.read(str(RECORDED))
    assert t.window == (66633796, 448090382)
    assert tr.busy_s(t) == pytest.approx(0.34132882, rel=1e-9)
    assert tr.kernel_s(t, "moe_grouped_matmul") == pytest.approx(
        0.164853933, rel=1e-9)
    assert [n for n, _ in tr.top_ops(t, n=3)] == [
        "moe_grouped_matmul", "dynamic-slice_bitcast_fusion", "fusion"]
    assert tr.idle_gaps(t) == [["bench.step", pytest.approx(0.040127766,
                                                            rel=1e-9)]]
    split = spans.idle_by_span(t, [])
    assert sum(split.values()) == pytest.approx(0.040127766, rel=1e-9)
    assert split["bench.step"] > 0.99 * 0.040127766


@pytest.mark.skipif(not RECORDED_SPANS.exists(),
                    reason="no recorded chip trace with program spans")
def test_recorded_program_spans():
    with gzip.open(RECORDED_SPANS, "rt") as f:
        d = json.load(f)
    t = tr.Trace.from_json(d)
    program = [tuple(e) for e in d["program"]]
    assert program
    gaps = spans.idle_by_span(t, program)
    assert sum(gaps.values()) == pytest.approx(t.window_s - tr.busy_s(t),
                                               rel=1e-6)
    _, share = spans.step_idle_labelled(gaps)
    assert share >= 0.95
    for st, kids in spans.per_step(program):
        if st[3]["B"]:
            assert [k[0] for k in kids if k[0] in EXEC] == EXEC
    t0 = time.perf_counter()
    spans.idle_by_span(t, program)
    assert time.perf_counter() - t0 < 5
