"""Every seed gets the same work: in another order, or, for a mix that
replays its trace, in the same order at the same times."""
import numpy as np

import tiny
from benchlib import traffic
import json


def _mix(name):
    return json.loads((tiny.BENCH / "traffic" / f"{name}.json").read_text())


def test_same_work_every_seed():
    for name in ("chat", "rollout", "bursty-switch"):
        mix = _mix(name)
        a = traffic.generate(mix, 40, 5, 32000)
        b = traffic.generate(mix, 40, 2**31 + 99, 32000)
        assert sorted(len(r.prompt) for r in a) == \
            sorted(len(r.prompt) for r in b)
        assert sorted(r.output for r in a) == sorted(r.output for r in b)
        gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))
        if mix["arrivals"]["kind"] == "poisson":
            assert len(a) == len(b)
        assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
        c = traffic.generate(mix, 40, 5, 32000)
        assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in c]
        assert all(0 < t < 32000 for r in a for t in r.prompt[:50])
        del gaps


def test_replay_keeps_order_and_times():
    mix = _mix("chat")
    assert mix["order"] == "replay"
    a = traffic.generate(mix, 40, 5, 32000)
    b = traffic.generate(mix, 40, 2**31 + 99, 32000)
    assert [(r.due, len(r.prompt), r.output) for r in a] == \
        [(r.due, len(r.prompt), r.output) for r in b]
    mix["order"] = "permute"
    c = traffic.generate(mix, 40, 2**31 + 99, 32000)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]


def test_phases_and_bounds():
    mix = _mix("chat")
    mix["arrivals"]["phases"] = [
        {"start": 0.0, "end": 0.3, "rate": 0.2},
        {"start": 0.3, "end": 0.5, "rate": 2.0},
        {"start": 0.5, "end": 1.0, "rate": 0.2}]
    reqs = traffic.generate(mix, 40, 1, 32000)
    due = np.array([r.due for r in reqs])
    for ph in mix["arrivals"]["phases"]:
        lo, hi = ph["start"] * 40, ph["end"] * 40
        n = ((due >= lo) & (due < hi)).sum()
        assert n == round(ph["rate"] * (hi - lo))
    assert due.min() == 0.0 and due.max() < 40
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.output <= mix["output"]["max"]


def test_rollout_groups_share_prompts():
    reqs = traffic.generate(_mix("rollout"), 40, 3, 151936)
    assert len(reqs) == 256 and all(r.due == 0.0 for r in reqs)
    for g in range(32):
        grp = reqs[8 * g:8 * g + 8]
        assert all(r.prompt is grp[0].prompt for r in grp)
