"""The trace reduction: by hand on a made-up trace, against a brute-force
timeline on the small trace recorded on a v5e chip, and `load` on a trace
the CPU records here."""
from pathlib import Path

import numpy as np
import pytest

from benchlib import trace as tr

RECORDED = Path(__file__).resolve().parent / "data" / "trace_v5e_chat.json.gz"

MADE = tr.Trace(
    ops={0: [("fusion.1", 10, 30), ("moe_grouped_matmul.3", 20, 50),
             ("paged_attention.2", 70, 80), ("fusion.12", 95, 130)],
         1: [("moe_grouped_matmul", 0, 40), ("all-reduce.7", 60, 100)]},
    spans=[("bench.step", 5, 55), ("bench.read", 55, 60),
           ("bench.step", 60, 100), ("bench.wait", 100, 120)])


def test_by_hand():
    assert MADE.window == (5, 120)
    # chip 0: [10,50] + [70,80] + [95,120] = 40 + 10 + 25; chip 1: [5,40]
    # + [60,100] = 35 + 40
    assert tr.busy_intervals(MADE, 0) == [(10, 50), (70, 80), (95, 120)]
    assert tr.busy_s(MADE) == pytest.approx((75 + 75) / 2 * 1e-9)
    # moe: chip 0 30 ns, chip 1 clipped to 35 ns
    assert tr.kernel_s(MADE, "moe_grouped_matmul") == pytest.approx(
        (30 + 35) / 2 * 1e-9)
    top = dict(tr.top_ops(MADE))
    assert top["fusion"] == pytest.approx((20 + 25) / 2 * 1e-9)
    assert top["all-reduce"] == pytest.approx(40 / 2 * 1e-9)
    gaps = dict(tr.idle_gaps(MADE))
    # chip 0 idle: [5,10] step, [50,70] mid 60 -> step, [80,95] step;
    # chip 1 idle: [40,60] mid 50 -> step, [100,120] wait
    assert gaps["bench.step"] == pytest.approx((5 + 20 + 15 + 20) / 2 * 1e-9)
    assert gaps["bench.wait"] == pytest.approx(20 / 2 * 1e-9)
    total_idle = sum(s for _, s in tr.idle_gaps(MADE))
    assert total_idle == pytest.approx(MADE.window_s - tr.busy_s(MADE))


def test_json_round_trip(tmp_path):
    p = tmp_path / "t.json.gz"
    tr.save(MADE, str(p))
    back = tr.read(str(p))
    assert back.ops == MADE.ops and back.spans == MADE.spans


def _brute_busy(t: tr.Trace, step_ns: int = 100) -> float:
    lo, hi = t.window
    n = (hi - lo) // step_ns + 1
    tot = 0
    for dev, ops in t.ops.items():
        line = np.zeros(n, bool)
        for _, s, e in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                line[(a - lo) // step_ns:(b - lo) // step_ns] = True
        tot += line.sum() * step_ns
    return tot / len(t.ops) * 1e-9


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    t = tr.read(str(RECORDED))
    assert t.ops and t.spans
    busy = tr.busy_s(t)
    assert 0 < busy <= t.window_s
    assert busy == pytest.approx(_brute_busy(t), rel=0.02)
    names = {op for ops in t.ops.values() for op, _, _ in ops}
    assert any("moe_grouped_matmul" in n for n in names)
    assert any("paged_attention" in n for n in names)
    moe = tr.kernel_s(t, "moe_grouped_matmul")
    lo, hi = t.window
    direct = sum(min(e, hi) - max(s, lo) for ops in t.ops.values()
                 for n, s, e in ops
                 if "moe_grouped_matmul" in n and e > lo and s < hi)
    assert moe == pytest.approx(direct / len(t.ops) * 1e-9)
    assert 0 < moe < busy
    idle = sum(s for _, s in tr.idle_gaps(t, n=1000))
    assert idle == pytest.approx(t.window_s - busy, rel=1e-6)
    # every op of a step runs inside the host span of that step or just
    # after it: device and host share one clock
    steps = [(s, e) for n, s, e in t.spans if n == "bench.step"]
    first = min(s for ops in t.ops.values() for _, s, _ in ops)
    assert steps[0][0] <= first


def test_load_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.read"):
        pass
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [n for n, _, _ in t.spans] == ["bench.step", "bench.read"]
    assert t.ops == {}                  # no TPU planes on the CPU
    assert t.window_s > 0
