"""Tiny stand-ins for the cells, for tests on the CPU: the configuration
files' structure at widths a test run can hold."""
from __future__ import annotations

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]

SMALL = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16}


def conf(name: str, **kw) -> dict:
    """A configuration file of bench/configs cut to tiny widths."""
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update({k: v for k, v in SMALL.items()
              if k in c or k == "head_dim"})
    if "num_experts" in c:
        c["num_experts"] = 8
    c["num_hidden_layers"] = 2
    e = c["engine"]
    e.update(pages_ep=128, max_pages_per_req=16, ladder=[2, 4],
             prefill_chunk=16, token_budget=0)
    c.update(kw)
    return c


def mix(name: str, **kw) -> dict:
    m = copy.deepcopy(json.loads((BENCH / "traffic" / f"{name}.json")
                                 .read_text()))
    m["prompt"].update(min=8, max=120, median=40)
    m["output"].update(min=4, max=40, median=12)
    m["output"].pop("p99", None)
    m["output"].setdefault("sigma", 0.5)
    if m["arrivals"]["kind"] == "batch":
        m["arrivals"].update(n_prompts=4, samples_per_prompt=2)
        m["prompt"].update(mean=40)
    else:
        for ph in m["arrivals"]["phases"]:
            ph["rate"] = 4.0
    m.update(kw)
    return m


def switch_cell():
    """The four-chip switch cell's files at tiny widths on a 1x4 mesh with
    tp and ep resident (4 layers, so the reference spreads one layer per
    device), chunked live switches with warm movers, and a burst that
    takes in-flight requests well past the threshold and back. The token
    budget is the top rung times the chunk, as in the file."""
    import run as bench_run
    c = conf("mixtral-8x7b-l8-tpep4", torch_dtype="float32",
             num_hidden_layers=4)
    e = c["engine"]
    e.update(t_high=4, ladder=[4, 8])
    e["token_budget"] = e["ladder"][-1] * e["prefill_chunk"]
    m = mix("bursty-switch")
    quiet, burst, after = m["arrivals"]["phases"]
    quiet["rate"], burst["rate"], after["rate"] = 1.0, 18.0, 1.0
    return bench_run.Cell(
        name="mixtral-tiny-tpep4.bursty", chips=4, conf=c, mix=m,
        limits={"compare": {"mismatch_pct": 1.0, "mean_logit_gap": 0.001},
                "min_tokens": 20, "pack_tokens": 512},
        metrics=[{"name": n, "unit": u} for n, u in (
            ("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"),
            ("switch_pause_ms", "ms"), ("switch_total_ms", "ms"),
            ("setup_s", "s"))],
        arch=bench_run.load_arch(c["arch"]))
