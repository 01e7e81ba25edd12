"""One general generator for every traffic mix. A mix is a data file
(`bench/traffic/<name>.json`) of arrival phases and length distributions.

Every seed gets the same work: the lengths and the gaps between arrivals
are drawn once from the mix's `base_seed`, and the run's seed only permutes
them and draws the token ids. So two seeds differ in order and content, not
in how much there is to serve. With `"order": "replay"` the mix is a trace
replayed as drawn: every seed gets the same lengths at the same times, and
the seed draws only the token ids. An open-loop mix near its knee wants
this, because where the long prompts fall among the arrivals sets its
queue.

Arrivals:
  {"kind": "poisson", "phases": [{"start": f0, "end": f1, "rate": r}, ...]}
      open loop; a phase spans the fractions [f0, f1) of the window and
      sends round(r * its seconds) requests at exponential gaps, rescaled to
      fill the phase exactly, the first at the phase's start.
  {"kind": "batch", "n_prompts": n, "samples_per_prompt": s}
      n * s requests due at t = 0; the s samples of a prompt share it.
Lengths:
  {"dist": "lognormal", "median": m, "sigma": s | "p99": q, "min", "max"}
  {"dist": "gamma", "shape": k, "mean": m, "offset": o, "min", "max"}
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

Z99 = 2.326347874


@dataclass
class Req:
    """One request as the client sends it."""
    rid: int
    due: float                # seconds after the window opens
    prompt: np.ndarray        # int32 token ids
    output: int               # forced output length


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "lognormal":
        mu = math.log(spec["median"])
        sigma = spec.get("sigma")
        if sigma is None:
            sigma = (math.log(spec["p99"]) - mu) / Z99
        x = np.exp(mu + sigma * rng.standard_normal(n))
    elif spec["dist"] == "gamma":
        k = spec["shape"]
        x = rng.gamma(k, spec["mean"] / k, n) + spec.get("offset", 0)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x.astype(np.int64), spec["min"], spec["max"])


def _arrivals(arr: dict, seconds: float, base, rng) -> np.ndarray:
    """Due times; `rng` permutes each phase's gaps, or is None to keep
    them as drawn."""
    if arr["kind"] == "batch":
        return np.zeros(arr["n_prompts"] * arr["samples_per_prompt"])
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    out = []
    for ph in arr["phases"]:
        t0, t1 = ph["start"] * seconds, ph["end"] * seconds
        n = int(round(ph["rate"] * (t1 - t0)))
        if n == 0:
            continue
        gaps = base.exponential(1.0, n)
        gaps *= (t1 - t0) / gaps.sum()
        if rng is not None:
            gaps = rng.permutation(gaps)
        out.append(t0 + np.concatenate([[0.0], np.cumsum(gaps[:-1])]))
    return np.sort(np.concatenate(out)) if out else np.zeros(0)


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> list[Req]:
    """The requests of one run, in order of due time."""
    base = np.random.default_rng(mix.get("base_seed", 0))
    rng = np.random.default_rng(seed)
    order = mix.get("order", "permute")
    if order not in ("permute", "replay"):
        raise ValueError(f"unknown order {order!r}")
    perm = rng if order == "permute" else None
    arr = mix["arrivals"]
    due = _arrivals(arr, seconds, base, perm)
    n = len(due)
    share = arr.get("samples_per_prompt", 1)
    n_prompts = -(-n // share)
    plens = _lengths(mix["prompt"], n_prompts, base)
    olens = _lengths(mix["output"], n, base)
    if perm is not None:
        plens, olens = perm.permutation(plens), perm.permutation(olens)
    prompts = [rng.integers(1, vocab, int(p), dtype=np.int32) for p in plens]
    return [Req(rid=i, due=float(due[i]), prompt=prompts[i // share],
                output=int(olens[i])) for i in range(n)]
