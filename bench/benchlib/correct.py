"""What decides `correct`: the tokens the timed window served, compared
with the plain reference once the window has closed.

A sample drawn from the seed of the requests that were served (the longest
first, then others until the packed sequence is full) is run through the
reference as prompt + served tokens. For every served token the gap is the
reference's best logit minus the reference's logit of that token (0 when
the program chose the reference's best). The numbers compared, each with
its limit, are those the cell's `bench/limits/<cell>.json` names under
`compare`: `mismatch_pct`, the share of served tokens that are not the
reference's best, and `mean_logit_gap`; the widest gap is printed beside
them.
"""
from __future__ import annotations

import numpy as np


def pick(served: list, seed: int, pack: int) -> list:
    """served: [(prompt ids, served ids)] with at least one served token.
    Returns the sample: the longest, then a seeded shuffle of the rest,
    each taken while prompt + served still fits `pack` positions."""
    if not served:
        return []
    lens = [len(p) + len(s) - 1 for p, s in served]
    order = np.random.default_rng(seed).permutation(len(served)).tolist()
    first = int(np.argmax(lens))
    order.remove(first)
    out, used = [], 0
    for i in [first] + order:
        if used + lens[i] <= pack:
            out.append(served[i])
            used += lens[i]
    return out


def packed(sample: list, pack: int):
    """One packed sequence: tokens, segment ids (-1 padding), the position
    of each served token's prediction, and the served tokens."""
    toks = np.zeros(pack, np.int32)
    seg = np.full(pack, -1, np.int32)
    idx, want = [], []
    at = 0
    for j, (p, s) in enumerate(sample):
        full = list(p) + list(s[:-1])
        toks[at:at + len(full)] = full
        seg[at:at + len(full)] = j
        idx.extend(range(at + len(p) - 1, at + len(full)))
        want.extend(s)
        at += len(full)
    return toks, seg, np.asarray(idx, np.int32), np.asarray(want, np.int32)


def check(arch, conf: dict, weights, sample: list, pack: int,
          control: bool = False) -> dict:
    """Widest gap over the sample, by the reference of the architecture
    module `arch`. With `control`, the reference in fp8 is put in the
    program's place: at every position the gap of the token the fp8
    forward ranks first."""
    toks, seg, idx, want = packed(sample, pack)
    xr = arch.hidden(conf, weights, toks, seg, idx)
    if control:
        xl = arch.hidden(conf, weights, toks, seg, idx, quant="fp8")
        want = arch.head(conf, weights, xl, want, quant="fp8")[2]
    best, picked, _ = arch.head(conf, weights, xr, want)
    g = best - picked
    n = max(len(g), 1)
    return {"max_logit_gap": float(g.max()) if len(g) else float("inf"),
            "mean_logit_gap": float(g.mean()) if len(g) else float("inf"),
            "mismatch_pct": 100.0 * int((g > 0).sum()) / n if len(g)
            else float("inf"),
            "tokens": int(len(g)), "requests": len(sample),
            "mismatches": int((g > 0).sum())}
