"""Helpers of the work counts that know no architecture. Each
architecture's module (`bench/arch/<arch>.py`) counts its own block's
operations and bytes from the model's shapes and the step's composition
alone: never from a kernel's grid, padding or capacity. So they count the
same useful work whatever implements the step.

A step is a list of rows (kind, start, n, prompt_len): a row feeds `n`
tokens whose KV positions are start .. start+n-1. A decode row has n == 1.
A row's sample is used when it is a decode row or the chunk that completes
its prompt (start + n == prompt_len); only those rows count the LM head.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def keys_seen(window: int, start: int, n: int) -> int:
    """Sum over the row's tokens of the keys each attends to (causal,
    within the sliding window if `window` > 0)."""
    if not window:
        # tokens at positions p = start .. start+n-1 see p + 1 keys
        return n * start + n * (n + 1) // 2
    return sum(min(p + 1, window) for p in range(start, start + n))


def keys_read(window: int, start: int, n: int) -> int:
    """Distinct KV positions the row reads."""
    end = start + n
    first = max(0, start - window + 1) if window else 0
    return end - first


def tokens(rows) -> int:
    return sum(n for _, _, n, _ in rows)


def sampled_rows(rows) -> int:
    return sum(1 for kind, s, n, plen in rows
               if kind == "decode" or s + n == plen)


def expected_experts(E: int, k: int, T: int) -> float:
    """Distinct experts hit by T tokens each routed to k of E, expected
    under uniform routing (random weights route near uniformly). Never more
    than E, and exactly k for one token."""
    if T <= 0:
        return 0.0
    return E * (1.0 - (1.0 - k / E) ** T)
