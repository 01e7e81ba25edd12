"""95th percentile over every request with two tokens or more of (last
token - first token) / (tokens - 1) (ms).
"""
from benchlib import readers


def read(run):
    return readers.tpot_p95_ms(run)
