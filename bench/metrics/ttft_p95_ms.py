"""95th percentile over every request sent in the window of the time from
its due time to its first token (ms).
"""
from benchlib import readers


def read(run):
    return readers.ttft_p95_ms(run)
