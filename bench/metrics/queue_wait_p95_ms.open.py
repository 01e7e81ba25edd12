"""Scheduler: 95th percentile of the time from a request's due time to the
start of the step in which it left waiting (ms).
"""
from benchlib import readers


def read(run):
    return readers.queue_wait_p95_ms(run)
