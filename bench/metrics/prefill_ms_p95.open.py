"""Scheduler: 95th percentile over the window's requests of the time from
first leaving waiting to the first token, both stamped by the program on
its own clock (`Request.prefill_start_s`, `first_token_s`) (ms). A program
without the stamp reports nothing.
"""
from benchlib import spans


def read(run):
    return spans.prefill_ms_p95(run)
