"""Scheduler: decode rows per device dispatch over the window
(ServeMetrics.decode_tokens / dispatches).
"""
from benchlib import readers


def read(run):
    return readers.decode_rows_per_step(run)
