"""Seconds from process start to the window's first due request,
compilation included.
"""
from benchlib import readers


def read(run):
    return run.setup_s
