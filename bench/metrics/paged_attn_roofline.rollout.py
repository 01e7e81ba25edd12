"""Kernel paged_attention: roofline bound of its useful work over its
device time (%).
"""
from benchlib import readers


def read(run):
    return readers.roofline_pct(run, "paged_attention")
