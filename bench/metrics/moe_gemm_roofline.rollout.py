"""Kernel moe_grouped_matmul: roofline bound of its useful work over its
device time (%).
"""
from benchlib import readers


def read(run):
    return readers.roofline_pct(run, "moe_grouped_matmul")
