"""Model: useful forward FLOPs of the traced window's steps over (window x
chips x bf16 peak) (%).
"""
from benchlib import readers


def read(run):
    return readers.mfu_pct(run)
