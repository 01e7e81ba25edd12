"""Device: share of the traced window with no op running, averaged over
the cell's four chips (%).
"""
from benchlib import readers


def read(run):
    return readers.idle_pct(run)
