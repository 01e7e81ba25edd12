"""Engine step: mean host-clock span of MoebiusEngine.step() over the
window's steps (ms).
"""
from benchlib import readers


def read(run):
    return readers.step_ms(run)
