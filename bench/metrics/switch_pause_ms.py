"""Switch: mean `SwitchRecord.pause_s` over the switches in the window, the
time each live switch held decode (its plan and its commit) (ms).
"""


def read(run):
    s = run.window.switches
    return sum(x["pause_s"] for x in s) / len(s) * 1e3 if s else None
