"""Switch: mean `SwitchRecord.total_s` over the switches in the window, the
time of each live switch from plan to commit, chunks included (ms).
"""


def read(run):
    s = run.window.switches
    return sum(x["total_s"] for x in s) / len(s) * 1e3 if s else None
