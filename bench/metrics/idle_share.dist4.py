"""Share of the traced window, over the joins, in which no operation ran on
the device: 1 − the union of each device's operation intervals / window,
the mean over the four device planes."""


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0:
        return None
    return 1.0 - red.busy_s / red.window_s
