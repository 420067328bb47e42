"""The device's idle share over the traced window: 1 - the union of the
intervals in which an operation ran on the device, over the window."""


def read(run):
    red = run.reduced
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
