"""Share of the traced window in which no op ran on the device, in
percent: 1 - busy / window, the highest over the cell's chips."""


def read(view):
    red = view.reduced
    if not red.devices or red.window_ns <= 0:
        return None
    return 100.0 * max(1.0 - dv.busy_ns / red.window_ns
                       for dv in red.devices)
