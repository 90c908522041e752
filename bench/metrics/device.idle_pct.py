"""Share of the window in which no operation ran on the device (%).

Source: the profiler trace; busy is the union of the device's operation
intervals inside the window.
"""


def read(w):
    if w.trace is None or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
