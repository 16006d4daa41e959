"""Share (%) of the traced window in which no program ran on the
device: 1 minus the union of the device's program intervals over the
window's length."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
