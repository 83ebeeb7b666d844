"""The device's idle share in the traced window: 1 minus the union of the
operations' intervals over the window, the mean over the cell's chips."""


def read(record, trace, peaks):
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
