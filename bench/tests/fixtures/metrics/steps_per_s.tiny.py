"""A fixture metric: training steps per second in the window."""


def read(record, trace, peaks):
    if "steps" not in record:
        return None
    return record["steps"] / record["window_s"]
