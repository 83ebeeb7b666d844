"""Device time of the paged-prefill program over its number of runs in the
traced window."""


def read(record, trace, peaks):
    if not trace:
        return None
    for name, p in trace["programs"].items():
        if "paged_prefill" in name and p["calls"]:
            return 1e3 * p["time_s"] / p["calls"]
    return None
