"""The scheduler's queue wait: the median time from a request's due time to
the engine's "admitted" event for it, over the requests due in the traced
window that were admitted before it closed."""


def read(record, trace, peaks):
    return record.get("queue_wait_ms")
