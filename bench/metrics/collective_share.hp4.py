"""The share of the traced window in which a chip runs collectives
(``bench/trace_reduce.py`` ``collective_s``, the mean over devices): the
exchange between chips as the device ops that ``trace_reduce.COLLECTIVE``
matches, in percent.  None without a trace, or with fewer than two devices
(no exchange to measure).

Not the exposed share: ``collective_exposed_s`` subtracts every other op
of the "XLA Ops" line, the ``while`` ops that enclose the step's loop
included, so a collective inside the loop counts as hidden whether or not
compute runs beside it."""


def read(record, trace, peaks):
    if not trace or trace["devices"] < 2 or not trace["window_s"]:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
