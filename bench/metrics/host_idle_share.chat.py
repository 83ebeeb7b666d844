"""The share of the traced window in which the device idles while the
serving engine runs a step on the host: the idle time charged to any of
the engine's own spans (serving/engine.py: ``engine.admit``,
``engine.prefill.launch``, ``engine.prefill.sync``,
``engine.decode.launch``, ``engine.decode.sync``, ``engine.commit``), as a
percentage of the window.

The sync spans are counted with the others: the idle time there is the
host reading a finished program's tokens back, and a gap that runs from a
readback into the next launch is charged whole to whichever span holds its
midpoint, so only the sum over all of them is steady.  A span in which no
idle time fell is absent from the trace's idle gaps and reads 0.  None
without a trace, or when none of the spans is among the idle gaps (a
program that emits none, such as one older than these spans)."""

SPANS = ("engine.admit", "engine.prefill.launch", "engine.prefill.sync",
         "engine.decode.launch", "engine.decode.sync", "engine.commit")


def read(record, trace, peaks):
    if not trace or not trace["window_s"]:
        return None
    idle = dict(trace["breakdown"]["idle_gaps"])
    if not any(n in idle for n in SPANS):
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in SPANS) / trace["window_s"]
