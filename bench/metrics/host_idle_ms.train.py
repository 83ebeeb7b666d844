"""The device's idle time while the trainer's host loop runs a step: the
idle time charged to any of the trainer's own spans (runtime/trainer.py
``Trainer.train``: ``train.put``, ``train.dispatch``, ``train.sync``,
``train.replan``, ``train.checkpoint``), per run of the train-step
program, in milliseconds.

The sync span is counted with the others: the idle time there is the host
reading a finished step back, and a gap that runs from the readback into
the next put and dispatch is charged whole to whichever span holds its
midpoint, so only the sum over all of them is steady.  A span in which no
idle time fell is absent from the trace's idle gaps and reads 0.  None
without a trace, without a train-step program, or when none of the spans
is among the idle gaps (a program that emits none, such as one older than
these spans)."""

SPANS = ("train.put", "train.dispatch", "train.sync", "train.replan",
         "train.checkpoint")


def read(record, trace, peaks):
    if not trace:
        return None
    calls = sum(p["calls"] for name, p in trace["programs"].items()
                if "train_step" in name)
    idle = dict(trace["breakdown"]["idle_gaps"])
    if not calls or not any(n in idle for n in SPANS):
        return None
    return 1e3 * sum(idle.get(n, 0.0) for n in SPANS) / calls
