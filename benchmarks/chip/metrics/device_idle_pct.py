"""1 - (union of device-operation intervals) / (traced time in which at
least one request was queued or running), in %, over the traced part of
the window."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    work = run.traced_s - run.idle_no_work_s()
    if work <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - run.trace.busy_s / work)
