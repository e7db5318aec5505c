"""Median over traced ``decode()`` calls of the call's wall time minus the
device time of the decode program inside it: the host code around the
dispatch (block planning, argument transfer, token bookkeeping)."""
from harness.readings import pct


def read(run):
    if run.trace is None or not run.trace.decode_calls:
        return None
    return 1e3 * pct([w - d for w, d in run.trace.decode_calls], 50)
