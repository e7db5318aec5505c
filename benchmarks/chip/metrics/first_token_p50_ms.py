"""Median, over the requests due in the traced part of the window, of the
time from the due time to the return of the ``try_admit`` that gave the
first token; a request with none by the traced part's end counts to that
end.  The time to first token of the cell's few arrivals: a reading
beside the end-to-end metrics, too unsteady between runs to bound
(PERF.md section 2)."""
import numpy as np


def read(run):
    lo, hi = run.log.w0, run.log.trace_end
    waits = [min(r.times[0] if r.times else hi, hi) - r.due
             for r in run.log.recs if lo <= r.due < hi and r.sent == r.sent]
    if not waits:
        return None
    return 1e3 * float(np.median(waits))
