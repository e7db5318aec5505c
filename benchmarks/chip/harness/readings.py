"""What one run measured, in the form the metric readers take.

End-to-end metrics come from the client's host-clock log; per-layer
metrics from the log, the runtime's counters differenced at the window's
edges, the operation counts of ``counts`` and the reduced trace.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from . import counts
from .client import Log
from .trace import Reduced


@dataclasses.dataclass
class Run:
    log: Log
    dims: counts.Dims
    peaks: Dict[str, float]
    chunk: int                          # prefill chunk
    decode_chunk: int
    setup_s: float
    trace: Optional[Reduced] = None

    # ------------------------------------------------------ the window
    @property
    def window_s(self) -> float:
        return self.log.w1 - self.log.w0

    def due_in_window(self, hi: Optional[float] = None) -> List:
        lo, hi = self.log.w0, self.log.w1 if hi is None else hi
        return [r for r in self.log.recs
                if lo <= r.due < hi and r.sent == r.sent]

    def ttft_s(self) -> List[float]:
        """Due time to first token; a request with none by the window's
        end counts at (end - due)."""
        w1 = self.log.w1
        return [min(r.times[0] if r.times else w1, w1) - r.due
                for r in self.due_in_window()]

    def tpot_s(self) -> List[float]:
        """Per request, over its tokens emitted in the window (two or
        more): (last - first) / (tokens - 1)."""
        lo, hi = self.log.w0, self.log.w1
        out = []
        for r in self.log.recs:
            t = [x for x in r.times if lo <= x <= hi]
            if len(t) >= 2:
                out.append((t[-1] - t[0]) / (len(t) - 1))
        return out

    def itl_s(self) -> List[float]:
        """One entry per output token emitted in the window after an
        earlier token of its request that was also in the window: the gap
        from that request's previous emission, shared by the tokens one
        ``decode()`` emitted together."""
        lo, hi = self.log.w0, self.log.w1
        out = []
        for r in self.log.recs:
            t = [x for x in r.times if lo <= x <= hi]
            i = 1
            while i < len(t):
                j = i
                while j < len(t) and t[j] == t[i]:
                    j += 1
                out += [(t[i] - t[i - 1]) / (j - i)] * (j - i)
                i = j
        return out

    def rows_per_decode(self) -> List[int]:
        """Running slots in each ``decode()`` of the window."""
        return [len(c.pos) for c in self.log.decodes
                if self.log.w0 <= c.t0 and c.t1 <= self.log.w1]

    def tokens_in_window(self) -> int:
        lo, hi = self.log.w0, self.log.w1
        return sum(1 for r in self.log.recs for x in r.times
                   if lo <= x <= hi)

    def counter(self, name: str, *, traced: bool = True) -> int:
        """A runtime counter over the traced part of the window (the
        whole window where nothing was traced, or ``traced=False``)."""
        hi = self.log.stats_trace_end if traced else self.log.stats_close
        return hi[name] - self.log.stats_open[name]

    # ------------------------------------ work, counted (traced part)
    @property
    def traced_s(self) -> float:
        return self.log.trace_end - self.log.w0

    def decodes(self) -> List:
        return [c for c in self.log.decodes if c.t1 <= self.log.trace_end]

    def admits(self) -> List:
        return [a for a in self.log.admits if a[1] <= self.log.trace_end]

    def decode_flops(self) -> float:
        return sum(counts.decode_model_flops(self.dims, c.pos, c.accepted)
                   for c in self.decodes())

    def prefill_flops(self) -> float:
        return sum(counts.prefill_model_flops(self.dims, a.prompt_len,
                                              a.start)
                   for _, _, adm in self.admits() for a in adm)

    def paged_decode_min_s(self) -> float:
        d, k = self.dims, self.decode_chunk
        return sum(d.L * counts.roofline_seconds(
            *counts.paged_decode_call(d, [p + j for p in c.pos]), self.peaks)
            for c in self.decodes() for j in range(k))

    def sgmv_decode_min_s(self) -> float:
        d, k = self.dims, self.decode_chunk
        t = 0.0
        for c in self.decodes():
            rows, ads = len(c.pos), len(set(c.adapters))
            t += d.L * k * sum(counts.roofline_seconds(
                *counts.sgmv_call(d, rows, ads, i, o), self.peaks)
                for i, o in d.sites())
        return t

    def paged_prefill_min_s(self) -> float:
        """Rounds of one admission that share an index ran side by side in
        one kernel call (a group); they are counted as one call."""
        d = self.dims
        t = 0.0
        for _, _, adm in self.admits():
            per_item = [counts.prefill_rows(a.prompt_len, a.start,
                                            self.chunk) for a in adm]
            for r in range(max((len(x) for x in per_item), default=0)):
                rows = [x[r] for x in per_item if r < len(x)]
                t += d.L * counts.roofline_seconds(
                    *counts.paged_prefill_call(d, rows), self.peaks)
        return t

    def idle_no_work_s(self) -> float:
        """In the traced part of the window."""
        lo, hi = self.log.w0, self.log.trace_end
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for s, e in self.log.idle)

    def lateness_s(self) -> List[float]:
        return [r.sent - r.due for r in self.due_in_window()]


def pct(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def share(part: float, whole: float) -> Optional[float]:
    """A share in %, or None where there is nothing to divide by."""
    if whole <= 0 or part <= 0:
        return None
    return 100.0 * part / whole


def kernel_s(run: Run, program: str, kernel: str) -> float:
    return run.trace.kernel_s.get((program, kernel), 0.0) \
        if run.trace is not None else 0.0


def program_s(run: Run, program: str) -> float:
    return run.trace.program_s.get(program, 0.0) \
        if run.trace is not None else 0.0

