"""The program's own spans (``runtime.*``) on a profiler trace.

A runtime with ``Telemetry(profiler=True)`` attached enters a
``jax.profiler.TraceAnnotation`` for every step of ``try_admit`` and
``decode()``, so those steps land on the trace's host plane, on the
device trace's clock (docs/observability.md).  ``load`` reads them as
plain ``trace.Ev``; the rest is arithmetic on plain events, checked on
hand-made ones (``tests/test_spans.py``):

* ``span_s``: the durations of each span name inside a window;
* ``idle_by_span``: all device idle time in a window, by the innermost
  host span (``harness.*`` or ``runtime.*``) over it;
* ``median_ms`` and ``admit_plan_ms``: what the span readings report.
"""
from __future__ import annotations

import bisect
import glob
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import Ev, union

PREFIX = "runtime."
NONE = "harness.none"          # idle time under no span


def load(trace_dir: str, prefix: str = PREFIX) -> List[Ev]:
    """The host-plane events whose name starts with ``prefix``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(Ev(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9))
    return out


def inside(spans: Sequence[Ev], window: Tuple[float, float]) -> List[Ev]:
    lo, hi = window
    return [s for s in spans if s.start >= lo and s.end <= hi]


def span_s(spans: Sequence[Ev], window: Tuple[float, float]
           ) -> Dict[str, List[float]]:
    """Seconds of each span inside the window, by name, in start order."""
    out: Dict[str, List[float]] = {}
    for s in sorted(inside(spans, window), key=lambda s: s.start):
        out.setdefault(s.name, []).append(s.dur)
    return out


def median_ms(durations: Sequence[float]) -> Optional[float]:
    if not durations:
        return None
    x = sorted(durations)
    n = len(x)
    mid = x[n // 2] if n % 2 else 0.5 * (x[n // 2 - 1] + x[n // 2])
    return 1e3 * mid


def admit_plan_ms(spans: Sequence[Ev], window: Tuple[float, float]
                  ) -> Optional[float]:
    """Median ``runtime.admit.plan`` over the ``runtime.admit`` spans
    that hold a ``runtime.prefill.round`` (an admission that dispatched
    a prefill; one that found no room plans and returns)."""
    sp = inside(spans, window)
    admits = [s for s in sp if s.name == PREFIX + "admit"]
    rounds = [s for s in sp if s.name == PREFIX + "prefill.round"]
    plans = [s for s in sp if s.name == PREFIX + "admit.plan"]
    out = []
    for a in admits:
        if not any(a.start <= r.start and r.end <= a.end for r in rounds):
            continue
        out += [p.dur for p in plans
                if a.start <= p.start and p.end <= a.end]
    return median_ms(out)


def idle_by_span(busy: Sequence[Tuple[float, float]], spans: Sequence[Ev],
                 window: Tuple[float, float]) -> Dict[str, float]:
    """Every idle second of the window (the complement of ``busy``, a
    sorted union of device-busy intervals), by the innermost span over
    it: where spans nest, the shortest one holding that instant; where
    none does, ``harness.none``.  The values add up to the idle time."""
    lo, hi = window
    busy = union((max(s, lo), min(e, hi)) for s, e in busy
                 if e > lo and s < hi)
    edges = [(lo, lo)] + busy + [(hi, hi)]
    holes = [(a, b) for (_, a), (b, _) in zip(edges[:-1], edges[1:])
             if b > a]
    sp = sorted((s for s in spans if s.end > lo and s.start < hi),
                key=lambda s: s.start)
    starts = [s.start for s in sp]
    longest = max((s.dur for s in sp), default=0.0)
    out: Dict[str, float] = {}
    for a, b in holes:
        # spans overlapping [a, b): they start before b and no earlier
        # than the longest span's length before a
        i0 = bisect.bisect_left(starts, a - longest)
        i1 = bisect.bisect_left(starts, b)
        over = [s for s in sp[i0:i1] if s.end > a]
        cuts = sorted({a, b} | {t for s in over for t in (s.start, s.end)
                                if a < t < b})
        for c0, c1 in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (c0 + c1)
            held = [s for s in over if s.start <= mid <= s.end]
            name = min(held, key=lambda s: s.dur).name if held else NONE
            out[name] = out.get(name, 0.0) + (c1 - c0)
    return out
