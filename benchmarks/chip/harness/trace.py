"""Reduce a profiler trace to what the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain events (seconds, on the trace's
clock): device operations and the jitted programs they ran in, from each
TPU plane, and the harness's own host spans (``harness.*``).  ``reduce``
does the arithmetic: the union of device-busy intervals, device time per
program kind and per kernel, and the idle gaps with what the host was
doing in each.  The reduction runs on plain events, so a small recorded
trace checks it (``tests/test_trace.py``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# kernel -> the name of its Pallas call in the trace (the HLO custom call
# is named after the function that makes the ``pallas_call``)
KERNELS = {"paged_decode": "paged_decode_attention",
           "paged_prefill": "paged_prefill_attention",
           "sgmv": "sgmv"}
# device ops that hold others (a scan's loop): not leaves of the timeline
CONTAINERS = ("while", "conditional", "call")
# program kind -> the jitted function's name inside the module name
PROGRAMS = {"decode": "decode_chunk", "prefill": "prefill_chunk"}
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Ev:
    name: str                   # short name: ``fusion``, ``sgmv``, ...
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Ev]               # device operations, every TPU plane
    modules: List[Ev]           # device program executions
    spans: List[Ev]             # harness host spans
    chips: int


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, spans, chips = [], [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chips += 1
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                ops_line = line.name == OPS_LINE
                out = ops if ops_line else modules
                for e in line.events:
                    out.append(Ev(short(e.name) if ops_line else e.name,
                                  e.start_ns * 1e-9, e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("harness."):
                        spans.append(Ev(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9))
    return Trace(ops, modules, spans, chips)


# ------------------------------------------------------------ arithmetic
def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def program_of(module_name: str) -> Optional[str]:
    for kind, key in PROGRAMS.items():
        if key in module_name:
            return kind
    return None


def short(hlo: str) -> str:
    """``%fusion.311 = f32[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name) or name


_KERNEL_OF = {v: k for k, v in KERNELS.items()}


def kernel_of(ev: Ev) -> Optional[str]:
    return _KERNEL_OF.get(ev.name)


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]          # trace clock
    busy_s: float                        # union of device ops, per chip
    program_s: Dict[str, float]          # device time per program kind
    kernel_s: Dict[Tuple[str, str], float]   # (program, kernel) -> s
    top_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]        # longest idle gaps, by host work
    decode_calls: List[Tuple[float, float]]  # per harness.decode
    #   span: (wall s, device s of the decode program inside it)


def _owner(starts: List[float], mods: List[Ev], t: float) -> Optional[Ev]:
    """The module execution whose interval holds ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].start <= t <= mods[i].end:
        return mods[i]
    return None


def reduce(tr: Trace, window: Tuple[float, float], *, n_top: int = 10
           ) -> Reduced:
    lo, hi = window
    chips = max(tr.chips, 1)
    ops = [e for e in tr.ops if e.end > lo and e.start < hi
           and e.name not in CONTAINERS]
    mods = sorted((e for e in tr.modules if e.end > lo and e.start < hi),
                  key=lambda e: e.start)
    starts = [m.start for m in mods]
    busy = union(clip([(e.start, e.end) for e in ops], lo, hi))
    program_s: Dict[str, float] = {}
    for m in mods:
        kind = program_of(m.name) or "other"
        s, e = max(m.start, lo), min(m.end, hi)
        program_s[kind] = program_s.get(kind, 0.0) + (e - s) / chips
    kernel_s: Dict[Tuple[str, str], float] = {}
    by_op: Dict[str, float] = {}
    for e in ops:
        m = _owner(starts, mods, e.start)
        prog = (program_of(m.name) if m is not None else None) or "other"
        k = kernel_of(e)
        d = (min(e.end, hi) - max(e.start, lo)) / chips
        if k is not None:
            kernel_s[(prog, k)] = kernel_s.get((prog, k), 0.0) + d
        label = f"{prog}:{k or e.name}"
        by_op[label] = by_op.get(label, 0.0) + d
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n_top]

    # the longest idle gaps, each named by the innermost harness span over
    # its middle
    edges = [(lo, lo)] + busy + [(hi, hi)]
    holes = sorted(((b - a, a, b) for (_, a), (b, _)
                    in zip(edges[:-1], edges[1:]) if b > a), reverse=True)
    spans = sorted(tr.spans, key=lambda s: s.dur)
    gaps = []
    for width, a, b in holes[:n_top]:
        mid = 0.5 * (a + b)
        host = next((s.name for s in spans if s.start <= mid <= s.end),
                    "harness.none")
        gaps.append((host, width))

    dec_mods = [m for m in mods if program_of(m.name) == "decode"]
    per_call = []
    for s in (tr.spans if tr.chips else []):
        if s.name != "harness.decode" or s.start < lo or s.end > hi:
            continue
        per_call.append((s.dur, sum(
            max(0.0, min(m.end, s.end) - max(m.start, s.start))
            for m in dec_mods) / chips))
    return Reduced((lo, hi), total(busy) / chips, program_s, kernel_s,
                   top, gaps, per_call)


def host_offset(tr: Trace, host_t0: float, name: str = "harness.decode"
                ) -> float:
    """Trace time minus harness time, from the first ``name`` span and the
    harness's own record of when that call started."""
    first = min((s for s in tr.spans if s.name == name),
                key=lambda s: s.start)
    return first.start - host_t0
