"""Span recorder for the serving runtime + Chrome-trace / metrics-JSON export.

Two kinds of span land in one ``Telemetry``:

* **Runtime spans, on the wall clock.**  ``ContinuousRuntime`` opens a
  span around every step of ``try_admit`` and ``decode()`` (the
  ``SPAN_RT_*`` names below, track ``runtime``), and a ``gc.callbacks``
  hook records each Python garbage collection as a ``runtime.gc`` span
  while the recorder is attached.  Each span reads ``time.perf_counter``
  — never the runtime's injected ``timer`` — so attaching a recorder
  leaves the runtime's timer-call sequence unchanged.  With
  ``profiler=True`` each span also enters
  ``jax.profiler.TraceAnnotation(name)``: inside a ``jax.profiler``
  trace it lands on the host plane, on the device trace's own clock,
  beside the device operations it waited on.
* **Lifecycle stamps, on the replay's virtual clock.**  ``replay_trace``
  advances a virtual clock by each dispatch's measured time and stamps
  the intervals the paper decomposes (``stamp``/``instant``):

    queued   -> [arrival, admit]                    track "queue"
    prefill  -> [admit dispatch, first token]       track "slot<i>"
    decode   -> [chunk dispatch, chunk end]         track "slot<i>"
    finish / abandon / reject / abort / stall       instant events

  plus per-dispatch spans on the "host" track.

Design constraints, in order:

1. **Zero behavioural footprint.**  Recording never changes what the
   runtime computes: with a deterministic injected timer, a replay with
   a recorder attached is bitwise-identical to one without (asserted in
   tests/test_telemetry.py).
2. **Cheap when attached.**  A span is one dataclass append and two
   clock reads; there is no formatting, no I/O, no device sync on the
   hot path.  Export happens once, after the run.
3. **No-op when absent.**  With ``runtime.telemetry is None`` each call
   site costs one ``is not None`` test (``NO_SPAN`` stands in for the
   span), no span state exists and no ``gc`` hook is installed.

Export formats:

* ``chrome_trace()`` — the Chrome/Perfetto trace-event JSON (an object
  with a ``traceEvents`` array of ``ph: "X"`` complete spans and
  ``ph: "i"`` instants; one ``tid`` per track, named via ``ph: "M"``
  metadata).  Open it at https://ui.perfetto.dev or chrome://tracing.
* ``write_metrics_json()`` — the flat registry snapshot
  (``runtime.metrics_snapshot()``), written as ``BENCH_serving.json`` by
  the benchmarks.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import time
from typing import Any, Dict, List, Optional, Tuple

# span/instant name constants — the span taxonomy is a public interface
# (docs/observability.md catalogs it); tests import these instead of
# retyping strings
SPAN_QUEUED = "queued"
SPAN_PREFILL = "prefill"
SPAN_DECODE = "decode"
SPAN_REQUEUED = "requeued"       # preempt -> re-admission backoff window
EVT_FINISH = "finish"
EVT_ABANDON = "abandon"
EVT_REJECT = "reject"
EVT_ABORT = "abort"
EVT_STALL = "stall"
EVT_PREEMPT = "preempt"          # slot released, KV demoted to cached LRU
EVT_RESUME = "resume"            # preempted request re-admitted
TRACK_QUEUE = "queue"
TRACK_HOST = "host"
TRACK_RUNTIME = "runtime"        # the wall-clock spans below
# runtime spans (wall clock; the profiler's host plane with profiler=True)
SPAN_RT_ADMIT = "runtime.admit"                # all of try_admit
SPAN_RT_ADMIT_PLAN = "runtime.admit.plan"      # resolve, fit, shed, blocks
SPAN_RT_PREFILL_ROUND = "runtime.prefill.round"  # one chunk-loop round
SPAN_RT_ADMIT_SYNC = "runtime.admit.sync"      # first tokens to the host
SPAN_RT_ADMIT_BIND = "runtime.admit.bind"      # counters, slot binding
SPAN_RT_DECODE = "runtime.decode"              # all of decode()
SPAN_RT_DECODE_PLAN = "runtime.decode.plan"    # block growth, stalls
SPAN_RT_DECODE_STAGE = "runtime.decode.stage"  # arrays + _decode enqueue
SPAN_RT_DECODE_SYNC = "runtime.decode.sync"    # tokens to the host
SPAN_RT_DECODE_EMIT = "runtime.decode.emit"    # token bookkeeping
SPAN_RT_GC = "runtime.gc"                      # a Python collection
RUNTIME_SPANS = (SPAN_RT_ADMIT, SPAN_RT_ADMIT_PLAN, SPAN_RT_PREFILL_ROUND,
                 SPAN_RT_ADMIT_SYNC, SPAN_RT_ADMIT_BIND, SPAN_RT_DECODE,
                 SPAN_RT_DECODE_PLAN, SPAN_RT_DECODE_STAGE,
                 SPAN_RT_DECODE_SYNC, SPAN_RT_DECODE_EMIT, SPAN_RT_GC)
# span-args keys (docs/observability.md catalogs them): prefill/decode
# spans carry the request's sampling mode (core.sampling.MODES) so a
# Perfetto timeline can be filtered by decode policy
ARG_SAMPLING_MODE = "sampling_mode"

# what a call site enters when no recorder is attached
NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class Span:
    """One closed interval on a named track.  ``parent`` is the index in
    ``Telemetry.spans`` of the runtime span open around it, or -1."""
    name: str
    track: str
    t0: float
    t1: float
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    parent: int = -1


@dataclasses.dataclass
class Instant:
    """One point event on a named track."""
    name: str
    track: str
    t: float
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


class _Open:
    """The context manager ``Telemetry.span`` returns: appends the span
    on entry (so ``spans`` is in start order and a child's ``parent``
    index is already known) and closes it on exit."""
    __slots__ = ("tel", "span", "ann")

    def __init__(self, tel: "Telemetry", span: Span):
        self.tel = tel
        self.span = span
        self.ann = None

    def __enter__(self) -> Span:
        tel = self.tel
        sp = self.span
        sp.parent = tel._stack[-1] if tel._stack else -1
        if tel._annotation is not None:
            self.ann = tel._annotation(sp.name)
            self.ann.__enter__()
        spans = tel.spans
        spans.append(sp)
        i = len(spans) - 1
        while spans[i] is not sp:      # a collection's span slipped in
            i -= 1
        tel._stack.append(i)
        sp.t0 = time.perf_counter()
        return sp

    def __exit__(self, *exc) -> bool:
        self.span.t1 = time.perf_counter()
        self.tel._stack.pop()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        return False


class Telemetry:
    """Span recorder.  Attach it with ``runtime.telemetry = tel`` (or pass
    it to ``replay_trace``) and export after the run."""

    def __init__(self, profiler: bool = False) -> None:
        self.profiler = profiler
        self._annotation = None
        if profiler:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self.spans: List[Span] = []
        self.instants: List[Instant] = []
        self._stack: List[int] = []      # indices of the open spans
        self._gc: Optional[Tuple[Span, Any]] = None  # collection running

    # ------------------------------------------------------------- record
    def span(self, name: str, **args: Any) -> _Open:
        """Context manager: a runtime span on the wall clock, nested
        under whichever runtime span is open.  Entering returns the
        ``Span``, whose ``args`` the caller may still fill in."""
        return _Open(self, Span(name, TRACK_RUNTIME, 0.0, 0.0, args))

    def stamp(self, name: str, track: str, t0: float, t1: float,
              **args: Any) -> None:
        """A closed interval measured elsewhere (``replay_trace``'s
        virtual clock)."""
        self.spans.append(Span(name, track, t0, t1, args))

    def instant(self, name: str, track: str, t: float, **args: Any) -> None:
        self.instants.append(Instant(name, track, t, args))

    # ------------------------------------------------------------ gc hook
    def attach(self) -> None:
        """Start recording Python collections (``runtime.telemetry``'s
        setter calls this)."""
        gc.callbacks.append(self._on_gc)

    def detach(self) -> None:
        """Stop recording them; a collection still open is closed now."""
        gc.callbacks.remove(self._on_gc)
        if self._gc is not None:
            self._on_gc("stop", {})

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            sp = Span(SPAN_RT_GC, TRACK_RUNTIME, time.perf_counter(), 0.0,
                      {"generation": info["generation"]},
                      self._stack[-1] if self._stack else -1)
            ann = None
            if self._annotation is not None:
                ann = self._annotation(SPAN_RT_GC)
                ann.__enter__()
            self.spans.append(sp)
            self._gc = (sp, ann)
        elif self._gc is not None:
            sp, ann = self._gc
            self._gc = None
            sp.t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)

    # ---------------------------------------------------------- aggregate
    def span_sequence(self) -> List[Tuple[str, str]]:
        """(name, track) pairs in emission order — the determinism probe:
        same trace + seed must yield the identical sequence regardless of
        measured timings (timestamps may differ; structure may not).
        Collections are left out: the allocator, not the trace, decides
        when they run."""
        return [(s.name, s.track) for s in self.spans
                if s.name != SPAN_RT_GC] + \
               [(e.name, e.track) for e in self.instants]

    # ------------------------------------------------------------- export
    def _tracks(self) -> List[str]:
        """Stable track order: queue, host, slots by index, the rest by
        name."""
        seen = {s.track for s in self.spans} | \
               {e.track for e in self.instants}
        slots = sorted((t for t in seen if t.startswith("slot")),
                       key=lambda t: int(t[4:]))
        fixed = [t for t in (TRACK_QUEUE, TRACK_HOST) if t in seen]
        rest = sorted(seen - set(slots) - set(fixed))
        return fixed + slots + rest

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON: ``{"traceEvents": [...]}``.

        Each track gets its own ``tid`` (seconds converted to the
        format's microseconds).  The ``runtime`` track is on the wall
        clock, rebased so its first event starts at 0; the others are on
        the replay's virtual clock.  Events are sorted by ``ts`` within
        each track, so ``ts`` is monotone per ``tid`` (asserted in
        tests)."""
        tracks = self._tracks()
        tid = {t: i for i, t in enumerate(tracks)}
        wall = [s.t0 for s in self.spans if s.track == TRACK_RUNTIME] + \
               [e.t for e in self.instants if e.track == TRACK_RUNTIME]
        base = {TRACK_RUNTIME: min(wall, default=0.0)}
        events: List[Dict[str, Any]] = []
        for t in tracks:
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": tid[t], "args": {"name": t}})
        per_track: Dict[str, List[Dict[str, Any]]] = {t: [] for t in tracks}
        for s in self.spans:
            b = base.get(s.track, 0.0)
            per_track[s.track].append(
                {"name": s.name, "cat": s.track, "ph": "X", "pid": 0,
                 "tid": tid[s.track], "ts": (s.t0 - b) * 1e6,
                 "dur": (s.t1 - s.t0) * 1e6, "args": dict(s.args)})
        for e in self.instants:
            b = base.get(e.track, 0.0)
            per_track[e.track].append(
                {"name": e.name, "cat": e.track, "ph": "i", "s": "t",
                 "pid": 0, "tid": tid[e.track], "ts": (e.t - b) * 1e6,
                 "args": dict(e.args)})
        for t in tracks:
            events.extend(sorted(per_track[t], key=lambda e: e["ts"]))
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


def write_metrics_json(snapshot: Dict[str, Any], path: str) -> None:
    """Dump a ``runtime.metrics_snapshot()`` payload (or any JSON-able
    metrics dict) to disk, pretty-printed for diffability."""
    with open(path, "w") as f:
        json.dump(snapshot, f, indent=2, sort_keys=True)
        f.write("\n")
