"""The wall-clock client: drives the program's served step API.

It calls what ``serving.replay.replay_trace`` calls, with the same
admission policy (the program's own ``AdmissionScheduler``, fill-or-expire
groups of two, the fits check, one retry with the group shrunk to one, and
a requeue when blocks are short), but every time is the host's
``time.perf_counter()``: a request is due at its arrival time, its first
token exists when the ``try_admit`` that produced it returns, and each
decoded token when its ``decode()`` returns.  Host work between dispatches
therefore counts, and the generator is late when the loop is busy.

Set-up first admits the traffic's in-service requests, then runs the
pre-roll's arrivals, so the window opens in the steady state of the
cell's rate.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

GROUP = 2            # replay_trace's admission group


@dataclasses.dataclass
class Rec:
    """One request's life, in host-clock seconds from the window's
    opening."""
    idx: int
    adapter: int
    prompt: np.ndarray
    output_len: int
    due: float
    sent: float = float("nan")           # pushed into the scheduler
    admit_call: float = float("nan")     # the try_admit that admitted it
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    done: float = float("nan")
    failed: bool = False
    covered: int = 0                     # prompt tokens the prefix cache
    #   mapped at admission


@dataclasses.dataclass
class DecodeCall:
    t0: float
    t1: float
    pos: List[int]                   # per running row, before the call
    adapters: List[int]
    accepted: List[int]              # tokens each of those rows kept


@dataclasses.dataclass
class Admitted:
    """One request's prefill: its prompt length, the first token the chunk
    loop computed (past the prefix-cache-covered blocks) and its adapter."""
    prompt_len: int
    start: int
    adapter: int


@dataclasses.dataclass
class Log:
    recs: List[Rec]
    decodes: List[DecodeCall]
    admits: List[Tuple[float, float, List[Admitted]]]
    idle: List[Tuple[float, float]]      # no request queued or running
    stats_open: Dict[str, int]
    stats_close: Dict[str, int]
    stats_trace_end: Dict[str, int]
    w0: float                            # the window: [w0, w1]
    w1: float
    trace_end: float                     # a traced run traced [w0, this]
    active_open: int                     # slots running at w0
    active_close: int                    # and at w1


class Client:
    """Runs one traffic set against a runtime: pre-roll, then the window.
    ``annotate`` wraps each call in a profiler span (traced runs only)."""

    def __init__(self, system, traffic, *, prefill_t0: float,
                 annotate: bool = False):
        from repro.serving import AdmissionScheduler
        self.rt = system.runtime
        self.traffic = traffic
        self.annotate = annotate
        self.sched = AdmissionScheduler(group=GROUP, slo_abandon=False)
        for a in range(system.n_adapters):
            # replay_trace's Eq. 2 profile from the warm-up prefill step
            self.sched.register(str(a), prefill_t0,
                                0.15 * prefill_t0 / GROUP)
        self.recs = [Rec(it.idx, it.adapter, it.prompt, it.output_len,
                         it.due) for it in traffic.items + traffic.in_service]
        self._busy = self.recs[len(traffic.items):]
        self.recs = self.recs[:len(traffic.items)]
        self.by_req: Dict[int, Rec] = {}
        self.by_sid: Dict[int, Rec] = {}
        self.decodes: List[DecodeCall] = []
        self.admits: List[Tuple[float, float, List[Admitted]]] = []
        self.idle: List[Tuple[float, float]] = []
        self._next = 0                   # next unsent item (arrival order)
        self._order = sorted(range(len(self.recs)),
                             key=lambda i: self.recs[i].due)

    # ------------------------------------------------------------ helpers
    def _span(self, name: str):
        if not self.annotate:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _now(self) -> float:
        return time.perf_counter() - self.t_open

    def _request(self, rec: Rec):
        from repro.serverless.batching import Request
        return Request(req_id=rec.idx, fn_id=str(rec.adapter),
                       arrival=rec.due, prompt_len=len(rec.prompt),
                       output_len=rec.output_len, slo_ttft=float("inf"))

    def _send_due(self, now: float) -> None:
        """Push every item due by now."""
        while self._next < len(self._order):
            rec = self.recs[self._order[self._next]]
            if rec.due > now:
                break
            self._push(rec, now)
            self._next += 1

    def _push(self, rec: Rec, now: float) -> None:
        rec.sent = now
        req = self._request(rec)
        self.by_req[rec.idx] = rec
        self.sched.push(req)

    def _finish(self, rec: Rec, t: float) -> None:
        rec.done = t

    # ----------------------------------------------------------- admission
    def _try_admit(self, batch, now: float):
        from repro.serving import ServeRequest
        from .model import adapter_name
        items = [ServeRequest(prompt=self.by_req[r.req_id].prompt,
                              adapter=adapter_name(int(r.fn_id)),
                              arrival=r.arrival,
                              max_new_tokens=r.output_len, request=r)
                 for r in batch]
        t0 = self._now()
        with self._span("harness.admit"):
            res = self.rt.try_admit(items, now=now)
        t1 = self._now()
        if res is not None and res.first_tokens:
            rej = {id(r) for r in res.rejected}
            bs = self.rt.scfg.block_size
            done = []
            for r, shared in zip([r for r in batch if id(r) not in rej],
                                 res.shared_blocks):
                self.by_req[r.req_id].covered = shared * bs
                L = r.prompt_len
                # the runtime's chunk loop starts here (_chunk_prefill)
                done.append(Admitted(L, min(shared * bs, (L - 1) // bs * bs),
                                     int(r.fn_id)))
            self.admits.append((t0, t1, done))
        return res, t0, t1

    def _admission(self, now: float) -> None:
        """replay_trace's admission loop, on the host clock."""
        rt, sched = self.rt, self.sched
        while True:
            free = len(rt.slots.free_slots())
            if rt.slots.num_active > 0 and free < GROUP \
                    and sched.pending >= GROUP:
                break
            batch = sched.pop_ready(self._now(), min(free, GROUP))
            if not batch:
                break
            fit = []
            for r in batch:
                if rt.fits(r.prompt_len, max(r.output_len, 1)):
                    fit.append(r)
                else:
                    rt.reject_too_long(r)
                    self.by_req[r.req_id].failed = True
                    self._finish(self.by_req[r.req_id], self._now())
            batch = fit
            if not batch:
                continue
            res, t0, t1 = self._try_admit(batch, self._now())
            if res is None and len(batch) > 1:
                sched.requeue_front(batch[1:])
                batch = batch[:1]
                res, t0, t1 = self._try_admit(batch, self._now())
            if res is None:
                sched.requeue_front(batch)
                if rt.slots.num_active == 0 and rt.pool.in_use == 0:
                    raise RuntimeError("KV pool too small for one request")
                break
            if res.rejected:
                rej = {id(r) for r in res.rejected}
                for r in res.rejected:
                    self.by_req[r.req_id].failed = True
                    self._finish(self.by_req[r.req_id], t1)
                batch = [r for r in batch if id(r) not in rej]
            for i, r in enumerate(batch):
                rec = self.by_req[r.req_id]
                rec.admit_call = t0
                rec.tokens.append(int(res.first_tokens[i]))
                rec.times.append(t1)
                sid = res.slot_ids[i]
                if sid >= 0:
                    self.by_sid[sid] = rec
                else:
                    self._finish(rec, t1)

    # -------------------------------------------------------------- decode
    def _decode(self) -> None:
        rt = self.rt
        running = [s for s in rt.slots.active()]
        pos = [s.pos for s in running]
        adapters = [s.adapter for s in running]
        t0 = self._now()
        with self._span("harness.decode"):
            res = rt.decode()
        t1 = self._now()
        if res is None:
            return
        accepted = []
        for s in running:
            toks = res.emitted.get(s.sid, [])
            accepted.append(len(toks))
            rec = self.by_sid.get(s.sid)
            if rec is not None and toks:
                rec.tokens.extend(int(t) for t in toks)
                rec.times.extend([t1] * len(toks))
        self.decodes.append(DecodeCall(t0, t1, pos, adapters, accepted))
        for st in res.finished:
            rec = self.by_sid.pop(st.sid, None)
            if rec is not None:
                self._finish(rec, t1)
        for st in list(res.aborted) + list(res.preempted):
            rec = self.by_sid.pop(st.sid, None)
            if rec is not None:
                rec.failed = True
                self._finish(rec, t1)

    # ---------------------------------------------------------------- run
    def run(self, seconds: float, on_open=None, *,
            trace_for: Optional[float] = None, on_trace_end=None) -> Log:
        """Pre-roll, then ``seconds`` of window.  ``on_open`` runs once the
        pre-roll is over, as the window opens (counters, trace); the window
        is [w0, w0 + seconds] on the clock that starts at 0 where the
        pre-roll ends.  ``on_trace_end`` runs at the first call boundary
        ``trace_for`` seconds into the window (``Log.trace_end``)."""
        rt = self.rt
        self._start_in_service()
        self.t_open = time.perf_counter() + self.traffic.preroll_s
        w0 = None
        trace_end = None
        stats_open: Dict[str, int] = {}
        stats_trace: Optional[Dict[str, int]] = None
        while True:
            now = self._now()
            if w0 is None and now >= 0.0:
                stats_open = dict(rt.stats)
                active_open = rt.slots.num_active
                if on_open is not None:
                    on_open()
                self.decodes, self.admits, self.idle = [], [], []
                w0 = now = self._now()
            if w0 is not None and trace_end is None \
                    and trace_for is not None and now >= w0 + trace_for:
                if on_trace_end is not None:
                    on_trace_end()
                trace_end = now
                stats_trace = dict(rt.stats)
            if w0 is not None and now >= w0 + seconds:
                break
            with self._span("harness.client"):
                self._send_due(now)
                self._admission(now)
            if rt.slots.num_active:
                self._decode()
                continue
            self._wait(now, w0 + seconds if w0 is not None else 0.0)
        w1 = self._now()
        close = dict(rt.stats)
        return Log(self.recs, self.decodes, self.admits, self.idle,
                   stats_open, close, stats_trace or close, w0, w1,
                   w1 if trace_end is None else trace_end, active_open,
                   rt.slots.num_active)

    def _start_in_service(self) -> None:
        """Admit the in-service requests, all due at once, through the
        same admission path, before the pre-roll's clock starts; then
        shift their times onto that clock (all before the window)."""
        self.t_open = time.perf_counter()
        for rec in self._busy:
            rec.due = 0.0
            self._push(rec, 0.0)
        while self.sched.pending:
            before = self.sched.pending
            self._admission(self._now())
            if self.sched.pending < before:
                continue
            # a group waiting to fill: its timer; else nothing can move
            t = self.sched.next_timer(self._now())
            if t is None:
                raise RuntimeError("in-service requests do not fit the "
                                   "slots and the pool")
            time.sleep(max(t - self._now(), 0.0))
        shift = self._now() + self.traffic.preroll_s
        for rec in self._busy:
            rec.due -= shift
            rec.sent -= shift
            rec.admit_call -= shift
            rec.times = [t - shift for t in rec.times]
            rec.done -= shift
        self.recs = self.recs + self._busy

    def drain(self, want: int, tokens: int, max_s: float) -> None:
        """After the window: admit nothing more, keep decoding until
        ``want`` requests serving ``tokens`` tokens between them have
        finished, or ``max_s`` have passed, so the correctness check has
        finished requests to compare.  Nothing measured comes from this
        part."""
        t_end = self._now() + max_s
        while self.rt.slots.num_active and self._now() < t_end:
            done = [r for r in self.recs if r.done == r.done
                    and not r.failed]
            if len(done) >= want and \
                    sum(len(r.tokens) for r in done) >= tokens:
                break
            self._decode()

    def _wait(self, now: float, until: float) -> None:
        """Nothing runs: sleep to the next arrival or batching timer."""
        nxt = [until]
        if self._next < len(self._order):
            nxt.append(self.recs[self._order[self._next]].due)
        t = self.sched.next_timer(now)
        if t is not None:
            nxt.append(t)
        wake = max(min(nxt), now)
        if self.sched.pending == 0:
            self.idle.append((now, wake))
        with self._span("harness.idle"):
            time.sleep(max(wake - now, 0.0))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
