"""The program's spans on a trace: idle time by the innermost span over
it, the span readings and the gap names, on hand-made events (every
value by hand); the prefill fill reading on hand-made counters; and the
recorded trace of ``test_trace.py``, which holds no program spans."""
import json
from pathlib import Path

import pytest

from harness import spans as sp
from harness.client import Log
from harness.readings import Run
from harness.trace import Ev, Trace, clip, reduce, union

FIXTURE = Path(__file__).with_name("fixtures") / "trace_small.json"


def hand_trace():
    """Device busy [1, 4), [5, 9), [12, 14) of a 20 s window; a decode
    call whose sync holds a collection, an admission that dispatched a
    prefill round, and one that found no room, outside any harness
    span."""
    ops = [Ev("fusion", 1.0, 3.0), Ev("paged_decode_attention", 5.0, 4.0),
           Ev("paged_prefill_attention", 12.0, 2.0)]
    mods = [Ev("jit_decode_chunk(1)", 1.0, 8.0),
            Ev("jit_prefill_chunk(2)", 12.0, 2.0)]
    harness = [Ev("harness.decode", 0.0, 10.0),
               Ev("harness.client", 10.0, 1.0),
               Ev("harness.admit", 11.0, 5.0)]
    runtime = [Ev("runtime.decode", 0.5, 9.3),
               Ev("runtime.decode.plan", 0.5, 0.5),
               Ev("runtime.decode.stage", 1.0, 0.5),
               Ev("runtime.decode.sync", 1.5, 7.5),
               Ev("runtime.gc", 4.2, 0.6),
               Ev("runtime.decode.emit", 9.0, 0.6),
               Ev("runtime.admit", 11.2, 4.3),
               Ev("runtime.admit.plan", 11.2, 0.4),
               Ev("runtime.prefill.round", 11.6, 0.4),
               Ev("runtime.admit.sync", 12.0, 3.0),
               Ev("runtime.admit.bind", 15.0, 0.4),
               Ev("runtime.admit", 16.5, 0.25),
               Ev("runtime.admit.plan", 16.5, 0.2)]
    return Trace(ops, mods, harness + runtime, chips=1), runtime


def busy_of(tr, window):
    return union(clip([(e.start, e.end) for e in tr.ops], *window))


def test_idle_by_span_by_hand():
    tr, _ = hand_trace()
    window = (0.0, 20.0)
    got = sp.idle_by_span(busy_of(tr, window), tr.spans, window)
    want = {"harness.decode": 0.5 + 0.2, "runtime.decode.plan": 0.5,
            "runtime.decode.sync": 0.2 + 0.2, "runtime.gc": 0.6,
            "runtime.decode.emit": 0.6, "runtime.decode": 0.2,
            "harness.client": 1.0, "harness.admit": 0.2 + 0.5,
            "runtime.admit.plan": 0.4 + 0.2, "runtime.prefill.round": 0.4,
            "runtime.admit.sync": 1.0, "runtime.admit.bind": 0.4,
            "runtime.admit": 0.1 + 0.05, "harness.none": 0.5 + 3.25}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(20.0 - 9.0)


def test_idle_by_span_clips_to_the_window():
    tr, _ = hand_trace()
    window = (4.5, 10.5)
    got = sp.idle_by_span(busy_of(tr, window), tr.spans, window)
    assert got == pytest.approx({"runtime.gc": 0.3,
                                 "runtime.decode.sync": 0.2,
                                 "runtime.decode.emit": 0.6,
                                 "runtime.decode": 0.2,
                                 "harness.decode": 0.2,
                                 "harness.client": 0.5})


def test_gaps_are_named_by_the_innermost_span():
    """A gap under ``runtime.gc`` inside ``harness.decode`` is named
    ``runtime.gc``; where no program span covers it, the harness's."""
    tr, _ = hand_trace()
    r = reduce(tr, (0.0, 20.0))
    assert r.gaps == [("harness.none", pytest.approx(6.0)),
                      ("harness.client", pytest.approx(3.0)),
                      ("runtime.gc", pytest.approx(1.0)),
                      ("runtime.decode.plan", pytest.approx(1.0))]
    # the program's spans leave the harness's readings alone
    assert r.busy_s == pytest.approx(9.0)
    assert r.decode_calls == [(10.0, 8.0)]


def test_span_readings_by_hand():
    _, runtime = hand_trace()
    window = (0.0, 20.0)
    durs = sp.span_s(runtime, window)
    assert durs["runtime.admit.plan"] == pytest.approx([0.4, 0.2])
    assert sp.median_ms(durs["runtime.decode.plan"]) == pytest.approx(500.0)
    assert sp.median_ms(durs["runtime.decode.stage"]) == pytest.approx(500.0)
    assert sp.median_ms(durs["runtime.decode.emit"]) == pytest.approx(600.0)
    # only the admission that dispatched a prefill round counts
    assert sp.admit_plan_ms(runtime, window) == pytest.approx(400.0)
    assert sp.admit_plan_ms(runtime, (16.0, 20.0)) is None
    # spans cut by the window's edge are left out
    assert "runtime.decode" not in sp.span_s(runtime, (1.0, 20.0))
    assert sp.median_ms([3.0, 1.0, 2.0, 10.0]) == pytest.approx(2500.0)
    assert sp.median_ms([]) is None


def test_recorded_trace_without_program_spans():
    """The recorded trace predates the program's spans: all its idle time
    falls under harness spans, and sums to the window less busy time."""
    d = json.loads(FIXTURE.read_text())
    ev = lambda rows: [Ev(n, s, t) for n, s, t in rows]  # noqa: E731
    tr = Trace(ev(d["ops"]), ev(d["modules"]), ev(d["spans"]), d["chips"])
    window = tuple(d["window"])
    r = reduce(tr, window)
    busy = union(clip([(e.start, e.end) for e in tr.ops
                       if e.name not in ("while", "conditional", "call")],
                      *window))
    got = sp.idle_by_span(busy, tr.spans, window)
    assert all(k.startswith("harness.") for k in got)
    assert sum(got.values()) == pytest.approx(
        window[1] - window[0] - r.busy_s, rel=1e-9)
    assert r.busy_s == pytest.approx(d["expected"]["busy_s"], rel=1e-9)


def run_of(stats_open, stats_trace_end):
    lg = Log([], [], [], [], stats_open, stats_trace_end, stats_trace_end,
             0.0, 10.0, 5.0, 0, 0)
    return Run(lg, None, {}, 128, 1, 0.0)


def test_prefill_live_rows_reader():
    from harness import spec
    read = spec.metric_reader("prefill_live_rows_per_dispatch").read
    # three rounds in the traced part, 6 real rows among them
    r = run_of({"prefill_live_rows": 10, "prefill_chunks": 10},
               {"prefill_live_rows": 16, "prefill_chunks": 13})
    assert read(r) == pytest.approx(2.0)
    # no prefill in the traced part, and a program that counts no rows
    assert read(run_of({"prefill_live_rows": 3, "prefill_chunks": 1},
                       {"prefill_live_rows": 3, "prefill_chunks": 1})) \
        is None
    assert read(run_of({"prefill_chunks": 1},
                       {"prefill_chunks": 4})) is None
