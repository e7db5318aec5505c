"""The trace reduction: the union of busy intervals, device time per
program and per kernel (by name), and the idle gaps named by the host
span over them; on hand-made events and on a small trace recorded on a
TPU v5e (``fixtures/trace_small.json``, written by ``probe_trace.py``)."""
import json
from pathlib import Path

import pytest

from harness.trace import Ev, Trace, reduce, short, union

FIXTURE = Path(__file__).with_name("fixtures") / "trace_small.json"


def hand_trace():
    mods = [Ev("jit_decode_chunk(1)", 0.0, 10.0),
            Ev("jit_prefill_chunk(2)", 12.0, 3.0)]
    ops = [Ev("while", 0.5, 9.0),      # a scan's loop holds the rest
           Ev("paged_decode_attention", 1.0, 3.0),
           Ev("fusion", 4.0, 2.0),
           Ev("sgmv", 6.0, 1.0),
           Ev("copy", 6.5, 1.5),
           Ev("paged_prefill_attention", 12.0, 2.0)]
    spans = [Ev("harness.decode", 0.0, 11.0),
             Ev("harness.client", 11.0, 1.0),
             Ev("harness.admit", 11.5, 4.0)]
    return Trace(ops, mods, spans, chips=1)


def test_short_names():
    assert short("%fusion.311 = f32[513024]{0} fusion(f32[16,32064] %a)") \
        == "fusion"
    assert short("%paged_decode_attention.8 = bf16[16,10,4,128] "
                 "custom-call(s32[16] %x)") == "paged_decode_attention"
    assert short("%dynamic-slice_bitcast_fusion.20 = bf16[64] f()") \
        == "dynamic-slice_bitcast_fusion"
    assert short("copy.4") == "copy"


def test_union():
    assert union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]


def test_reduce_by_hand():
    r = reduce(hand_trace(), (0.0, 16.0))
    assert r.busy_s == pytest.approx(9.0)          # [1, 8) and [12, 14)
    assert r.program_s == {"decode": 10.0, "prefill": 3.0}
    assert r.kernel_s == {("decode", "paged_decode"): 3.0,
                          ("decode", "sgmv"): 1.0,
                          ("prefill", "paged_prefill"): 2.0}
    assert r.gaps == [("harness.decode", 4.0), ("harness.admit", 2.0),
                      ("harness.decode", 1.0)]
    assert r.decode_calls == [(11.0, 10.0)]
    assert dict(r.top_ops)["decode:paged_decode"] == 3.0
    assert dict(r.top_ops)["decode:copy"] == 1.5


def test_reduce_clips_to_the_window():
    r = reduce(hand_trace(), (2.0, 13.0))
    assert r.busy_s == pytest.approx(6.0 + 1.0)    # [2, 8) and [12, 13)
    assert r.kernel_s[("decode", "paged_decode")] == pytest.approx(2.0)


def recorded():
    d = json.loads(FIXTURE.read_text())
    ev = lambda rows: [Ev(n, s, t) for n, s, t in rows]  # noqa: E731
    return (Trace(ev(d["ops"]), ev(d["modules"]), ev(d["spans"]),
                  d["chips"]), tuple(d["window"]), d)


def test_reduce_recorded_trace():
    tr, window, d = recorded()
    r = reduce(tr, window)
    lo, hi = window
    assert 0 < r.busy_s <= hi - lo
    assert r.program_s["decode"] > 0
    # every kernel found ran inside a served program, and no program's
    # kernels take longer than the program
    for (prog, kern), s in r.kernel_s.items():
        assert prog in ("decode", "prefill") and s > 0
        assert s <= r.program_s[prog]
    assert ("decode", "paged_decode") in r.kernel_s
    assert ("decode", "sgmv") in r.kernel_s
    assert all(w >= 0 and name.startswith("harness.")
               for name, w in r.gaps)
    for wall, dev in r.decode_calls:
        assert 0 < dev <= wall
    for key, want in d["expected"].items():
        got = {"busy_s": r.busy_s,
               "decode_s": r.program_s["decode"],
               "paged_decode_s": r.kernel_s[("decode", "paged_decode")],
               "sgmv_decode_s": r.kernel_s[("decode", "sgmv")]}[key]
        assert got == pytest.approx(want, rel=1e-9)
