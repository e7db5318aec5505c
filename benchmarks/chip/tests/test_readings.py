"""The end-to-end readings of a run, on a hand-made log."""
import numpy as np
import pytest

from harness.client import DecodeCall, Log, Rec
from harness.readings import Run


def rec(idx, due, times, done=float("nan")):
    r = Rec(idx, 0, np.zeros(4, np.int32), len(times), due)
    r.times, r.tokens, r.done = list(times), [1] * len(times), done
    r.sent = due
    return r


def run_of(recs, decodes=()):
    lg = Log(recs, list(decodes), [], [], {}, {}, {}, 0.0, 10.0, 10.0, 0, 0)
    return Run(lg, None, {}, 128, 4, 0.0)


def test_ttft_counts_a_stall_to_the_window_end():
    r = run_of([rec(0, 1.0, [3.0, 5.0]), rec(1, 8.0, []),
                rec(2, -2.0, [1.0])])         # due before the window: out
    assert sorted(r.ttft_s()) == pytest.approx([2.0, 2.0])


def test_tpot_per_request_over_tokens_in_the_window():
    # first token before the window: only the in-window ones count
    r = run_of([rec(0, -5.0, [-1.0, 2.0, 2.0, 4.0]),
                rec(1, 1.0, [3.0])])          # one token: no TPOT
    assert r.tpot_s() == pytest.approx([1.0])


def test_itl_shares_a_decode_call_among_its_tokens():
    # tokens at 2 (first), then 4 tokens at 4, then 2 tokens at 5
    r = run_of([rec(0, 1.0, [2.0, 4.0, 4.0, 4.0, 4.0, 5.0, 5.0])])
    assert r.itl_s() == pytest.approx([0.5] * 4 + [0.5] * 2)
    r = run_of([rec(0, 1.0, [2.0, 6.0, 6.0, 7.0])])
    assert r.itl_s() == pytest.approx([2.0, 2.0, 1.0])


def test_rows_per_decode_inside_the_window():
    calls = [DecodeCall(-1.0, 0.5, [1, 2], [0, 0], [4, 4]),
             DecodeCall(1.0, 2.0, [1, 2, 3], [0, 0, 0], [4, 4, 4])]
    assert run_of([], calls).rows_per_decode() == [3]


def test_first_token_reader_is_the_traced_parts_median():
    from harness import spec
    read = spec.metric_reader("first_token_p50_ms").read
    # TTFTs 2, 1 and (10 - 8): a request with no first token counts to
    # the traced part's end (here the window's)
    r = run_of([rec(0, 1.0, [3.0]), rec(1, 2.0, [3.0]), rec(2, 8.0, [])])
    assert read(r) == pytest.approx(2000.0)
    assert read(run_of([rec(0, -2.0, [1.0])])) is None
    # a traced part that ends at 5: the request due at 8 is out, and the
    # one due at 4 counts to 5
    lg = r.log
    lg.trace_end = 5.0
    lg.recs.append(rec(3, 4.0, [9.0]))
    assert read(r) == pytest.approx(1000.0)
