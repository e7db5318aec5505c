"""The readings of a run, on a hand-made log: the end-to-end ones, and
every per-layer reader on a hand-made traced part."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import spec
from harness.client import Admitted, DecodeCall, Log, Rec
from harness.readings import Run
from harness.trace import Ev, Trace, reduce


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


# ---------------------------------------- per-layer readers, every one
FIXTURES = Path(__file__).with_name("fixtures")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# Each per-layer reader's value on ``traced_run``, with the counts of the
# cell's configuration and of the Phi-3-shaped fixture: computed once by
# the harness as it stood before the counts moved into the reference
# module (``counts.Dims`` built from the program's ModelConfig and by hand).
PARENT = {
    "qwen": {
        "prefix_hit_pct": 32.0,
        "first_token_p50_ms": 800.0,
        "prefill_mfu_pct": 67.39727662521823,
        "paged_prefill_roofline": 7.589276166768778,
        "decode_host_ms": 5.915252999999954,
        "decode_mfu_pct": 0.07609650402636216,
        "paged_decode_roofline": 1.3674649054262324,
        "sgmv_roofline.decode": 17.020656145297586,
        "device_idle_pct": 83.30362035897465,
        "prefill_live_rows_per_dispatch": 1.2222222222222223,
    },
    "phi": {
        "prefix_hit_pct": 32.0,
        "first_token_p50_ms": 800.0,
        "prefill_mfu_pct": 80.50961080527912,
        "paged_prefill_roofline": 4.990139371887217,
        "decode_host_ms": 5.915252999999954,
        "decode_mfu_pct": 0.08527486769971702,
        "paged_decode_roofline": 1.717371215202571,
        "sgmv_roofline.decode": 12.292696104937145,
        "device_idle_pct": 83.30362035897465,
        "prefill_live_rows_per_dispatch": 1.2222222222222223,
    },
}
CONFS = {"qwen": Path(__file__).parents[1] / "configs" / "qwen2_5_3b.json",
         "phi": FIXTURES / "phi3_medium_shape.json"}


def traced_run(counts):
    """A hand-made traced part: the decode call recorded on a TPU v5e
    (``fixtures/trace_small.json``), then a hand-made prefill round, and a
    log of three decode calls (two of them traced) and two admissions."""
    d = json.loads((FIXTURES / "trace_small.json").read_text())
    ev = lambda rows: [Ev(n, s, t) for n, s, t in rows]  # noqa: E731
    ops = ev(d["ops"]) + [Ev("paged_prefill_attention", 1.70, 0.05),
                          Ev("sgmv", 1.75, 0.004), Ev("fusion", 1.76, 0.02)]
    mods = ev(d["modules"]) + [Ev("jit_prefill_chunk(7)", 1.69, 0.10)]
    spans = ev(d["spans"]) + [Ev("harness.admit", 1.65, 0.25)]
    tr = reduce(Trace(ops, mods, spans, d["chips"]), (0.0, 2.0))
    recs = [rec(0, 0.1, [0.9, 1.2]), rec(1, 0.5, [1.9]), rec(2, 1.5, []),
            rec(3, 2.5, [3.0])]
    for r in recs:
        r.adapter = r.idx % 5
    decodes = [DecodeCall(0.0, 1.64, [900 + 37 * j for j in range(20)],
                          [j % 7 for j in range(20)],
                          [2 if j % 3 else 1 for j in range(20)]),
               DecodeCall(1.91, 1.99, [3000, 4100], [1, 1], [2, 2]),
               DecodeCall(2.5, 2.6, [10, 20], [0, 1], [2, 2])]
    admits = [(0.2, 0.3, [Admitted(300, 288, 1)]),
              (1.65, 1.9, [Admitted(1500, 512, 3), Admitted(700, 0, 5),
                           Admitted(2600, 2048, 2)])]
    s_open = {"prompt_tokens": 10000, "shared_tokens": 3000,
              "prefill_live_rows": 50, "prefill_chunks": 40}
    s_end = {"prompt_tokens": 12500, "shared_tokens": 3800,
             "prefill_live_rows": 61, "prefill_chunks": 49}
    s_close = {k: v + 100 for k, v in s_end.items()}
    lg = Log(recs, decodes, admits, [(1.92, 1.97)], s_open, s_close, s_end,
             0.0, 10.0, 2.0, 20, 18)
    return Run(lg, counts, PEAKS, 128, 2, 50.0, tr)


@pytest.mark.parametrize("arch", sorted(PARENT))
def test_every_per_layer_reader_reads_as_before(arch):
    conf = json.loads(CONFS[arch].read_text())
    run = traced_run(spec.reference_module(conf).counts_of(conf))
    names = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    assert sorted(names) == sorted(PARENT[arch])
    assert {n: spec.metric_reader(n).read(run) for n in names} \
        == PARENT[arch]
