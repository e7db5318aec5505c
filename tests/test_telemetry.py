"""Telemetry layer: metrics-registry semantics, span determinism under the
virtual clock, Chrome-trace export validity, TTFT/TPOT reconstruction from
spans alone, the runtime's span tree inside ``try_admit`` and ``decode()``
(in memory and on a profiler trace's host plane), and the two invariants
the runtime promises — recording never changes replay results (bitwise)
and, with no recorder attached, nothing is recorded at all."""
import dataclasses
import gc
import glob
import json

import numpy as np
import pytest

from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import (MetricsRegistry, ServeRequest, Telemetry,
                           replay_trace)
from repro.serving import telemetry as tm
from repro.serving.metrics import percentile

from conftest import FakeTimer, make_runtime

# legacy stats-dict keys every runtime must keep exposing (PR 2-5 scripts,
# benches and docs index them directly)
LEGACY_STATS_KEYS = (
    "prompt_tokens", "prefill_tokens", "recomputed_tokens", "shared_tokens",
    "shared_block_maps", "prefill_chunks", "rejected_too_long",
    "reclaimed_blocks")


def _workload(duration: float = 4.0, seed: int = 11):
    specs = [TraceSpec(f"fn{i}", "bursty", 1.5, duration, prompt_len=12,
                       output_len=8, slo_ttft=30.0) for i in range(3)]
    return make_workload(specs, seed=seed), {f"fn{i}": i for i in range(3)}


def _replay(cfg, params, *, telemetry=None, timer=None):
    kw = {"timer": timer} if timer is not None else {}
    rt = make_runtime(cfg, params, **kw)
    wl, fa = _workload()
    res, events = replay_trace(rt, [dict(w) for w in wl], fa, seed=3,
                               collect_events=True, slo_abandon=False,
                               telemetry=telemetry)
    return rt, res, events


# ----------------------------------------------------------- metrics unit
def test_percentile_interpolation():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert percentile(vals, 0.0) == 1.0
    assert percentile(vals, 1.0) == 4.0
    assert percentile(vals, 0.5) == pytest.approx(2.5)
    assert percentile([7.0], 0.99) == 7.0


def test_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    c = m.counter("reqs", "served requests")
    c.inc()
    c.inc(2)
    assert m.counter("reqs").value == 3        # get-or-create, same object
    g = m.gauge("pool", "free blocks")
    for v in (5.0, 2.0, 9.0):
        g.set(v)
    s = g.summary()
    assert (s["last"], s["min"], s["max"], s["samples"]) == (9.0, 2.0, 9.0, 3)
    h = m.histogram("lat", "latency")
    for v in range(1, 101):
        h.observe(float(v))
    hs = h.summary()
    assert hs["count"] == 100 and hs["min"] == 1.0 and hs["max"] == 100.0
    assert hs["p50"] == pytest.approx(50.5)
    assert hs["p99"] == pytest.approx(99.01)
    snap = m.snapshot()
    assert snap["counters"] == {"reqs": 3}
    assert snap["gauges"]["pool"]["mean"] == pytest.approx(16.0 / 3)
    assert snap["histograms"]["lat"]["p95"] == pytest.approx(95.05)


def test_counter_view_is_a_dict_over_the_registry():
    m = MetricsRegistry()
    m.counter("a").inc(4)
    view = m.counter_view()
    view["a"] += 1                       # legacy ``stats["a"] += 1`` idiom
    view["b"] = 7                        # setitem creates
    assert view["a"] == 5 and m.counter("a").value == 5
    assert m.counter("b").value == 7
    assert dict(view) == {"a": 5, "b": 7}
    with pytest.raises(KeyError):
        view["missing"]


# ------------------------------------------------ replay-level invariants
def test_legacy_stats_keys_still_present(llama_model):
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    for key in LEGACY_STATS_KEYS + ("decode_chunks", "stall_steps"):
        assert key in rt.stats, f"stats counter {key} vanished"
        assert rt.stats[key] == 0


def test_replay_bitwise_identical_with_and_without_telemetry(llama_model):
    """Attaching a recorder must not perturb replay: the runtime takes the
    identical timer-call sequence either way, so with a deterministic
    clock the SimResult (and event log) must match bit for bit."""
    cfg, params = llama_model
    _, res_off, ev_off = _replay(cfg, params, timer=FakeTimer())
    tele = Telemetry()
    _, res_on, ev_on = _replay(cfg, params, telemetry=tele,
                               timer=FakeTimer())
    assert [dataclasses.asdict(r) for r in res_off.requests] == \
           [dataclasses.asdict(r) for r in res_on.requests]
    assert [dataclasses.asdict(e) for e in ev_off] == \
           [dataclasses.asdict(e) for e in ev_on]
    assert tele.spans, "instrumented replay recorded no spans"


def test_span_sequence_deterministic(llama_model):
    cfg, params = llama_model
    runs = []
    for _ in range(2):
        tele = Telemetry()
        _replay(cfg, params, telemetry=tele, timer=FakeTimer())
        runs.append(tele)
    assert runs[0].span_sequence() == runs[1].span_sequence()
    # the runtime's own spans read the wall clock; the virtual-clock
    # stamps must match bit for bit
    virtual = [[dataclasses.asdict(s) for s in t.spans
                if s.track != tm.TRACK_RUNTIME] for t in runs]
    assert virtual[0] and virtual[0] == virtual[1]
    assert [dataclasses.asdict(i) for i in runs[0].instants] == \
           [dataclasses.asdict(i) for i in runs[1].instants]


def test_ttft_tpot_reconstructible_from_spans(llama_model):
    """Acceptance: the trace alone reconstructs EXACT per-request TTFT and
    TPOT — queued starts at arrival, prefill ends at first_token, the last
    decode span of a finished request ends at done."""
    cfg, params = llama_model
    tele = Telemetry()
    _, res, _ = _replay(cfg, params, telemetry=tele, timer=FakeTimer())
    queued = {s.args["req_id"]: s for s in tele.spans
              if s.name == tm.SPAN_QUEUED}
    prefill = {s.args["req_id"]: s for s in tele.spans
               if s.name == tm.SPAN_PREFILL}
    decodes = {}
    for s in tele.spans:
        if s.name == tm.SPAN_DECODE:
            decodes.setdefault(s.args["req_id"], []).append(s)
    served = [r for r in res.requests if r.first_token >= 0]
    assert served
    for r in served:
        assert queued[r.req_id].t0 == r.arrival
        assert queued[r.req_id].t1 == r.dispatch
        assert prefill[r.req_id].t1 == r.first_token
        ttft_spans = prefill[r.req_id].t1 - queued[r.req_id].t0
        assert ttft_spans == r.first_token - r.arrival
        if r.output_len > 1 and r.done >= 0:
            last = max(decodes[r.req_id], key=lambda s: s.t1)
            assert last.t1 == r.done
            tpot_spans = (last.t1 - prefill[r.req_id].t1) / \
                (r.output_len - 1)
            assert tpot_spans == pytest.approx(
                (r.done - r.first_token) / (r.output_len - 1))


def test_latency_histograms_match_simresult(llama_model):
    cfg, params = llama_model
    rt, res, _ = _replay(cfg, params, timer=FakeTimer())
    snap = rt.metrics_snapshot()
    served = [r for r in res.requests if r.first_token >= 0]
    h = snap["histograms"]
    assert h["ttft_s"]["count"] == len(served)
    assert h["ttft_s"]["mean"] == pytest.approx(res.mean_ttft)
    assert h["tpot_s"]["mean"] == pytest.approx(res.mean_tpot)
    c = snap["counters"]
    assert 0 < c["prefill_live_rows"] <= \
        c["prefill_chunks"] * rt.scfg.prefill_rows
    for gauge in ("pool_free_blocks", "pool_live_blocks",
                  "pool_cached_blocks", "pool_high_water_blocks",
                  "slots_active", "slot_utilization_frac",
                  "prefix_trie_blocks"):
        assert gauge in snap["gauges"], f"gauge {gauge} missing"
    for key in LEGACY_STATS_KEYS:
        assert key in snap["counters"]


def test_chrome_trace_valid_json_monotone_per_track(llama_model, tmp_path):
    cfg, params = llama_model
    tele = Telemetry()
    _replay(cfg, params, telemetry=tele, timer=FakeTimer())
    path = tmp_path / "trace.json"
    tele.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert tm.TRACK_QUEUE in names and tm.TRACK_HOST in names
    assert tm.TRACK_RUNTIME in names
    assert any(n.startswith("slot") for n in names)
    last_ts = {}
    for e in events:
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "M":
            continue
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        tid = e["tid"]
        assert e["ts"] >= last_ts.get(tid, -1.0), \
            f"ts not monotone on track {tid}"
        last_ts[tid] = e["ts"]


def test_no_recorder_records_nothing(llama_model):
    """The off path: with no recorder the runtime holds no span state and
    no ``gc`` hook, and a replay makes exactly the timer calls that a
    replay with a recorder makes."""
    cfg, params = llama_model
    hooks = len(gc.callbacks)
    calls = []
    for tele in (None, Telemetry()):
        timer = FakeTimer()
        rt = make_runtime(cfg, params, timer=timer)
        wl, fa = _workload()
        replay_trace(rt, [dict(w) for w in wl], fa, seed=3,
                     slo_abandon=False, telemetry=tele)
        assert rt.telemetry is None and len(gc.callbacks) == hooks
        calls.append(timer.calls)
    assert calls[0] == calls[1] > 0
    rt = make_runtime(cfg, params)
    assert rt.telemetry is None and rt._telemetry is None
    assert not any(isinstance(getattr(cb, "__self__", None), Telemetry)
                   for cb in gc.callbacks)


def test_gc_hook_follows_the_attachment(llama_model):
    """``rt.telemetry = tel`` installs the collection hook and ``None``
    removes it; a collection inside an open span nests under it."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    tele = Telemetry()
    rt.telemetry = tele
    assert tele._on_gc in gc.callbacks
    with tele.span(tm.SPAN_RT_DECODE):
        gc.collect(1)
    rt.telemetry = None
    assert tele._on_gc not in gc.callbacks
    gcs = [s for s in tele.spans if s.name == tm.SPAN_RT_GC]
    assert gcs and gcs[-1].args == {"generation": 1}
    assert tele.spans[gcs[-1].parent].name == tm.SPAN_RT_DECODE
    n = len(tele.spans)
    gc.collect()
    assert len(tele.spans) == n


def test_gc_hook_leaves_with_a_collected_runtime(llama_model):
    """A runtime dropped with its recorder still attached takes the
    collection hook with it."""
    cfg, params = llama_model
    hooks = list(gc.callbacks)
    tele = Telemetry()
    rt = make_runtime(cfg, params, telemetry=tele)
    assert tele._on_gc in gc.callbacks
    del rt
    gc.collect()
    assert gc.callbacks == hooks
    assert all(s.t1 >= s.t0 for s in tele.spans)


def test_failed_replay_gives_the_recorder_back(llama_model):
    """A replay that raises after attaching its recorder (here: a
    request with no prompt) still detaches it and its hook."""
    cfg, params = llama_model
    hooks = list(gc.callbacks)
    rt = make_runtime(cfg, params)
    wl, fa = _workload()
    with pytest.raises(ValueError):
        replay_trace(rt, [dict(w) for w in wl], fa, prompts={},
                     telemetry=Telemetry())
    assert rt.telemetry is None
    assert gc.callbacks == hooks


def _two_prompts(lens=(40, 20), seed=0):
    rng = np.random.default_rng(seed)
    return [ServeRequest(prompt=rng.integers(1, 200, size=n),
                         adapter=1 + i % 2, max_new_tokens=8)
            for i, n in enumerate(lens)]


def test_runtime_span_tree(llama_model):
    """One two-item ``try_admit`` and one ``decode()``: every runtime
    span name appears, each child lies inside its parent, and the parent
    indices point at the right span."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params, prefill_rows=4)
    tele = Telemetry()
    rt.telemetry = tele
    items = _two_prompts()
    res = rt.try_admit(items)
    assert res is not None and len(res.first_tokens) == 2
    assert rt.decode() is not None
    rt.telemetry = None
    spans = [s for s in tele.spans if s.name != tm.SPAN_RT_GC]
    assert all(s.track == tm.TRACK_RUNTIME for s in spans)
    names = {s.name for s in spans}
    assert names == set(tm.RUNTIME_SPANS) - {tm.SPAN_RT_GC}
    parent_of = {tm.SPAN_RT_ADMIT: None, tm.SPAN_RT_DECODE: None,
                 tm.SPAN_RT_ADMIT_PLAN: tm.SPAN_RT_ADMIT,
                 tm.SPAN_RT_PREFILL_ROUND: tm.SPAN_RT_ADMIT,
                 tm.SPAN_RT_ADMIT_SYNC: tm.SPAN_RT_ADMIT,
                 tm.SPAN_RT_ADMIT_BIND: tm.SPAN_RT_ADMIT,
                 tm.SPAN_RT_DECODE_PLAN: tm.SPAN_RT_DECODE,
                 tm.SPAN_RT_DECODE_STAGE: tm.SPAN_RT_DECODE,
                 tm.SPAN_RT_DECODE_SYNC: tm.SPAN_RT_DECODE,
                 tm.SPAN_RT_DECODE_EMIT: tm.SPAN_RT_DECODE}
    for s in tele.spans:
        assert s.t0 <= s.t1
        if s.name == tm.SPAN_RT_GC:
            continue
        if parent_of[s.name] is None:
            assert s.parent == -1
            continue
        p = tele.spans[s.parent]
        assert p.name == parent_of[s.name]
        assert p.t0 <= s.t0 and s.t1 <= p.t1
    # siblings in program order, one after another
    order = [s.name for s in spans]
    assert order == ([tm.SPAN_RT_ADMIT, tm.SPAN_RT_ADMIT_PLAN]
                     + [tm.SPAN_RT_PREFILL_ROUND] * 3
                     + [tm.SPAN_RT_ADMIT_SYNC, tm.SPAN_RT_ADMIT_BIND,
                        tm.SPAN_RT_DECODE, tm.SPAN_RT_DECODE_PLAN,
                        tm.SPAN_RT_DECODE_STAGE, tm.SPAN_RT_DECODE_SYNC,
                        tm.SPAN_RT_DECODE_EMIT])
    for i in (1, 2, 3, 4, 5, 8, 9, 10):          # consecutive siblings
        assert spans[i].t1 <= spans[i + 1].t0
    admit = spans[0]
    assert admit.args == {"req_ids": [it.request.req_id for it in items],
                          "admitted": 2}
    assert spans[7].args == {"rows": 2}


def test_prefill_live_rows_counts_real_rows(llama_model):
    """Prompts of 40 and 20 tokens at 16-token chunks into 4-row
    dispatches: three rounds with 2, 2 and 1 real rows."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params, prefill_rows=4)
    tele = Telemetry()
    rt.telemetry = tele
    assert rt.try_admit(_two_prompts()) is not None
    rt.telemetry = None
    rounds = [s.args for s in tele.spans
              if s.name == tm.SPAN_RT_PREFILL_ROUND]
    assert rounds == [{"round": 0, "live_rows": 2},
                      {"round": 1, "live_rows": 2},
                      {"round": 2, "live_rows": 1}]
    assert rt.stats["prefill_chunks"] == 3
    assert rt.stats["prefill_live_rows"] == 5
    # with no recorder the counter still counts
    rt2 = make_runtime(cfg, params, prefill_rows=4)
    assert rt2.try_admit(_two_prompts(lens=(20,))) is not None
    assert rt2.stats["prefill_chunks"] == 2
    assert rt2.stats["prefill_live_rows"] == 2


def test_profiler_trace_holds_the_runtime_spans(llama_model, tmp_path):
    """With ``profiler=True`` the spans land on a profiler trace's host
    plane, nested as they are in memory."""
    import jax
    from jax.profiler import ProfileData
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    assert rt.try_admit(_two_prompts()) is not None
    rt.decode()                                  # compiled before tracing
    rt.telemetry = Telemetry(profiler=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert rt.decode() is not None
    finally:
        jax.profiler.stop_trace()
        rt.telemetry = None
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert path
    pd = ProfileData.from_file(path[0])
    evs = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("runtime."):
                    evs[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    want = (tm.SPAN_RT_DECODE_PLAN, tm.SPAN_RT_DECODE_STAGE,
            tm.SPAN_RT_DECODE_SYNC, tm.SPAN_RT_DECODE_EMIT)
    assert set(want) | {tm.SPAN_RT_DECODE} <= set(evs)
    outer = evs[tm.SPAN_RT_DECODE]
    for name in want:
        assert outer[0] <= evs[name][0] and evs[name][1] <= outer[1]
