"""Overload robustness: deadline-aware shedding, abort/preempt accounting,
demote-to-cached resume, the deterministic fault-injection harness, and the
terminal-state conservation audit.

The anchor regressions: an EMPTY FaultPlan (and default infinite
deadlines) is token-bitwise identical to running without one, and a
preempted-then-resumed request recomputes strictly fewer prefill tokens
than a cold admission of the same prompt.
"""
import dataclasses

import numpy as np
import pytest

from repro.checkpoint.store import load_checkpoint, save_checkpoint
from repro.core.lora import random_adapter
from repro.serverless.batching import Request
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import (AdapterRegistry, ArtifactFault,
                           ArtifactLoadError, DispatchSlowdown, FaultPlan,
                           PoolSqueeze, RobustConfig, SamplingParams,
                           ServeRequest, replay_trace, retry_with_backoff,
                           terminal_state)

from conftest import FakeTimer, make_runtime

BS = 8


def _mk_rt(cfg, params, *, num_blocks=32, robust=None, timer=None):
    return make_runtime(cfg, params, block_size=BS, num_blocks=num_blocks,
                        robust=robust or RobustConfig(), timer=timer)


def _workload(duration=3.0, seed=5, output_len=8, rate=1.5, fns=3):
    specs = [TraceSpec(f"fn{i}", "bursty", rate, duration, prompt_len=12,
                       output_len=output_len, slo_ttft=1e9)
             for i in range(fns)]
    return make_workload(specs, seed=seed), {f"fn{i}": i for i in range(fns)}


# ------------------------------------------------------ retry primitive
def test_retry_with_backoff_recovers_and_bounds():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise ArtifactLoadError("transient")
        return "ok"

    assert retry_with_backoff(flaky, retries=2, backoff_s=0.1,
                              sleep=slept.append) == "ok"
    assert calls["n"] == 3
    assert slept == [0.1, 0.2]          # exponential: backoff * 2**attempt

    def always():
        raise ArtifactLoadError("permanent")

    with pytest.raises(ArtifactLoadError):
        retry_with_backoff(always, retries=2, sleep=slept.append)
    with pytest.raises(ValueError):
        retry_with_backoff(always, retries=-1)


# ----------------------------------------------------- terminal taxonomy
def test_terminal_state_classification():
    def req(**breakdown):
        r = Request(req_id=0, fn_id="f", arrival=0.0, prompt_len=4,
                    output_len=4, slo_ttft=1.0)
        r.breakdown.update(breakdown)
        return r

    assert terminal_state(req()) is None            # still in flight
    fin = req()
    fin.first_token, fin.done = 1.0, 2.0
    assert terminal_state(fin) == "finished"
    assert terminal_state(req(rejected_deadline=1.0)) == "rejected"
    assert terminal_state(req(abandoned=3.0)) == "abandoned"
    ab = req(aborted_oom=1.0)
    ab.first_token, ab.done = 1.0, 2.0
    assert terminal_state(ab) == "aborted"          # abort wins over done
    with pytest.raises(ValueError):
        terminal_state(req(aborted=1.0, rejected_deadline=1.0))


# --------------------------------------------- empty plan is a proven no-op
def test_empty_fault_plan_bitwise_identical(llama_model):
    cfg, params = llama_model

    def run(faults):
        rt = _mk_rt(cfg, params, timer=FakeTimer())
        wl, fa = _workload()
        sink = {}
        res, _ = replay_trace(rt, [dict(w) for w in wl], fa, seed=3,
                              slo_abandon=False, faults=faults,
                              token_sink=sink)
        return [dataclasses.asdict(r) for r in res.requests], sink

    base_reqs, base_toks = run(None)
    plan = FaultPlan()
    assert plan.empty()
    empty_reqs, empty_toks = run(plan)
    assert empty_toks == base_toks                  # token-bitwise
    assert empty_reqs == base_reqs                  # every timestamp too
    assert plan.report() == {"artifact_failures": 0, "pool_squeezes": 0,
                             "slowed_dispatches": 0}


# ------------------------------------------------------- deadline shedding
def test_deadline_shedding_provable_misses_only(llama_model):
    cfg, params = llama_model
    rt = _mk_rt(cfg, params, timer=FakeTimer())
    wl, fa = _workload(seed=9)
    # half the trace opts into an impossible TTFT deadline; the other half
    # keeps the infinite default and must be completely untouched
    doomed = {w["req_id"] for w in wl if w["req_id"] % 2 == 0}
    for w in wl:
        if w["req_id"] in doomed:
            w["deadline_ttft"] = 1e-9
    res, _ = replay_trace(rt, wl, fa, slo_abandon=False)
    shed = {r.req_id for r in res.requests
            if "rejected_deadline" in r.breakdown}
    assert shed == doomed
    assert rt.stats["rejected_deadline"] == len(doomed)
    for r in res.requests:
        if r.req_id not in doomed:
            assert terminal_state(r) == "finished"


# --------------------------------------------------------- abort account
def test_abort_releases_everything(llama_model):
    cfg, params = llama_model
    rt = _mk_rt(cfg, params)
    AdapterRegistry(rt, names=["a0", "a1", "a2"])
    rt.warmup()
    prompt = np.arange(12, dtype=np.int32) % cfg.vocab_size
    res = rt.try_admit([ServeRequest(prompt=prompt, adapter="a1",
                                     max_new_tokens=16, request=Request(
                                         req_id=7, fn_id="a1", arrival=0.0,
                                         prompt_len=12, output_len=16,
                                         slo_ttft=1e9))])
    assert res is not None and res.slot_ids[0] >= 0
    rt.decode()
    assert not rt.abort(999)                        # unknown id: no-op
    assert rt.abort(7)
    assert rt.slots.num_active == 0
    assert rt.pool.in_use == 0                      # demoted or freed
    assert rt.pool.num_cached > 0                   # completed KV parked
    assert rt.adapters.pin_counts() == {}           # pin released
    assert rt.stats["aborted"] == 1
    report = rt.check_invariants()
    assert report["problems"] == []


# --------------------------------------- preempt + cheap resume (bitwise)
def test_preempt_resume_bitwise_and_strictly_cheaper(llama_model):
    cfg, params = llama_model
    robust = RobustConfig(preemption=True)
    prompt = (np.arange(23, dtype=np.int32) * 5 + 1) % cfg.vocab_size
    out = 12

    def admit(rt, req):
        return rt.try_admit([ServeRequest(prompt=prompt, adapter=1,
                                          max_new_tokens=out, request=req)],
                            now=0.0)

    def drain(rt, res):
        toks = list(res.first_tokens)
        sid = res.slot_ids[0]
        while rt.slots.states[sid] is not None:
            toks.extend(rt.decode().emitted.get(sid, []))
        return toks

    # uninterrupted oracle
    rt1 = _mk_rt(cfg, params, robust=robust)
    rt1.warmup()
    ref = drain(rt1, admit(rt1, Request(req_id=0, fn_id="f", arrival=0.0,
                                        prompt_len=len(prompt),
                                        output_len=out, slo_ttft=1e9)))

    # preempt after two chunks, then resume through the prefix cache
    rt2 = _mk_rt(cfg, params, robust=robust)
    rt2.warmup()
    req = Request(req_id=0, fn_id="f", arrival=0.0, prompt_len=len(prompt),
                  output_len=out, slo_ttft=1e9)
    res = admit(rt2, req)
    sid = res.slot_ids[0]
    rt2.decode()
    rt2.decode()
    st = rt2.preempt(sid, now=1.0)
    assert st.req is req
    assert req.breakdown["preempted"] == 1.0
    assert rt2.stats["preemptions"] == 1
    assert rt2.slots.num_active == 0 and rt2.pool.in_use == 0
    assert rt2.pool.num_cached > 0                  # demoted, not freed
    assert rt2.stats["demoted_blocks"] > 0

    res2 = admit(rt2, req)
    assert res2 is not None
    assert res2.shared_blocks[0] > 0                # resume hit the cache
    assert rt2.stats["resume_prefix_hits"] == 1
    # strictly fewer prefill tokens than a cold admission of this prompt
    assert req.breakdown["resume_recomputed_tokens"] < len(prompt)
    assert req.breakdown["resumed_covered_tokens"] > 0
    assert drain(rt2, res2) == ref                  # greedy => bitwise
    rt2.check_invariants()


def test_preempt_resume_bitwise_with_sampling(llama_model):
    """The greedy preempt/resume guarantee extended to SAMPLED decode:
    the RNG counter is derived from tokens-produced (demoted with the
    slot's history, restored on re-admission), so a resumed request
    replays the identical key sequence — token-bitwise equal to the
    uninterrupted sampled run."""
    cfg, params = llama_model
    robust = RobustConfig(preemption=True)
    prompt = (np.arange(23, dtype=np.int32) * 5 + 1) % cfg.vocab_size
    out = 12
    sp = SamplingParams(temperature=0.9, top_k=20, seed=42)

    def admit(rt, req):
        return rt.try_admit([ServeRequest(prompt=prompt, adapter=1,
                                          max_new_tokens=out, request=req,
                                          sampling=sp)],
                            now=0.0)

    def drain(rt, res):
        toks = list(res.first_tokens)
        sid = res.slot_ids[0]
        while rt.slots.states[sid] is not None:
            toks.extend(rt.decode().emitted.get(sid, []))
        return toks

    # uninterrupted sampled oracle
    rt1 = _mk_rt(cfg, params, robust=robust)
    rt1.warmup()
    ref = drain(rt1, admit(rt1, Request(req_id=0, fn_id="f", arrival=0.0,
                                        prompt_len=len(prompt),
                                        output_len=out, slo_ttft=1e9)))
    assert len(ref) == out
    assert len(set(ref)) > 1, "sampled stream degenerate (all one token)"

    # preempt after two chunks, resume through the prefix cache
    rt2 = _mk_rt(cfg, params, robust=robust)
    rt2.warmup()
    req = Request(req_id=0, fn_id="f", arrival=0.0, prompt_len=len(prompt),
                  output_len=out, slo_ttft=1e9)
    res = admit(rt2, req)
    sid = res.slot_ids[0]
    rt2.decode()
    rt2.decode()
    produced_at_preempt = rt2.slots.states[sid].produced
    assert rt2.slots.rng_counter[sid] == produced_at_preempt
    st = rt2.preempt(sid, now=1.0)
    # the counter survives in the demoted SlotState (== produced); the
    # table mirror resets with the released slot
    assert st.produced == produced_at_preempt
    assert rt2.slots.rng_counter[sid] == 0

    res2 = admit(rt2, req)
    assert res2 is not None
    assert res2.shared_blocks[0] > 0                # resume hit the cache
    sid2 = res2.slot_ids[0]
    # re-bound mirror picks the stream back up at tokens-produced
    assert rt2.slots.rng_counter[sid2] == rt2.slots.states[sid2].produced
    assert drain(rt2, res2) == ref, \
        "resumed sampled stream diverged from the uninterrupted run"
    rt2.check_invariants()


# ------------------------------------- force-evict: one victim, bitwise
def test_all_stall_force_evict_single_victim_bitwise(llama_model):
    cfg, params = llama_model
    wl, fa = _workload(duration=2.0, seed=2, output_len=16, rate=2.0,
                       fns=1)

    def run(num_blocks):
        rt = _mk_rt(cfg, params, num_blocks=num_blocks, timer=FakeTimer())
        sink = {}
        res, _ = replay_trace(rt, [dict(w) for w in wl], fa,
                              slo_abandon=False, token_sink=sink)
        return rt, res, sink

    rt_small, res_small, sink_small = run(8)        # starved: must evict
    rt_ample, _, sink_ample = run(32)               # oracle: nobody dies
    evicted = [r for r in res_small.requests
               if "aborted_oom" in r.breakdown]
    assert evicted, "starved pool never force-evicted"
    assert rt_small.stats["aborted"] == len(evicted)
    survivors = [r for r in res_small.requests
                 if terminal_state(r) == "finished"]
    assert survivors, "force-evict starved everyone (livelock proxy)"
    for r in survivors:                             # bitwise vs ample pool
        assert sink_small[r.req_id] == sink_ample[r.req_id]
    assert rt_small.pool.in_use == 0 and rt_small.slots.num_active == 0


# ------------------------------- preemption under overload, retry budget
def test_preemption_replay_conserves_and_retries(llama_model):
    cfg, params = llama_model
    wl, fa = _workload(duration=2.0, seed=2, output_len=16, rate=2.0,
                       fns=1)
    robust = RobustConfig(preemption=True, retry_budget=2, backoff_s=0.01)
    rt = _mk_rt(cfg, params, num_blocks=8, robust=robust,
                timer=FakeTimer())
    res, _ = replay_trace(rt, [dict(w) for w in wl], fa, slo_abandon=False)
    assert rt.stats["preemptions"] > 0
    assert not any("aborted_oom" in r.breakdown for r in res.requests)
    states = {r.req_id: terminal_state(r) for r in res.requests}
    assert set(states.values()) <= {"finished", "abandoned"}
    retried = [r for r in res.requests if r.breakdown.get("preempted")]
    assert retried, "preemption fired but nothing was requeued"
    # a preempted request either finished on a later attempt or ran out of
    # retry budget — both are terminal, nothing is lost
    for r in retried:
        if "abandoned_retries" in r.breakdown:
            assert r.breakdown["preempted"] > robust.retry_budget


# ----------------------------------------------- fault plan: pool + time
def test_pool_squeeze_and_slowdown_inject_deterministically(llama_model):
    cfg, params = llama_model
    wl, fa = _workload(duration=2.0, seed=4)

    def run(faults):
        rt = _mk_rt(cfg, params, timer=FakeTimer())
        sink = {}
        replay_trace(rt, [dict(w) for w in wl], fa, slo_abandon=False,
                     faults=faults, token_sink=sink)
        return rt, sink

    _, base_sink = run(None)
    plan = FaultPlan(
        pool_squeezes=[PoolSqueeze(t0=0.0, t1=1.0, blocks=6)],
        slowdowns=[DispatchSlowdown(t0=0.0, t1=1e9, factor=4.0)])
    rt, sink = run(plan)
    rep = plan.report()
    assert rep["pool_squeezes"] == 1
    assert rep["slowed_dispatches"] > 0
    assert rt.stats["injected_pool_squeezes"] == 1
    assert rt.pool.in_use == 0                      # squeeze released
    # neither fault touches device results: tokens stay bitwise identical
    assert sink == base_sink


# --------------------------------------------- artifact faults + retries
def test_adapter_load_retries_then_rolls_back(llama_model):
    cfg, params = llama_model
    rt = _mk_rt(cfg, params)          # robust.artifact_retries = 2
    reg = AdapterRegistry(rt, names=["a0"])
    tree = random_adapter(params, 1)

    rt.faults = FaultPlan(artifact_faults=[
        ArtifactFault("adapter", name="flaky", fails=2)])
    reg.load("flaky", tree)                         # 2 fails < 2 retries+1
    assert rt.stats["artifact_retries"] == 2
    assert "flaky" in reg.names()

    rt.faults = FaultPlan(artifact_faults=[
        ArtifactFault("adapter", name="cursed", fails=99)])
    with pytest.raises(ArtifactLoadError):
        reg.load("cursed", tree)
    assert "cursed" not in reg.names()              # rollback: name unbound
    rt.faults = None
    reg.load("recovered", tree)                     # freed slot is reusable
    assert "recovered" in reg.names()


def test_checkpoint_load_retries_through_fault_hook(llama_model, tmp_path):
    cfg, params = llama_model
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, {"w": np.arange(4, dtype=np.float32)},
                    meta={"k": 1})
    plan = FaultPlan(artifact_faults=[ArtifactFault("checkpoint", fails=1)])
    loaded, meta = load_checkpoint(path, retries=1,
                                   fault_hook=plan.artifact_check)
    assert meta == {"k": 1}
    np.testing.assert_array_equal(np.asarray(loaded["w"]),
                                  np.arange(4, dtype=np.float32))
    assert plan.artifact_faults[0].injected == 1
    plan2 = FaultPlan(artifact_faults=[ArtifactFault("checkpoint",
                                                     fails=5)])
    with pytest.raises(ArtifactLoadError):
        load_checkpoint(path, retries=1, fault_hook=plan2.artifact_check)


# ----------------------------------------------------- invariant auditor
def test_check_invariants_detects_pin_leak(llama_model):
    cfg, params = llama_model
    rt = _mk_rt(cfg, params)
    reg = AdapterRegistry(rt, names=["a0", "a1", "a2"])
    rt.warmup()
    prompt = np.arange(12, dtype=np.int32) % cfg.vocab_size
    res = rt.try_admit([ServeRequest(prompt=prompt, adapter="a2",
                                     max_new_tokens=8)])
    assert res is not None
    assert rt.check_invariants()["problems"] == []
    reg.pin(0)                                      # leak a pin on purpose
    report = rt.check_invariants(raise_on_error=False)
    assert any("pin" in p for p in report["problems"])
    with pytest.raises(AssertionError):
        rt.check_invariants()
    reg.unpin(0)
    while rt.slots.num_active:
        rt.decode()
    assert rt.check_invariants()["problems"] == []
