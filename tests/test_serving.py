"""Continuous-batching serving subsystem: block-pool invariants, paged ==
contiguous decode equivalence, slot-wise insert/extract roundtrip, and an
end-to-end trace replay (every admitted request finishes, slots and blocks
are fully reclaimed, decode never re-jits)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.engine import (make_extract_fn, make_insert_fn,
                               make_prefill_step, make_serve_step)
from repro.models import transformer as tf
from repro.models.cache import (GARBAGE_BLOCK, init_paged_cache,
                                paging_unsupported_reason)
from repro.serverless.batching import Request
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import (BlockPool, CompileGuard, ServeRequest,
                           blocks_for_tokens, replay_trace)

from conftest import make_runtime


def _sr(req, prompt, adapter):
    return ServeRequest(prompt=prompt, adapter=adapter, request=req)



# ------------------------------------------------------------- block pool
def test_block_pool_alloc_free_invariants():
    pool = BlockPool(num_blocks=8, block_size=4)
    assert pool.available == 7            # block 0 reserved for garbage
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert GARBAGE_BLOCK not in a + b
    assert len(set(a + b)) == 5           # all distinct
    assert pool.in_use == 5 and pool.available == 2
    assert pool.alloc(3) is None          # all-or-nothing
    assert pool.in_use == 5               # failed alloc left no residue
    pool.free(b)
    assert pool.in_use == 3 and pool.available == 4
    with pytest.raises(KeyError):
        pool.free(b)                      # double-free is a bug
    with pytest.raises(KeyError):
        pool.free([GARBAGE_BLOCK])        # garbage block is never allocated
    pool.free(a)
    assert pool.in_use == 0 and pool.available == 7


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 8) == 0
    assert blocks_for_tokens(1, 8) == 1
    assert blocks_for_tokens(8, 8) == 1
    assert blocks_for_tokens(9, 8) == 2


def test_block_pool_free_is_atomic():
    """A rejected free (foreign/double-freed/duplicate id) must leave the
    pool exactly as it was — no partial mutation for callers that catch."""
    pool = BlockPool(num_blocks=8, block_size=4)
    a = pool.alloc(3)
    snap = (list(pool._free), dict(pool._ref))
    with pytest.raises(KeyError):
        pool.free([a[0], a[1], 99])       # valid prefix + foreign id
    assert (list(pool._free), dict(pool._ref)) == snap
    with pytest.raises(KeyError):
        pool.free([a[0], a[0]])           # duplicate in one call
    assert (list(pool._free), dict(pool._ref)) == snap
    pool.free(a)                          # the valid free still works
    assert pool.in_use == 0 and pool.available == 7


def test_paging_support_matrix_over_all_configs():
    """Every registered config is either servable by the paged runtime or
    rejected with a reason naming WHY.  Since hybrid/attention-free stacks
    grew per-slot state rows, only encoder/cross-attention models remain
    out (their encoder K/V is keyed to frame embeddings the replay does
    not carry)."""
    from repro.configs import ARCH_IDS
    from repro.models.cache import has_slot_state

    rejected = {"whisper_medium"}         # encoder-decoder audio
    needs_state = {"recurrentgemma_9b", "mamba2_780m"}
    for arch in ARCH_IDS:
        cfg = get_smoke(arch)
        reason = paging_unsupported_reason(cfg)
        if arch in rejected:
            assert reason is not None and "encoder" in reason, (arch, reason)
            with pytest.raises(ValueError):
                init_paged_cache(cfg, 8, 4, num_slots=2)
        else:
            assert reason is None, (arch, reason)
        assert has_slot_state(cfg) == (arch in needs_state), arch
    # REC/SSD state rows are sized by num_slots: forgetting it must be a
    # loud error, not a silently stateless cache
    with pytest.raises(ValueError):
        init_paged_cache(get_smoke("mamba2_780m"), 8, 4)
    # sliding-window configs are servable: the paged decode masks the
    # window in-kernel (block reclamation is an optimization, not a gate)
    swa = get_smoke("llama2_7b").with_(sliding_window=8)
    assert paging_unsupported_reason(swa) is None


# ---------------------------------------------------- paged == contiguous
def test_paged_decode_matches_contiguous(llama_model):
    """The gather-based paged decode must reproduce the ring-cache decode
    logits bit-for-bit (same math, different K/V layout)."""
    cfg, params = llama_model
    B, T, steps, bs = 2, 8, 6, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    ai = jnp.array([1, 2], jnp.int32)
    prefill, serve = make_prefill_step(cfg), make_serve_step(cfg)

    cache = tf.init_cache(cfg, B, 32)
    logits, cache = prefill(params, toks, cache, adapter_idx=ai)
    ref = [logits]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for s in range(steps):
        lg, cache = serve(params, tok, cache, jnp.array(T + s, jnp.int32),
                          adapter_idx=ai)
        ref.append(lg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)

    pool = init_paged_cache(cfg, 16, bs)
    pre = tf.init_cache(cfg, B, T)
    lg2, pre = prefill(params, toks, pre, adapter_idx=ai,
                       last_pos=jnp.full((B,), T - 1, jnp.int32))
    np.testing.assert_allclose(ref[0], lg2, atol=1e-5)
    pool = jax.jit(make_insert_fn(cfg, bs))(
        pool, pre, jnp.array([[1, 2], [3, 4]], jnp.int32))
    tbl = np.full((B, 8), -1, np.int32)
    tbl[0, :4] = [1, 2, 5, 7]
    tbl[1, :4] = [3, 4, 6, 8]
    tbl = jnp.asarray(tbl)
    tok2 = jnp.argmax(lg2, -1).astype(jnp.int32)
    pos = jnp.full((B,), T, jnp.int32)
    for s in range(steps):
        lg, pool = serve(params, tok2, pool, pos, adapter_idx=ai,
                         block_tbl=tbl)
        np.testing.assert_allclose(ref[s + 1], lg, atol=1e-5)
        tok2 = jnp.argmax(lg, -1).astype(jnp.int32)
        pos = pos + 1


def test_paged_kernel_matches_gather_and_contiguous(llama_model):
    """In-kernel block-table walk == gather reference == contiguous ring
    decode, across ragged per-row positions and an inactive (-1) row."""
    cfg, params = llama_model
    B, T, steps, bs = 2, 8, 4, 4
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0,
                              cfg.vocab_size)
    ai = jnp.array([1, 0], jnp.int32)
    prefill, serve = make_prefill_step(cfg), make_serve_step(cfg)

    cache = tf.init_cache(cfg, B, 32)
    logits, cache = prefill(params, toks, cache, adapter_idx=ai)
    ref = [logits]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for s in range(steps):
        lg, cache = serve(params, tok, cache, jnp.array(T + s, jnp.int32),
                          adapter_idx=ai)
        ref.append(lg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)

    def paged_run(use_kernel):
        pool = init_paged_cache(cfg, 16, bs)
        pre = tf.init_cache(cfg, B, T)
        lg2, pre = prefill(params, toks, pre, adapter_idx=ai,
                           last_pos=jnp.full((B,), T - 1, jnp.int32))
        pool = jax.jit(make_insert_fn(cfg, bs))(
            pool, pre, jnp.array([[1, 2], [3, 4]], jnp.int32))
        tbl = np.full((B + 1, 8), -1, np.int32)   # extra row: inactive slot
        tbl[0, :4] = [1, 2, 5, 7]
        tbl[1, :4] = [3, 4, 6, 8]
        tbl = jnp.asarray(tbl)
        tok2 = jnp.argmax(lg2, -1).astype(jnp.int32)
        tok3 = jnp.concatenate([tok2, jnp.zeros((1,), jnp.int32)])
        ai3 = jnp.concatenate([ai, jnp.zeros((1,), jnp.int32)])
        pos = jnp.array([T, T, 0], jnp.int32)
        outs = []
        for s in range(steps):
            lg, pool = serve(params, tok3, pool, pos, adapter_idx=ai3,
                             block_tbl=tbl, use_paged_kernel=use_kernel)
            outs.append(lg[:B])
            tok3 = jnp.argmax(lg, -1).astype(jnp.int32)
            # live rows advance at their own depth; inactive row stays put
            pos = pos + jnp.array([1, 1, 0], jnp.int32)
        return outs

    gather, kernel = paged_run(False), paged_run(True)
    for s in range(steps):
        np.testing.assert_allclose(ref[s + 1], gather[s], atol=1e-5)
        np.testing.assert_allclose(ref[s + 1], kernel[s], atol=1e-5)
        np.testing.assert_allclose(gather[s], kernel[s], atol=1e-5)


def test_insert_extract_roundtrip(llama_model):
    cfg, params = llama_model
    B, T, bs = 2, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                              cfg.vocab_size)
    prefill = make_prefill_step(cfg)
    pre = tf.init_cache(cfg, B, T)
    _, pre = prefill(params, toks, pre,
                     adapter_idx=jnp.zeros((B,), jnp.int32))
    pool = init_paged_cache(cfg, 16, bs)
    ids = jnp.array([[1, 2], [3, 4]], jnp.int32)
    pool = jax.jit(make_insert_fn(cfg, bs))(pool, pre, ids)
    extract = jax.jit(make_extract_fn(cfg, bs))
    for row in range(B):
        ext = extract(pool, ids[row])
        for pj in pre["periods"]:
            np.testing.assert_array_equal(
                np.asarray(ext["periods"][pj]["k"]),
                np.asarray(pre["periods"][pj]["k"][:, row]))
            np.testing.assert_array_equal(
                np.asarray(ext["periods"][pj]["v"]),
                np.asarray(pre["periods"][pj]["v"][:, row]))


# ------------------------------------------------------------- end-to-end
def test_mid_flight_join_and_leave(llama_model):
    """A request joins while another is mid-decode; both finish; all blocks
    and slots are reclaimed."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    rng = np.random.default_rng(0)

    def req(rid, out):
        return Request(req_id=rid, fn_id="fn0", arrival=0.0, prompt_len=12,
                       output_len=out, slo_ttft=10.0)

    r0 = rt.try_admit([_sr(req(0, 12), rng.integers(0, 512, 12,
                                                 dtype=np.int32), 0)])
    assert r0 is not None and rt.slots.num_active == 1
    first = rt.decode()
    assert first is not None and len(first.emitted[r0.slot_ids[0]]) == 4
    # join mid-decode
    r1 = rt.try_admit([_sr(req(1, 6), rng.integers(0, 512, 12,
                                                dtype=np.int32), 1)])
    assert r1 is not None and rt.slots.num_active == 2
    produced = {0: 1 + 4, 1: 1}
    for _ in range(10):
        res = rt.decode()
        if res is None:
            break
        for sid, toks in res.emitted.items():
            rid = 0 if sid == r0.slot_ids[0] else 1
            produced[rid] += len(toks)
    assert produced == {0: 12, 1: 6}
    assert rt.slots.num_active == 0
    assert rt.pool.in_use == 0


def test_replay_trace_end_to_end(llama_model):
    """Bursty 3-adapter trace through the real engine: every admitted
    request gets first_token set, slots/blocks fully reclaimed, and the
    decode step compiled exactly once after warmup."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    specs = [TraceSpec(f"fn{i}", "bursty", 1.5, 8.0, prompt_len=12,
                       output_len=8, slo_ttft=5.0) for i in range(3)]
    wl = make_workload(specs, seed=11)
    assert len(wl) > 10
    # CompileGuard raises CompileBudgetExceeded on __exit__ if either
    # jitted step compiled more than once across the whole replay
    # (warmup included).
    with CompileGuard({"decode": 1, "prefill": 1}, runtime=rt):
        res, events = replay_trace(rt, wl, {f"fn{i}": i for i in range(3)},
                                   collect_events=True)
    served = [r for r in res.requests if r.first_token >= 0]
    assert served, "nothing served"
    for r in served:
        assert r.dispatch >= r.arrival
        assert r.first_token >= r.dispatch
        assert r.done >= r.first_token
    # abandoned requests (if any) are marked, not silently dropped
    for r in res.requests:
        if r.first_token < 0:
            assert "abandoned" in r.breakdown
    assert rt.slots.num_active == 0, "slots leaked"
    assert rt.pool.in_use == 0, "KV blocks leaked"
    # counter symmetry: decode dispatches are counted like prefill ones,
    # and the stall counter exists even when the pool never ran dry
    assert rt.stats["decode_chunks"] > 0
    assert rt.stats["prefill_chunks"] > 0
    assert rt.stats["stall_steps"] >= 0
    kinds = {e.kind for e in events}
    assert "admit" in kinds and "finish" in kinds


@pytest.mark.parametrize("window", [None, 8])
def test_decode_kv_blocks_counts_the_bounded_walk(llama_model, window):
    """``decode_kv_blocks`` adds, per decode dispatch, the blocks the
    paged-decode kernel walks: for each running row and scan step at
    position p, blocks ``max(0, p - window + 1) // bs`` (0 without a
    window) through ``p // bs``; the free slot adds nothing, and blocks
    reclaimed behind the window are never in the walk."""
    cfg, params = llama_model
    if window is not None:
        cfg = cfg.with_(sliding_window=window)
    bs, chunk, n = 8, 4, 3
    rt = make_runtime(cfg, params, num_slots=3, block_size=bs,
                      decode_chunk=chunk)
    lens = (12, 19)
    rng = np.random.default_rng(0)
    res = rt.try_admit([ServeRequest(
        prompt=rng.integers(1, cfg.vocab_size, L).astype(np.int32),
        max_new_tokens=24) for L in lens])
    assert sorted(rt.slots.pos[res.slot_ids].tolist()) == sorted(lens)
    assert rt.slots.num_active == 2             # one slot stays free
    expected = 0
    for d in range(n):
        for L in lens:
            for p in range(L + d * chunk, L + (d + 1) * chunk):
                first = 0 if window is None else max(0, p - window + 1) // bs
                expected += p // bs - first + 1
        rt.decode()
    assert rt.stats["decode_chunks"] == n
    assert rt.stats["decode_kv_blocks"] == expected
    if window is not None:
        assert rt.stats["reclaimed_blocks"] > 0


def test_oversized_request_rejected_gracefully(llama_model):
    """An oversized request mid-trace must not kill the replay: it is
    counted (stats + breakdown flag), reported failed, and every other
    request is still served (the old path raised ValueError)."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    specs = [TraceSpec("fn0", "bursty", 2.0, 6.0, prompt_len=12,
                       output_len=6, slo_ttft=30.0)]
    wl = make_workload(specs, seed=3)
    assert len(wl) >= 3
    big = wl[1]["req_id"]
    wl[1]["prompt_len"] = 80            # 80 + 6 - 1 > 6 * 8 slot capacity
    res, events = replay_trace(rt, wl, {"fn0": 0}, slo_abandon=False,
                               collect_events=True)
    assert rt.stats["rejected_too_long"] == 1
    rej = [r for r in res.requests if r.req_id == big][0]
    assert rej.first_token < 0 and rej.breakdown["rejected_too_long"] == 1.0
    served = [r for r in res.requests if r.first_token >= 0]
    assert len(served) == len(wl) - 1, "healthy requests were dropped too"
    assert any(e.kind == "reject" and e.req_id == big for e in events)
    assert rt.slots.num_active == 0 and rt.pool.in_use == 0


def test_try_admit_mixed_group_rejects_only_oversized(llama_model):
    """Direct try_admit with a fit + an oversized item: the oversized one
    lands in AdmitResult.rejected (counted once, idempotently), the fit
    one is admitted, and the per-item lists align with the survivors."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    rng = np.random.default_rng(2)
    ok = Request(req_id=0, fn_id="fn0", arrival=0.0, prompt_len=12,
                 output_len=6, slo_ttft=10.0)
    big = Request(req_id=1, fn_id="fn0", arrival=0.0, prompt_len=80,
                  output_len=6, slo_ttft=10.0)
    res = rt.try_admit([
        _sr(ok, rng.integers(0, 512, 12, dtype=np.int32), 0),
        _sr(big, rng.integers(0, 512, 80, dtype=np.int32), 0)])
    assert [r.req_id for r in res.rejected] == [1]
    assert len(res.slot_ids) == 1 and res.slot_ids[0] >= 0
    assert rt.stats["rejected_too_long"] == 1
    rt.reject_too_long(big)              # idempotent: no double count
    assert rt.stats["rejected_too_long"] == 1
    # an all-oversized group admits nothing but still reports the drops
    res2 = rt.try_admit([_sr(big, rng.integers(0, 512, 80,
                                            dtype=np.int32), 0)])
    assert res2.slot_ids == [] and [r.req_id for r in res2.rejected] == [1]
    for _ in range(6):
        if rt.decode() is None:
            break
    assert rt.slots.num_active == 0 and rt.pool.in_use == 0


def test_prompt_longer_than_chunk_and_any_bucket(llama_model):
    """Prompt length is capped by the block table, not a bucket set: a
    40-token prompt (chunk 16 -> 3 chunk dispatches, longer than the old
    largest bucket 32) is served with ONE prefill compile."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 512, 40, dtype=np.int32)
    req = Request(req_id=0, fn_id="fn0", arrival=0.0, prompt_len=40,
                  output_len=6, slo_ttft=10.0)
    with CompileGuard({"prefill": 1}, runtime=rt):
        res = rt.try_admit([_sr(req, prompt, 0)])
        assert res is not None and res.slot_ids[0] >= 0
        assert rt.stats["prefill_chunks"] == 3
        produced = 1
        for _ in range(6):
            d = rt.decode()
            if d is None:
                break
            produced += sum(len(t) for t in d.emitted.values())
    assert produced == 6
    assert rt.slots.num_active == 0 and rt.pool.in_use == 0


def test_stall_does_not_corrupt_output(llama_model):
    """A slot that stalls on pool exhaustion must, after resuming, emit
    exactly the tokens it would have emitted with an ample pool (the stall
    chunk's KV writes must be invisible)."""
    cfg, params = llama_model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, 8, dtype=np.int32) for _ in range(2)]

    def run(num_blocks):
        rt = make_runtime(cfg, params, num_slots=2, block_size=4,
                          num_blocks=num_blocks, max_blocks_per_slot=4,
                          prefill_chunk=8, decode_chunk=4)
        reqs = [Request(req_id=i, fn_id="fn0", arrival=0.0, prompt_len=8,
                        output_len=9, slo_ttft=10.0) for i in range(2)]
        res = rt.try_admit([_sr(reqs[i], prompts[i], i) for i in range(2)])
        out = {sid: [tok] for sid, tok in
               zip(res.slot_ids, res.first_tokens)}
        stalls = 0
        for _ in range(12):
            d = rt.decode()
            if d is None:
                break
            stalls += len(d.stalled)
            for sid, toks in d.emitted.items():
                out[sid].extend(toks)
        assert rt.pool.in_use == 0
        assert rt.stats["stall_steps"] == stalls, \
            "stall_steps counter disagrees with DecodeResult.stalled"
        return out, stalls

    # prompt 8 -> 3 blocks each at admit; budget 9 -> 4 blocks each.
    # 8 blocks (7 usable) forces one slot to stall for the 4th block until
    # the other finishes; 32 blocks never stalls.
    tight, tight_stalls = run(8)
    ample, ample_stalls = run(32)
    assert tight_stalls > 0, "scenario no longer exercises the stall path"
    assert ample_stalls == 0
    assert tight == ample, "stall chunk leaked state into the output"


def test_admit_prefill_finish_reports_unbound_slot(llama_model):
    """A request that finishes at prefill (output_len == 1) is never bound
    to a slot; AdmitResult must say -1, not a phantom free slot id."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    rng = np.random.default_rng(1)
    reqs = [Request(req_id=i, fn_id="fn0", arrival=0.0, prompt_len=12,
                    output_len=o, slo_ttft=10.0)
            for i, o in enumerate((1, 6))]
    res = rt.try_admit([_sr(r, rng.integers(0, 512, 12, dtype=np.int32), 0)
                        for r in reqs])
    assert res.slot_ids[0] == -1          # finished at prefill, unbound
    assert res.slot_ids[1] >= 0           # the live one got a real slot
    assert [st.req.req_id for st in res.finished] == [0]
    assert res.finished[0].sid == -1
    assert rt.slots.num_active == 1
    assert rt.slots.states[res.slot_ids[1]].req.req_id == 1
    # drain; everything reclaimed
    for _ in range(6):
        if rt.decode() is None:
            break
    assert rt.slots.num_active == 0 and rt.pool.in_use == 0


def test_replay_finish_never_predates_dispatch(llama_model):
    """Chunks clipped by budget/EOS: the finishing token is stamped at the
    end of the decode dispatch that produced it, so ``done`` can never
    precede the dispatch and TPOT can never go negative."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    # output 6 with decode_chunk 4: the finishing chunk accepts 2 of 4
    specs = [TraceSpec("fn0", "normal", 2.0, 4.0, prompt_len=12,
                       output_len=6, slo_ttft=30.0)]
    wl = make_workload(specs, seed=5)
    assert any(w["output_len"] % rt.scfg.decode_chunk for w in wl)
    res, events = replay_trace(rt, wl, {"fn0": 0}, slo_abandon=False,
                               collect_events=True)
    fin = {e.req_id: e for e in events if e.kind == "finish"}
    served = [r for r in res.requests if r.first_token >= 0]
    assert served and fin
    for r in served:
        ev = fin[r.req_id]
        # the finish event is logged at the end of the producing dispatch
        assert r.done >= ev.t - 1e-9, (r.req_id, r.done, ev.t)
        assert abs(r.done - ev.t) < 1e-9
        assert r.done >= r.first_token
        if r.output_len > 1:
            assert r.done > r.first_token   # TPOT strictly positive


def test_sliding_window_served_end_to_end(llama_model):
    """A sliding-window config round-trips through the paged runtime with
    the in-kernel window mask, and matches the gather reference path."""
    cfg, params = llama_model
    swa = cfg.with_(sliding_window=8)

    def run(use_kernel):
        rt = make_runtime(swa, params, use_kernel=use_kernel)
        specs = [TraceSpec("fn0", "bursty", 2.0, 4.0, prompt_len=12,
                           output_len=8, slo_ttft=30.0)]
        wl = make_workload(specs, seed=5)
        with CompileGuard({"decode": 1}, runtime=rt):
            res, _ = replay_trace(rt, wl, {"fn0": 0}, slo_abandon=False)
        assert rt.slots.num_active == 0 and rt.pool.in_use == 0
        served = [r for r in res.requests if r.first_token >= 0]
        assert served, "sliding-window trace served nothing"
        return res

    run(True)
    run(False)


def test_sliding_window_paged_matches_contiguous(llama_model):
    """Windowed paged decode (all blocks retained, window masked in-kernel)
    == the contiguous ring cache that physically evicts old positions."""
    cfg, params = llama_model
    swa = cfg.with_(sliding_window=8)
    B, T, steps, bs = 2, 8, 6, 4
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, T), 0,
                              swa.vocab_size)
    ai = jnp.array([0, 2], jnp.int32)
    prefill, serve = make_prefill_step(swa), make_serve_step(swa)

    # contiguous: ring buffer of window length (the SWA memory win)
    cache = tf.init_cache(swa, B, 32)
    logits, cache = prefill(params, toks, cache, adapter_idx=ai)
    ref = [logits]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for s in range(steps):
        lg, cache = serve(params, tok, cache, jnp.array(T + s, jnp.int32),
                          adapter_idx=ai)
        ref.append(lg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)

    for use_kernel in (False, True):
        pool = init_paged_cache(swa, 16, bs)
        pre = tf.init_cache(swa, B, T, clamp_window=False)
        lg2, pre = prefill(params, toks, pre, adapter_idx=ai,
                           last_pos=jnp.full((B,), T - 1, jnp.int32))
        np.testing.assert_allclose(ref[0], lg2, atol=1e-5)
        pool = jax.jit(make_insert_fn(swa, bs))(
            pool, pre, jnp.array([[1, 2], [3, 4]], jnp.int32))
        tbl = np.full((B, 8), -1, np.int32)
        tbl[0, :4] = [1, 2, 5, 7]
        tbl[1, :4] = [3, 4, 6, 8]
        tbl = jnp.asarray(tbl)
        tok2 = jnp.argmax(lg2, -1).astype(jnp.int32)
        pos = jnp.full((B,), T, jnp.int32)
        for s in range(steps):
            lg, pool = serve(params, tok2, pool, pos, adapter_idx=ai,
                             block_tbl=tbl, use_paged_kernel=use_kernel)
            np.testing.assert_allclose(ref[s + 1], lg, atol=1e-5,
                                       err_msg=f"step {s} kernel="
                                               f"{use_kernel}")
            tok2 = jnp.argmax(lg, -1).astype(jnp.int32)
            pos = pos + 1


def test_pool_exhaustion_progress(llama_model):
    """A pool too small for the full working set stalls/aborts but never
    livelocks, and still reclaims every block."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params, num_blocks=8)
    specs = [TraceSpec("fn0", "bursty", 4.0, 3.0, prompt_len=12,
                       output_len=16, slo_ttft=30.0)]
    wl = make_workload(specs, seed=2)
    res, _ = replay_trace(rt, wl, {"fn0": 0}, slo_abandon=False)
    assert rt.pool.in_use == 0
    assert rt.slots.num_active == 0
    done = [r for r in res.requests if r.done >= 0]
    assert done, "no request ever completed"


def test_lower_dispatch_is_the_served_program(llama_model):
    """``lower_dispatch`` lowers decode and prefill at the one serving
    shape without compiling or running them: warmup afterwards still
    compiles each exactly once.  Off the TPU neither program calls a
    Pallas kernel (the jnp walk and the SGMV reference serve there)."""
    cfg, params = llama_model
    rt = make_runtime(cfg, params)
    for kind in ("decode", "prefill"):
        text = rt.lower_dispatch(kind).as_text()
        assert "tpu_custom_call" not in text
    assert rt.decode_compiles() == 0 and rt.prefill_compiles() == 0
    rt.warmup()
    assert rt.decode_compiles() == 1 and rt.prefill_compiles() == 1
