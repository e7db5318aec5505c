"""Static fixed-batch vs continuous-batching serving on a bursty
multi-LoRA trace — REAL engine execution on both sides, shared virtual
clock (arrivals on the trace timeline, time advances by measured device
wall-time).

Static baseline = the per-function serverless pattern the paper improves
on: each adapter function queues its own requests, dispatches a fixed-size
batch (fill-or-delay), and the batch holds its slice of the chip until the
LAST member finishes (convoy effect, no cross-adapter mixing).

Continuous = the `repro.serving` runtime: one fixed-shape slot batch mixes
every adapter, requests join/leave at chunk boundaries, KV lives in a paged
block pool.

Asserts (issue acceptance): continuous throughput >= static throughput, and
the decode step compiles exactly once after warmup — enforced by running
the whole replay under ``CompileGuard(max_compiles={"decode": 1,
"prefill": 1})`` (docs/static-analysis.md).

The headline numbers plus the runtime's full metrics snapshot are
recorded under ``results/BENCH_serving.json`` (``common.record_bench``).

Run: PYTHONPATH=src python -m benchmarks.bench_continuous
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core.engine import InferenceEngine
from repro.models import transformer as tf
from repro.serverless.batching import BatchingScheduler, BatchProfile, Request
from repro.serverless.simulator import SimResult
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import (CompileGuard, ContinuousRuntime, SamplingParams,
                           ServingConfig, replay_trace)
from repro.serving.replay import synth_prompts

PROMPT_LEN = 16
OUTPUT_MIN, OUTPUT_MAX = 2, 48
LONG_EVERY = 6          # every Nth request gets the full OUTPUT_MAX budget
SLO = 6.0


def bursty_workload(adapters: int, rate: float, duration: float,
                    seed: int) -> List[Dict]:
    """Saturating burst with HETEROGENEOUS output lengths — the workload
    shape where continuous batching pays off: static batches convoy on
    their longest member, continuous slots free exactly on budget."""
    specs = [TraceSpec(f"fn{a}", "bursty", rate, duration,
                       prompt_len=PROMPT_LEN, output_len=OUTPUT_MAX,
                       slo_ttft=SLO)
             for a in range(adapters)]
    wl = make_workload(specs, seed=seed)
    for w in wl:
        if w["req_id"] % LONG_EVERY == 0:
            w["output_len"] = OUTPUT_MAX          # long-tail chat turns
        else:
            w["output_len"] = OUTPUT_MIN + (w["req_id"] * 7) % 15
    return wl


# mixed-sampling assignment for the parity run: cycle every decode policy
# across the trace so one fixed decode shape serves all of them at once
SAMPLING_MIX = (
    None,                                            # greedy (default path)
    SamplingParams(temperature=0.8),
    SamplingParams(temperature=0.9, top_k=40),
    SamplingParams(temperature=0.7, top_p=0.9),
    SamplingParams(temperature=1.0, top_k=50, top_p=0.95),
)


def mixed_sampling(workload: List[Dict]) -> Dict[int, SamplingParams]:
    """req_id -> SamplingParams, cycling SAMPLING_MIX; greedy rows are
    simply absent from the dict (replay passes sampling=None through)."""
    out: Dict[int, SamplingParams] = {}
    for w in workload:
        sp = SAMPLING_MIX[w["req_id"] % len(SAMPLING_MIX)]
        if sp is not None:
            out[w["req_id"]] = sp
    return out


def run_static(cfg, params, workload: List[Dict], *, fixed_batch: int,
               fixed_delay: float, seed: int) -> SimResult:
    """Per-function fixed batches through InferenceEngine.generate, padded
    to ``fixed_batch`` rows so the whole baseline also compiles once."""
    eng = InferenceEngine(cfg, params, max_context=64)
    prompts = synth_prompts(workload, cfg.vocab_size, seed)
    sched = BatchingScheduler(adaptive=False, fixed_batch=fixed_batch,
                              fixed_delay=fixed_delay)
    fns = sorted({w["fn_id"] for w in workload})
    for fn in fns:
        sched.register(fn, BatchProfile(0.01, 0.001, fixed_batch))

    # warmup compile (excluded from the clock), split prefill/decode so
    # first_token is measured at the prefill boundary.  Fixed-batch
    # semantics: the jitted loop always runs OUTPUT_MAX-1 steps (one
    # compile); short requests ride the convoy and waste the tail steps.
    def run_batch(tok_mat, adapter):
        ai = jnp.full((fixed_batch,), adapter, jnp.int32)
        t0 = time.perf_counter()
        logits, cache = eng.prefill(jnp.asarray(tok_mat), ai)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        np.asarray(first)
        t_pre = time.perf_counter() - t0
        t0 = time.perf_counter()
        rest, _ = eng._gen_loop(eng.params, first, cache,
                                jnp.array(PROMPT_LEN, jnp.int32), ai,
                                OUTPUT_MAX - 1)
        np.asarray(rest)
        return t_pre, time.perf_counter() - t0

    warm = np.zeros((fixed_batch, PROMPT_LEN), np.int32)
    run_batch(warm, 0)

    requests = [Request(**w) for w in workload]
    arrivals = sorted(requests, key=lambda r: r.arrival)
    now, ai_idx = 0.0, 0
    pending = True
    while pending:
        while ai_idx < len(arrivals) and arrivals[ai_idx].arrival <= now:
            sched.push(arrivals[ai_idx])
            ai_idx += 1
        ready = sched.ready_queues(now)
        dispatched = False
        for q in ready:
            batch = q.pop_batch()
            if not batch:
                continue
            tok_mat = np.zeros((fixed_batch, PROMPT_LEN), np.int32)
            for i, r in enumerate(batch):
                tok_mat[i] = prompts[r.req_id]
            t_pre, t_dec = run_batch(tok_mat, int(q.fn_id[2:]))
            for r in batch:
                r.dispatch = now
                r.breakdown["queue_wait"] = now - r.arrival
                r.first_token = now + t_pre
                r.done = now + t_pre + t_dec     # convoy: batch holds slot
            now += t_pre + t_dec
            dispatched = True
            break                                # serial: one chip
        if not dispatched:
            nxt = []
            if ai_idx < len(arrivals):
                nxt.append(arrivals[ai_idx].arrival)
            t = sched.next_timer(now)
            if t is not None:
                nxt.append(t)
            if not nxt:
                pending = False
            else:
                now = max(now + 1e-9, min(nxt))
    return SimResult("static-fixed-batch", requests, 0.0, 0.0)


def throughput(res: SimResult) -> float:
    ok = [r for r in res.requests if r.first_token >= 0]
    toks = sum(r.output_len for r in ok)
    horizon = max((r.done for r in ok), default=1e-9)
    return toks / horizon


def run(adapters: int = 3, rate: float = 200.0, duration: float = 1.0,
        seed: int = 7, slots: int = 8, fixed_batch: int = 4) -> Dict:
    cfg = get_smoke("llama2_7b").with_(name="bench-continuous",
                                       dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg,
                            lora_adapters=adapters)
    wl = bursty_workload(adapters, rate, duration, seed)
    print(f"trace: {len(wl)} requests, {adapters} bursty adapter fns, "
          f"prompt {PROMPT_LEN} / output {OUTPUT_MIN}..{OUTPUT_MAX}")

    static = run_static(cfg, params, [dict(w) for w in wl],
                        fixed_batch=fixed_batch, fixed_delay=0.03, seed=seed)

    scfg = ServingConfig(num_slots=slots, block_size=8, num_blocks=128,
                         max_blocks_per_slot=8, prefill_chunk=PROMPT_LEN,
                         decode_chunk=8)
    rt = ContinuousRuntime(cfg, params, scfg)
    # CompileGuard replaces the old decode/prefill_compiles asserts: it
    # raises CompileBudgetExceeded on exit if either step re-jitted
    guard = CompileGuard({"decode": 1, "prefill": 1}, runtime=rt)
    with guard:
        cont, _ = replay_trace(rt, [dict(w) for w in wl],
                               {f"fn{a}": a for a in range(adapters)},
                               seed=seed, prefill_group=4,
                               slo_abandon=False)

    # mixed-sampling parity run: same trace, every decode policy cycled
    # across requests, still ONE decode + ONE prefill compile — sampling
    # params ride the dispatch as data, never as shape
    rt_s = ContinuousRuntime(cfg, params, scfg)
    guard_s = CompileGuard({"decode": 1, "prefill": 1}, runtime=rt_s)
    with guard_s:
        sampled, _ = replay_trace(rt_s, [dict(w) for w in wl],
                                  {f"fn{a}": a for a in range(adapters)},
                                  seed=seed, prefill_group=4,
                                  slo_abandon=False,
                                  sampling=mixed_sampling(wl))
    sampled.policy = "continuous-sampled"

    rows = {}
    for res in (static, cont, sampled):
        rows[res.policy] = {
            "served": len([r for r in res.requests if r.first_token >= 0]),
            "tok_per_s": throughput(res),
            "mean_ttft_ms": res.mean_ttft * 1e3,
            "p99_ttft_ms": res.p99_ttft * 1e3,
            "mean_tpot_ms": res.mean_tpot * 1e3,
        }
    hdr = f"{'policy':24s} {'served':>6s} {'tok/s':>8s} " \
          f"{'TTFT ms':>9s} {'p99 ms':>9s} {'TPOT ms':>8s}"
    print("\n" + hdr + "\n" + "-" * len(hdr))
    for name, m in rows.items():
        print(f"{name:24s} {m['served']:6d} {m['tok_per_s']:8.1f} "
              f"{m['mean_ttft_ms']:9.1f} {m['p99_ttft_ms']:9.1f} "
              f"{m['mean_tpot_ms']:8.2f}")

    speedup = rows["continuous-real"]["tok_per_s"] / \
        max(rows["static-fixed-batch"]["tok_per_s"], 1e-9)
    parity = rows["continuous-sampled"]["tok_per_s"] / \
        max(rows["continuous-real"]["tok_per_s"], 1e-9)
    mode_counters = {k: v for k, v in rt_s.stats.items()
                     if k.startswith("tokens_mode_") or k == "sampled_tokens"}
    print(f"\ncontinuous/static throughput: {speedup:.2f}x")
    print(f"sampled/greedy throughput parity: {parity:.2f}x "
          f"(mixed temperature/top-k/top-p vs all-greedy, same trace; "
          f"compile guard: {guard_s.report()})")
    print(f"sampling mode counters: {mode_counters}")
    greport = guard.report()
    print(f"compile guard: {greport}")
    syncs = rt.stats["admit_syncs"]
    served_cont = rows["continuous-real"]["served"]
    print(f"admission syncs: {syncs} whole-batch logit transfers "
          f"(before: the per-item np.asarray loop paid "
          f"{served_cont} — one device sync per admitted prompt)")
    # throughput comparison is only meaningful under backlog: when both
    # systems drain arrivals in real time, tok/s is arrival-limited on both
    # sides and the ratio is measurement noise around 1.0
    trace_end = max(w["arrival"] for w in wl)
    makespan = max(r.done for r in static.requests)
    saturated = makespan > 1.2 * trace_end
    if saturated:
        assert speedup >= 1.0, \
            f"continuous batching must not lose throughput " \
            f"(got {speedup:.2f}x)"
    else:
        print("note: trace does not saturate the engine "
              "(arrival-limited) — throughput assert skipped; raise "
              "--rate for the saturating comparison")

    from benchmarks.common import record_bench
    path = record_bench("bench_continuous", {
        "rows": rows,
        "speedup_vs_static": speedup,
        "sampling_parity_vs_greedy": parity,
        "sampling_mode_counters": mode_counters,
        "sampling_compile_guard": guard_s.report(),
        "compile_guard": greport,
        "admit_syncs": syncs,
        "metrics": rt.metrics_snapshot(),
    })
    print(f"metrics snapshot -> {path}")
    return rows


def run_csv(quick: bool = False) -> List[str]:
    """``benchmarks.run`` driver entry: run the quick comparison, emit
    CSV rows, and leave BENCH_serving.json behind (run() writes it)."""
    rows = (run(rate=40.0, duration=0.5, slots=4, fixed_batch=2)
            if quick else run())
    out = []
    for policy, m in rows.items():
        out.append(
            f"serving/{policy},{m['mean_ttft_ms'] * 1e3:.1f},"
            f"tok_per_s={m['tok_per_s']:.1f} served={m['served']}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=float, default=200.0)
    ap.add_argument("--duration", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--quick", action="store_true",
                    help="short low-rate trace + small static batch for "
                         "CI smoke (same correctness/compile assertions; "
                         "the throughput gate already self-disables when "
                         "the trace is arrival-limited)")
    args = ap.parse_args()
    if args.quick:
        run(rate=40.0, duration=0.5, seed=args.seed, slots=4,
            fixed_batch=2)
    else:
        run(rate=args.rate, duration=args.duration, seed=args.seed)
