"""Mixed-adapter serving vs one-runtime-per-adapter — the paper's C1.

The baseline is the serverless pattern ServerlessLoRA argues against: one
fully-provisioned runtime per LoRA function, each holding its own copy of
the backbone (99 % of the bytes duplicated).  The multi-LoRA runtime
serves every adapter from ONE resident backbone plus a stacked bank, with
per-slot deltas applied by SGMV inside the SAME compiled decode/prefill
steps.

What this bench asserts (issue acceptance):

* **Bitwise fidelity** — a mixed-adapter batch (every adapter live in one
  decode dispatch) produces per-request token streams identical to N=1
  single-adapter oracle runtimes sliced from the same bank.
* **Zero re-jit across churn** — a mixed trace replay, adapter unload +
  load of a NEW adapter into the recycled slot, and a second replay all
  run under one ``CompileGuard({"decode": 1, "prefill": 1})``.
* **Backbone resident exactly once** — the report quantifies the memory
  redundancy the per-adapter baseline pays (N backbones) vs the shared
  runtime (1), the paper's headline cost claim.

Run: PYTHONPATH=src python -m benchmarks.bench_multi_lora [--quick]
"""
from __future__ import annotations

import argparse
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core.lora import (backbone_param_count, combine_lora,
                             lora_param_count, partition_lora,
                             random_adapter)
from repro.models import transformer as tf
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import (AdapterRegistry, CompileGuard, ContinuousRuntime,
                           ServeRequest, ServingConfig, replay_trace)

PROMPT_LEN = 16
SLO = 8.0


def _single_adapter_params(params, slot: int):
    """One bank slot sliced into an N=1 bank over the SAME backbone arrays
    — the per-adapter oracle runtime's params."""
    bb, bank = partition_lora(params)
    one = jax.tree_util.tree_map(
        lambda x: None if x is None else
        jax.lax.slice_in_dim(x, slot, slot + 1, axis=-3),
        bank, is_leaf=lambda x: x is None)
    return combine_lora(bb, one)


def _serve(rt, items) -> List[List[int]]:
    """Admit [(prompt, adapter, out)] and run to completion; returns each
    item's full token stream (first token + decode emissions)."""
    res = rt.try_admit([ServeRequest(prompt=p, adapter=a, max_new_tokens=o)
                        for p, a, o in items])
    assert res is not None and not res.rejected, "bench admit failed"
    toks = {i: [res.first_tokens[i]] for i in range(len(items))}
    sid2i = {sid: i for i, sid in enumerate(res.slot_ids) if sid >= 0}
    while rt.slots.num_active:
        d = rt.decode()
        for sid, t in d.emitted.items():
            if sid in sid2i:
                toks[sid2i[sid]].extend(t)
    return [toks[i] for i in range(len(items))]


def _workload(fns: List[str], rate: float, duration: float,
              seed: int) -> List[Dict]:
    specs = [TraceSpec(fn, "bursty", rate, duration, prompt_len=PROMPT_LEN,
                       output_len=2 + (i * 5) % 10, slo_ttft=SLO)
             for i, fn in enumerate(fns)]
    return make_workload(specs, seed=seed)


def run(adapters: int = 3, rate: float = 60.0, duration: float = 0.6,
        seed: int = 7, slots: int = 4, decode_tokens: int = 8) -> Dict:
    assert adapters >= 2, "the multi-LoRA story needs >= 2 adapters"
    cfg = get_smoke("llama2_7b").with_(name="bench-multi-lora",
                                      dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg,
                            lora_adapters=adapters)
    scfg = ServingConfig(num_slots=slots, block_size=8, num_blocks=96,
                         max_blocks_per_slot=8, prefill_chunk=PROMPT_LEN,
                         decode_chunk=4)
    rt = ContinuousRuntime(cfg, params, scfg)
    reg = AdapterRegistry(rt)
    names = [f"fn{a}" for a in range(adapters)]
    for i, name in enumerate(names):
        reg.load(name, random_adapter(params, 100 + i))
    print(f"bank: {adapters} adapters loaded into {reg.capacity} slots "
          f"({', '.join(reg.names())})")

    # ---- memory: the paper's C1 redundancy claim, quantified ----------
    bytes_per = np.dtype(np.float32).itemsize
    bb_mb = backbone_param_count(rt.params) * bytes_per / 2 ** 20
    ad_mb = lora_param_count(rt.params) * bytes_per / adapters / 2 ** 20
    baseline_mb = adapters * (bb_mb + ad_mb)     # N full runtimes
    shared_mb = bb_mb + adapters * ad_mb         # ONE backbone + bank
    redundancy = 1.0 - shared_mb / baseline_mb
    print(f"weights resident: one-runtime-per-adapter {baseline_mb:.1f} "
          f"MiB ({adapters}x backbone) vs shared {shared_mb:.1f} MiB "
          f"(backbone resident ONCE) -> {redundancy * 100:.1f}% less")

    # ---- mixed trace replay + churn under ONE CompileGuard ------------
    guard = CompileGuard({"decode": 1, "prefill": 1}, runtime=rt)
    fn_map = {n: n for n in names}               # fn_id -> adapter NAME
    with guard:
        wl1 = _workload(names, rate, duration, seed)
        res1, _ = replay_trace(rt, wl1, fn_map, seed=seed,
                               prefill_group=2, slo_abandon=False)
        # adapter churn against the LIVE runtime: retire fn0, recycle its
        # slot for a brand-new adapter — zero recompiles
        reg.unload(names[0])
        churn_name = "fn_new"
        slot = reg.load(churn_name, random_adapter(params, 999))
        print(f"churn: unloaded {names[0]}, loaded {churn_name} into "
              f"recycled slot {slot}")
        fn_map2 = {n: n for n in names[1:] + [churn_name]}
        wl2 = _workload(list(fn_map2), rate, duration, seed + 1)
        res2, _ = replay_trace(rt, wl2, fn_map2, seed=seed + 1,
                               prefill_group=2, slo_abandon=False)
    greport = guard.report()
    print(f"compile guard across replay + churn + replay: {greport}")

    # ---- bitwise: mixed batch vs single-adapter oracle runtimes -------
    rng = np.random.default_rng(seed)
    live = reg.names()[:slots]                   # one request per adapter,
    #   all in ONE decode batch
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN, dtype=np.int32)
               for _ in live]
    mixed = _serve(rt, [(p, n, decode_tokens)
                        for p, n in zip(prompts, live)])
    mismatches = 0
    for p, name, want in zip(prompts, live, mixed):
        single = ContinuousRuntime(
            cfg, _single_adapter_params(rt.params, reg.slot_of(name)),
            scfg)
        got = _serve(single, [(p, 0, decode_tokens)])[0]
        if got != want:
            mismatches += 1
            print(f"  MISMATCH {name}: mixed {want} != single {got}")
    assert mismatches == 0, \
        f"{mismatches}/{len(live)} adapters diverged from the oracle"
    assert len({tuple(t) for t in mixed}) > 1, \
        "adapters produced identical streams — deltas are vacuous"
    print(f"bitwise: {len(live)} adapters in one mixed decode batch == "
          f"their single-adapter oracle runtimes, token for token")

    served1 = len([r for r in res1.requests if r.first_token >= 0])
    served2 = len([r for r in res2.requests if r.first_token >= 0])
    fns_served = {r.fn_id for r in res1.requests + res2.requests
                  if r.first_token >= 0}
    assert len(fns_served) >= 2, "mixed replay served < 2 adapters"
    print(f"replay: {served1}+{served2} requests served across "
          f"{len(fns_served)} adapter fns from one backbone")

    summary = {
        "adapters": adapters,
        "fns_served": sorted(fns_served),
        "served": served1 + served2,
        "mean_ttft_ms": res1.mean_ttft * 1e3,
        "backbone_mb": bb_mb,
        "adapter_mb": ad_mb,
        "baseline_resident_mb": baseline_mb,
        "shared_resident_mb": shared_mb,
        "memory_redundancy_saved": redundancy,
        "bitwise_oracle_adapters": len(live),
        "compile_guard": greport,
        "adapter_loads": rt.stats["adapter_loads"],
        "adapter_unloads": rt.stats["adapter_unloads"],
        "metrics": rt.metrics_snapshot(),
    }
    from benchmarks.common import record_bench
    path = record_bench("bench_multi_lora", summary)
    print(f"metrics snapshot -> {path}")
    return summary


def run_csv(quick: bool = False) -> List[str]:
    s = (run(rate=30.0, duration=0.4, decode_tokens=4) if quick else run())
    return [
        f"serving/multi-lora,{s['mean_ttft_ms']:.1f},"
        f"served={s['served']} adapters={len(s['fns_served'])} "
        f"mem_saved={s['memory_redundancy_saved'] * 100:.0f}%",
    ]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--adapters", type=int, default=3)
    ap.add_argument("--rate", type=float, default=60.0)
    ap.add_argument("--duration", type=float, default=0.6)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--quick", action="store_true",
                    help="short low-rate trace for CI smoke (same "
                         "bitwise/compile/memory assertions)")
    args = ap.parse_args()
    if args.quick:
        run(adapters=args.adapters, rate=30.0, duration=0.4,
            seed=args.seed, decode_tokens=4)
    else:
        run(adapters=args.adapters, rate=args.rate,
            duration=args.duration, seed=args.seed)
