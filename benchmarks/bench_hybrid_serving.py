"""Hybrid REC/SSD serving through the continuous-batching runtime — the
two attention-free/hybrid architectures the paged runtime used to reject
(mamba2-shaped pure SSD, recurrentgemma-shaped REC+local-attention), each
replayed end to end with per-slot recurrent state rows beside the paged
KV pool.

Prints the per-slot decode working set by layer kind: ATTN layers page
per-position K/V blocks (grows with context until the window/table cap),
REC/SSD layers pin a FIXED-size dense state row (conv tail + hidden/SSM
state) regardless of context — the memory shape that makes long-context
decode natively cheap for these families.

Asserts (issue acceptance): both hybrid traces serve every admitted
request with slots/blocks fully reclaimed, exactly ONE decode and ONE
prefill compile after warmup, and the dense state-per-slot accounting
matches ``models.cache.state_bytes_per_slot``.

Run: PYTHONPATH=src python -m benchmarks.bench_hybrid_serving [--quick]
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_smoke
from repro.core.engine import make_state_extract_fn
from repro.models import transformer as tf
from repro.models.cache import slot_state_spec, state_bytes_per_slot
from repro.models.config import ATTN
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import ContinuousRuntime, ServingConfig, replay_trace

from benchmarks.common import record_bench

ARCHS = ("mamba2_780m", "recurrentgemma_9b")


def kv_bytes_per_slot(cfg, scfg: ServingConfig) -> int:
    """Paged-KV working-set CAP per slot: max_blocks_per_slot blocks of
    (K + V) per attention layer."""
    layers = list(cfg.pattern) * cfg.num_periods + list(cfg.remainder_layers)
    n_attn = sum(1 for k in layers if k == ATTN)
    per_block = (2 * cfg.num_kv_heads * scfg.block_size * cfg.head_dim_
                 * cfg.jnp_dtype.itemsize)
    return n_attn * scfg.max_blocks_per_slot * per_block


def state_table(cfg) -> str:
    layers = list(cfg.pattern) * cfg.num_periods + list(cfg.remainder_layers)
    lines = []
    for kind in sorted(set(layers)):
        n = layers.count(kind)
        spec = slot_state_spec(kind, cfg)
        if not spec:
            lines.append(f"    {kind:4s} x{n}: paged K/V blocks (no dense "
                         f"slot state)")
            continue
        parts = ", ".join(f"{name} {shp} {jax.numpy.dtype(dt).name}"
                          for name, (shp, dt) in spec.items())
        lines.append(f"    {kind:4s} x{n}: {parts}")
    return "\n".join(lines)


def run_arch(arch: str, quick: bool) -> None:
    cfg = get_smoke(arch).with_(dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg, lora_adapters=2)
    scfg = ServingConfig(num_slots=4, block_size=8, num_blocks=48,
                         max_blocks_per_slot=6, prefill_chunk=16,
                         decode_chunk=4)
    rt = ContinuousRuntime(cfg, params, scfg)
    assert rt.has_state, f"{arch} should carry REC/SSD slot state"

    sb = state_bytes_per_slot(cfg)
    kb = kv_bytes_per_slot(cfg, scfg)
    print(f"\n=== {arch} (smoke shape) ===")
    print(f"per-slot decode working set: {sb} B dense REC/SSD state "
          f"(fixed) + up to {kb} B paged KV (table cap)")
    print(state_table(cfg))

    duration = 3.0 if quick else 8.0
    specs = [TraceSpec(f"fn{a}", "bursty", 1.5, duration, prompt_len=20,
                       output_len=12, slo_ttft=30.0) for a in range(2)]
    wl = make_workload(specs, seed=7)
    res, _ = replay_trace(rt, wl, {f"fn{a}": a for a in range(2)},
                          slo_abandon=False)
    served = [r for r in res.requests if r.first_token >= 0]
    print(f"served {len(served)}/{len(wl)} requests | mean TTFT "
          f"{res.mean_ttft * 1e3:.1f} ms | mean TPOT "
          f"{res.mean_tpot * 1e3:.2f} ms")

    assert served and len(served) == len(wl), "hybrid trace dropped requests"
    assert rt.slots.num_active == 0, "slots leaked"
    assert rt.pool.in_use == 0, "KV blocks leaked"
    assert rt.decode_compiles() == 1, "decode step re-jitted"
    assert rt.prefill_compiles() == 1, "chunked prefill re-jitted"
    # accounting sanity: the docs-table number equals the MEASURED nbytes
    # of one slot's rows in the live cache (independent of the formula)
    ext = make_state_extract_fn(cfg)(rt.cache, 0)
    measured = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(ext))
    assert measured == sb, (measured, sb)
    print("OK: all served, pool drained, compile-once, accounting matches")
    record_bench(f"bench_hybrid_serving/{arch}", {
        "served": len(served),
        "mean_ttft_ms": res.mean_ttft * 1e3,
        "mean_tpot_ms": res.mean_tpot * 1e3,
        "state_bytes_per_slot": sb,
        "kv_bytes_per_slot_cap": kb,
        "metrics": rt.metrics_snapshot(),
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter traces (CI smoke)")
    args = ap.parse_args()
    for arch in ARCHS:
        run_arch(arch, args.quick)


if __name__ == "__main__":
    main()
