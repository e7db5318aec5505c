"""Paged decode attention: in-kernel block-table walk vs the gather path.

Three measurements, one per layer of the claim:

1. **Attention-op microbench** — the legacy gather path (materialize every
   slot's (MB*bs) K/V view + additive mask tensor + full softmax) against
   the ``use_kernel`` dispatch (Pallas in-kernel table walk on TPU; fused
   jnp block walk elsewhere — same math, no gathered copy, no mask
   tensor).  Asserts the in-kernel path is >= 1x on decode step time at
   serving shapes and reports tokens/s.
2. **Bytes-moved model** — why the gather path loses: per decode step per
   layer it writes the gathered K/V copy and reads it back (3 passes over
   pool bytes vs the kernel's 1) plus a mask + f32 score round-trip.
3. **End-to-end serving** — two ``ContinuousRuntime``s on the same trace
   (use_kernel on/off): reports decode-chunk latency and replay tokens/s,
   asserts the decode step compiled exactly once per run, and round-trips
   a ``sliding_window`` config through ``replay_trace`` with paged serving
   enabled (the window is masked in-kernel; no dense fallback).

Run: PYTHONPATH=src python -m benchmarks.bench_paged_attn
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import statistics
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.kernels.paged_attention.ops import paged_decode_gqa
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models import transformer as tf
from repro.serverless.traces import TraceSpec, make_workload
from repro.serving import ContinuousRuntime, ServingConfig, replay_trace

from benchmarks.common import record_bench


@dataclasses.dataclass(frozen=True)
class Shape:
    B: int          # decode slots
    K: int          # kv heads
    G: int          # query heads per kv head
    hd: int
    bs: int         # block size
    MB: int         # max blocks per slot
    NB: int         # physical pool blocks
    label: str
    asserted: bool  # part of the >= 1x acceptance set


SHAPES = [
    Shape(8, 2, 4, 64, 16, 8, 64, "serving-small", True),
    Shape(8, 4, 4, 128, 16, 16, 256, "serving-mid", True),
    Shape(4, 2, 2, 32, 8, 6, 32, "smoke-cfg", True),
    Shape(16, 8, 4, 128, 32, 32, 512, "large (report only)", False),
]


def _mk_inputs(s: Shape, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (s.B, s.K * s.G, s.hd), jnp.float32)
    kp = jax.random.normal(ks[1], (s.K, s.NB, s.bs, s.hd), jnp.float32)
    vp = jax.random.normal(ks[2], (s.K, s.NB, s.bs, s.hd), jnp.float32)
    rng = np.random.default_rng(seed)
    tbl = np.full((s.B, s.MB), -1, np.int32)
    pos = np.zeros((s.B,), np.int32)
    for b in range(s.B):
        nb = int(rng.integers(max(1, s.MB // 2), s.MB + 1))
        tbl[b, :nb] = rng.choice(np.arange(1, s.NB), size=nb, replace=False)
        pos[b] = int(rng.integers((nb - 1) * s.bs, nb * s.bs))
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(pos)


def _timeit(fn, args, *, iters: int, repeats: int) -> float:
    """Median-of-repeats steady-state seconds per call."""
    out = fn(*args)
    jax.block_until_ready(out)
    meds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        meds.append((time.perf_counter() - t0) / iters)
    return statistics.median(meds)


def bytes_moved(s: Shape, itemsize: int = 4) -> Dict[str, float]:
    """HBM traffic model for ONE decode step of ONE attention layer.

    Pool bytes P = 2 * B*MB*bs*K*hd * itemsize (K and V, every table entry
    — -1 entries clip to the garbage block but are still fetched by both
    paths).  Gather: read pool + write the gathered view + read it back in
    attention = 3P, plus the (B, MB*bs) f32 mask and (B, H, MB*bs) f32
    score round-trips.  Kernel: stream pool tiles once through VMEM = P;
    masks/scores never leave registers/VMEM."""
    S = s.MB * s.bs
    P = 2 * s.B * S * s.K * s.hd * itemsize
    mask = s.B * S * 4
    scores = s.B * s.K * s.G * S * 4
    return {
        "gather_bytes": 3 * P + 2 * (mask + scores),
        "kernel_bytes": float(P),
        "model_ratio": (3 * P + 2 * (mask + scores)) / P,
    }


def bench_ops(iters: int, repeats: int,
              shapes: Sequence[Shape] = SHAPES) -> List[Dict]:
    rows = []
    for s in shapes:
        args = _mk_inputs(s)
        gather = jax.jit(paged_attention_ref)
        kernel = jax.jit(functools.partial(paged_decode_gqa,
                                           use_kernel=True))
        t_g = _timeit(gather, args, iters=iters, repeats=repeats)
        t_k = _timeit(kernel, args, iters=iters, repeats=repeats)
        bm = bytes_moved(s)
        rows.append({
            "shape": s, "gather_ms": t_g * 1e3, "kernel_ms": t_k * 1e3,
            "speedup": t_g / t_k,
            "gather_tok_s": s.B / t_g, "kernel_tok_s": s.B / t_k,
            **bm,
        })
    return rows


def bench_serving(rate: float, duration: float, seed: int,
                  sliding_window: Optional[int] = None) -> Dict:
    cfg = get_smoke("llama2_7b").with_(dtype="float32")
    if sliding_window is not None:
        cfg = cfg.with_(sliding_window=sliding_window)
    params = tf.init_params(jax.random.PRNGKey(0), cfg, lora_adapters=3)
    specs = [TraceSpec(f"fn{i}", "bursty", rate, duration, prompt_len=12,
                       output_len=8, slo_ttft=30.0) for i in range(3)]
    wl = make_workload(specs, seed=seed)
    out = {"requests": len(wl), "window": sliding_window}
    for use_kernel in (False, True):
        scfg = ServingConfig(num_slots=8, block_size=8, num_blocks=64,
                             max_blocks_per_slot=6, prefill_chunk=16,
                             decode_chunk=4, use_kernel=use_kernel)
        rt = ContinuousRuntime(cfg, params, scfg)
        res, _ = replay_trace(rt, [dict(w) for w in wl],
                              {f"fn{i}": i for i in range(3)},
                              slo_abandon=False)
        served = [r for r in res.requests if r.first_token >= 0]
        toks = sum(r.output_len for r in served)
        horizon = max((r.done for r in served), default=1e-9)
        compiles = rt.decode_compiles()
        assert compiles == 1, \
            f"decode re-jitted mid-serving ({compiles} cache entries, " \
            f"use_kernel={use_kernel})"
        assert rt.slots.num_active == 0 and rt.pool.in_use == 0, \
            "slots/blocks leaked"
        assert served, "nothing served"
        # steady-state decode-chunk latency, post-replay (fully compiled):
        # drive the jitted chunk directly, median of repeats
        tok = jnp.asarray(rt.slots.tokens)
        pos = jnp.asarray(rt.slots.pos)
        tbl = jnp.asarray(rt.slots.block_tbl)
        ai = jnp.asarray(rt.slots.adapter)
        srows = jnp.asarray(rt.slots.state_rows(rt.garbage_state_row))
        # greedy sampling vectors (temp 0 / filters off) — the fused
        # epilogue is part of the steady-state chunk being timed
        temp = jnp.asarray(rt.slots.temp)
        top_k = jnp.asarray(rt.slots.top_k)
        top_p = jnp.asarray(rt.slots.top_p)
        seed = jnp.asarray(rt.slots.seed)
        cnt = jnp.asarray(rt.slots.rng_counter)
        meds = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                toks_, rt.cache = rt._decode(rt.params, tok, rt.cache,
                                             pos, tbl, ai, srows, temp,
                                             top_k, top_p, seed, cnt)
            np.asarray(toks_)
            meds.append((time.perf_counter() - t0) / 10)
        t_dec = statistics.median(meds)
        key = "kernel" if use_kernel else "gather"
        out[key] = {"tok_per_s": toks / horizon, "served": len(served),
                    "decode_chunk_ms": t_dec * 1e3, "compiles": compiles}
    return out


def run(iters: int = 30, repeats: int = 5, rate: float = 4.0,
        duration: float = 3.0, seed: int = 7, quick: bool = False) -> Dict:
    backend = jax.default_backend()
    impl = "pallas in-kernel walk" if backend == "tpu" \
        else "fused jnp block walk (pallas interpret reserved for tests)"
    print(f"backend: {backend} — in-kernel path = {impl}"
          + (" [--quick: tiny shapes, perf assertion off]" if quick else "")
          + "\n")
    shapes = [s for s in SHAPES if s.label == "smoke-cfg"] if quick \
        else SHAPES

    print("== attention-op decode step: gather path vs in-kernel walk ==")
    hdr = (f"{'shape':20s} {'B':>3s} {'S':>5s} {'gather ms':>10s} "
           f"{'kernel ms':>10s} {'speedup':>8s} {'tok/s (kernel)':>14s} "
           f"{'bytes model':>11s}")
    print(hdr + "\n" + "-" * len(hdr))
    rows = bench_ops(iters, repeats, shapes)
    for r in rows:
        s = r["shape"]
        print(f"{s.label:20s} {s.B:3d} {s.MB * s.bs:5d} "
              f"{r['gather_ms']:10.3f} {r['kernel_ms']:10.3f} "
              f"{r['speedup']:7.2f}x {r['kernel_tok_s']:14.0f} "
              f"{r['model_ratio']:10.1f}x")
    asserted = [r for r in rows if r["shape"].asserted]
    worst = min(asserted, key=lambda r: r["speedup"])
    print(f"\nworst asserted speedup: {worst['speedup']:.2f}x "
          f"({worst['shape'].label})")
    if quick:
        # CI smoke guards against script rot (imports, shapes, the e2e
        # correctness/compile assertions below), not steady-state perf —
        # 2 iters on a shared runner is noise, so the >= 1x gate is off
        print("(--quick: speedup assertion skipped)")
    else:
        assert worst["speedup"] >= 1.0, \
            f"in-kernel path lost to the gather path at " \
            f"{worst['shape'].label} ({worst['speedup']:.2f}x)"

    print("\n== end-to-end paged serving (replay_trace) ==")
    e2e = bench_serving(rate, duration, seed)
    for key in ("gather", "kernel"):
        m = e2e[key]
        print(f"{key:8s}: {m['served']:3d} served, "
              f"{m['tok_per_s']:8.1f} tok/s, decode chunk "
              f"{m['decode_chunk_ms']:7.2f} ms, compiles={m['compiles']}")

    print("\n== sliding-window config through paged serving ==")
    swa = bench_serving(rate, duration, seed, sliding_window=8)
    for key in ("gather", "kernel"):
        m = swa[key]
        print(f"{key:8s}: {m['served']:3d} served, "
              f"{m['tok_per_s']:8.1f} tok/s, decode chunk "
              f"{m['decode_chunk_ms']:7.2f} ms, compiles={m['compiles']}")
    print("\nsliding-window trace round-tripped with paged serving "
          "(window masked in-kernel; decode compiled once)")
    out = {"ops": rows, "e2e": e2e, "swa": swa}
    # Shape dataclasses -> labels for the JSON record
    rec = {"ops": [{**r, "shape": r["shape"].label} for r in rows],
           "e2e": e2e, "swa": swa}
    print(f"metrics snapshot -> {record_bench('bench_paged_attn', rec)}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes + short trace for CI smoke; keeps "
                         "the correctness/compile assertions, skips the "
                         "perf one")
    a = ap.parse_args()
    if a.quick:
        run(iters=2, repeats=2, rate=3.0, duration=1.0, seed=a.seed,
            quick=True)
    else:
        run(iters=a.iters, repeats=a.repeats, rate=a.rate,
            duration=a.duration, seed=a.seed)
