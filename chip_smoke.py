"""Chip smoke test: serve Qwen2.5-3B at its published widths on one TPU.

Drives the normal serving entry points once, in this one process:
``init_params`` (seeded random weights with a 4-slot LoRA bank) ->
``ContinuousRuntime`` + ``AdapterRegistry`` (4 named rank-16 adapters) ->
``replay_trace`` over 16 requests (prompts of 64-1024 tokens, some longer
than the prefill chunk and some sharing a per-adapter system prompt, 32
output tokens each).  It fails unless every request finished, the pool
drained, decode and prefill each compiled exactly once, every token is in
``[0, vocab)``, and both compiled programs call Pallas kernels
(``tpu_custom_call``; none means a kernel fell back to jnp).  A second
phase compares each served kernel (paged decode, paged prefill, SGMV) with
its jnp reference at the same widths, each within a stated tolerance.

Run on a machine with one TPU::

    python chip_smoke.py

The last line of stdout is then one JSON object naming the device.  With
no TPU the script exits non-zero and prints no result.  ``--smoke`` is
the CPU rehearsal (the reduced Qwen2.5 config, ``JAX_PLATFORMS=cpu``):
it runs every phase, with the Pallas kernels of the second phase in
interpret mode, and prints no result line.

Times printed here are a first-run sanity figure, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2_5_3b"
N_ADAPTERS = 4
N_REQUESTS = 16
OUTPUT_LEN = 32


def _sizes(smoke: bool) -> dict:
    """Serving shape and trace lengths.  Full size: 2048 blocks of 16
    tokens hold 32768 tokens of KV (36 KiB each at 36 layers, 2 KV heads,
    hd 128, bf16: 1.2 GB), far more than 8 slots of at most 1056 tokens
    need, beside 6.2 GB of bf16 weights on a 16 GB chip."""
    if smoke:
        return dict(num_blocks=256, max_blocks_per_slot=16, chunk=32,
                    min_prompt=16, max_prompt=200, sys_prompt=48)
    return dict(num_blocks=2048, max_blocks_per_slot=128, chunk=128,
                min_prompt=64, max_prompt=1024, sys_prompt=256)


def _trace(vocab: int, sz: dict, seed: int):
    """16 requests round-robin over 4 adapters.  Requests 0-3 and 8-11
    open with their adapter's system prompt, so the second of each pair
    maps the first one's prefix blocks from the prefix cache."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sys_p = [rng.integers(0, vocab, sz["sys_prompt"], dtype=np.int32)
             for _ in range(N_ADAPTERS)]
    workload, prompts = [], {}
    for i in range(N_REQUESTS):
        a = i % N_ADAPTERS
        shared = i % 8 < 4
        lo = sz["sys_prompt"] + sz["chunk"] if shared else sz["min_prompt"]
        n = int(rng.integers(lo, sz["max_prompt"] + 1))
        if i == 0:
            n = sz["max_prompt"]               # the longest prompt served
        head = sys_p[a] if shared else np.zeros(0, np.int32)
        prompts[i] = np.concatenate(
            [head, rng.integers(0, vocab, n - len(head), dtype=np.int32)])
        workload.append(dict(req_id=i, fn_id=f"fn{a}", arrival=0.02 * i,
                             prompt_len=n, output_len=OUTPUT_LEN,
                             slo_ttft=float("inf")))
    return workload, prompts


def _peak_hbm(dev):
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _fmt_bytes(n):
    return "not reported by this backend" if n is None else \
        f"{n} B ({n / 2**30:.2f} GiB)"


def serve_phase(cfg, seed: int, smoke: bool, dev) -> None:
    import jax
    import numpy as np

    from repro.core.lora import random_adapter
    from repro.models import transformer as tf
    from repro.serving import (AdapterRegistry, ContinuousRuntime,
                               ServingConfig, replay_trace, terminal_state)

    sz = _sizes(smoke)
    t0 = time.perf_counter()
    params = tf.init_params(jax.random.PRNGKey(seed), cfg,
                            lora_adapters=N_ADAPTERS)
    jax.block_until_ready(params)
    print(f"init: {cfg.name} {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.dtype}; "
          f"{time.perf_counter() - t0:.1f} s; peak HBM after init "
          f"{_fmt_bytes(_peak_hbm(dev))}")

    scfg = ServingConfig(num_slots=8, block_size=16,
                         num_blocks=sz["num_blocks"],
                         max_blocks_per_slot=sz["max_blocks_per_slot"],
                         prefill_chunk=sz["chunk"], prefill_rows=4,
                         decode_chunk=4)
    rt = ContinuousRuntime(cfg, params, scfg)
    reg = AdapterRegistry(rt)
    for a in range(N_ADAPTERS):
        reg.load(f"fn{a}", random_adapter(params, 100 + seed + a))
    print(f"serving: {scfg.num_slots} slots, {scfg.num_blocks} blocks x "
          f"{scfg.block_size} tokens, {scfg.max_blocks_per_slot} blocks "
          f"per slot, prefill chunk {scfg.prefill_chunk} x "
          f"{scfg.prefill_rows} rows, decode chunk {scfg.decode_chunk}; "
          f"adapters {', '.join(reg.names())} (rank {cfg.lora.rank})")

    kernels = {kind: rt.lower_dispatch(kind).as_text().count(
        "tpu_custom_call") for kind in ("decode", "prefill")}
    print(f"tpu_custom_call ops: decode {kernels['decode']}, prefill "
          f"{kernels['prefill']}")

    t0 = time.perf_counter()
    rt.warmup()
    compile_s = time.perf_counter() - t0
    workload, prompts = _trace(cfg.vocab_size, sz, seed)
    sink = {}
    t0 = time.perf_counter()
    res, _ = replay_trace(rt, workload, {f"fn{a}": f"fn{a}"
                                         for a in range(N_ADAPTERS)},
                          seed=seed, slo_abandon=False, prompts=prompts,
                          token_sink=sink)
    serve_s = time.perf_counter() - t0
    st = rt.stats
    n_tok = sum(len(t) for t in sink.values())
    print(f"first-run sanity figures, not a benchmark: warmup (compile + "
          f"2 runs of each program) {compile_s:.1f} s; serving "
          f"{len(workload)} requests {serve_s:.1f} s wall, {n_tok} tokens")
    print(f"prompt tokens {st['prompt_tokens']}, shared from the prefix "
          f"cache {st['shared_tokens']}, prefill chunks "
          f"{st['prefill_chunks']}, decode chunks {st['decode_chunks']}")
    print(f"peak HBM after serving {_fmt_bytes(_peak_hbm(dev))}")

    states = [terminal_state(r) for r in res.requests]
    n_done = states.count("finished")
    print(f"finished {n_done}/{len(workload)}; pool in use after drain "
          f"{rt.pool.in_use}; decode compiles {rt.decode_compiles()}, "
          f"prefill compiles {rt.prefill_compiles()}")
    toks = np.concatenate([np.asarray(t, np.int64) for t in sink.values()])
    failures = []
    if n_done != len(workload):
        failures.append(f"only {n_done}/{len(workload)} requests finished")
    if sorted(sink) != list(range(N_REQUESTS)) or any(
            len(t) != OUTPUT_LEN for t in sink.values()):
        failures.append("a request did not emit all of its tokens")
    if rt.pool.in_use != 0:
        failures.append(f"{rt.pool.in_use} pool blocks in use after drain")
    if rt.decode_compiles() != 1 or rt.prefill_compiles() != 1:
        failures.append("decode or prefill compiled more than once")
    if toks.min() < 0 or toks.max() >= cfg.vocab_size:
        failures.append(f"token ids outside [0, {cfg.vocab_size})")
    if st["shared_tokens"] == 0:
        failures.append("no prompt block came from the prefix cache")
    if not smoke and min(kernels.values()) < 1:
        failures.append(f"a served program calls no Pallas kernel "
                        f"(tpu_custom_call counts {kernels})")
    if failures:
        raise RuntimeError("serve phase: " + "; ".join(failures))


def _check(name: str, out, ref, tol: float, why: str) -> None:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    ok = bool(np.all(np.isfinite(out))) and np.allclose(
        out, ref, atol=tol, rtol=tol)
    print(f"kernel {name}: max |kernel - ref| {err:.3e} (max |ref| "
          f"{float(np.max(np.abs(ref))):.3e}), allclose atol=rtol={tol} "
          f"[{why}]: {'ok' if ok else 'FAILED'}")
    if not ok:
        raise RuntimeError(f"kernel {name} disagrees with its reference")


# The kernels take bf16 operands, accumulate in f32 and round their output
# to bf16 (8-bit significand: one step is 2**-8 = 3.9e-3 of the value);
# the attention kernels also round the softmax weights to bf16 before the
# PV matmul.  The references run in f32 at "highest" matmul precision and
# round once, so the honest error is a few bf16 steps of each output.
ATTN_TOL, ATTN_WHY = 2e-2, ("bf16 softmax weights and bf16 output vs an "
                            "f32 reference; outputs are O(1), 2e-2 is ~5 "
                            "bf16 steps there")
SGMV_TOL, SGMV_WHY = 3e-2, ("bf16 output of two chained f32-accumulated "
                            "matmuls vs an f32 reference; rtol 3e-2 is ~8 "
                            "bf16 steps of each output")


def kernel_phase(cfg, seed: int, smoke: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.ops import paged_decode_gqa
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.kernels.paged_prefill.ops import paged_prefill_gqa
    from repro.kernels.paged_prefill.ref import paged_prefill_ref
    from repro.kernels.sgmv.ops import sgmv_apply
    from repro.kernels.sgmv.ref import sgmv_ref

    sz = _sizes(smoke)
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    D, r, bs = cfg.d_model, cfg.lora.rank, 16
    NB, MB, C = sz["num_blocks"], sz["max_blocks_per_slot"], sz["chunk"]
    dt = cfg.jnp_dtype
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    kp = jax.random.normal(ks[0], (K, NB, bs, hd), jnp.float32).astype(dt)
    vp = jax.random.normal(ks[1], (K, NB, bs, hd), jnp.float32).astype(dt)
    # interpret mode only off the chip (CPU rehearsal); on the TPU the
    # default dispatch must take the Pallas kernel
    interp = True if smoke else None

    def tables(B, last_pos):
        """Per row: distinct physical blocks (never the garbage block 0)
        covering positions 0..last_pos, then -1."""
        tbl = np.full((B, MB), -1, np.int32)
        perm = rng.permutation(np.arange(1, NB))
        used = 0
        for b in range(B):
            n = int(last_pos[b]) // bs + 1
            tbl[b, :n] = perm[used:used + n]
            used += n
        return jnp.asarray(tbl)

    hi = jax.default_matmul_precision("highest")

    B = 8                                            # decode slots
    pos = rng.integers(0, MB * bs, B).astype(np.int32)
    tbl = tables(B, pos)
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32).astype(dt)
    out = paged_decode_gqa(q, kp, vp, tbl, jnp.asarray(pos),
                           interpret=interp)
    with hi:
        ref = paged_attention_ref(q, kp, vp, tbl, jnp.asarray(pos))
    _check("paged_decode_gqa", out, ref, ATTN_TOL, ATTN_WHY)

    G = 4                                            # prefill rows
    start = (rng.integers(0, (MB * bs - C) // bs, G) * bs).astype(np.int32)
    q_pos = jnp.asarray(start[:, None] + np.arange(C, dtype=np.int32))
    tbl = tables(G, start + C - 1)
    q = jax.random.normal(ks[3], (G, C, H, hd), jnp.float32).astype(dt)
    out = paged_prefill_gqa(q, kp, vp, tbl, q_pos, interpret=interp)
    with hi:
        ref = paged_prefill_ref(q, kp, vp, tbl, q_pos)
    _check("paged_prefill_gqa", out, ref, ATTN_TOL, ATTN_WHY)

    # SGMV at the LoRA sites' widths: q/o project D -> D, k/v D -> K*hd
    scaling = cfg.lora.scaling
    for R in (B, G * C):                             # decode / prefill rows
        for O in (H * hd, K * hd):
            x = jax.random.normal(ks[4], (R, D), jnp.float32).astype(dt)
            a = (jax.random.normal(ks[5], (N_ADAPTERS, D, r), jnp.float32)
                 / np.sqrt(D)).astype(dt)
            b = (jax.random.normal(ks[6], (N_ADAPTERS, r, O), jnp.float32)
                 / np.sqrt(r)).astype(dt)
            idx = jnp.asarray(rng.integers(0, N_ADAPTERS, R), jnp.int32)
            out = sgmv_apply(x, a, b, idx, scaling=scaling, use_kernel=True)
            with hi:
                ref = sgmv_ref(x, a, b, idx, scaling=scaling)
            _check(f"sgmv_apply R={R} O={O}", out, ref, SGMV_TOL, SGMV_WHY)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the reduced config; prints no "
                         "result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config, get_smoke

    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"use --smoke for the CPU rehearsal", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
    cfg = (get_smoke if args.smoke else get_config)(ARCH)
    serve_phase(cfg, args.seed, args.smoke, dev)
    kernel_phase(cfg, args.seed, args.smoke)
    if args.smoke:
        print("smoke rehearsal passed (CPU, reduced config: no chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
