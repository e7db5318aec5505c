"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up builds the cell's configuration with weights and 64 LoRA adapters
made on the device from the seed, loads the adapters through the
program's ``AdapterRegistry``, compiles (or loads from
``<checkout>/.jax_cache``) the decode and prefill programs through
``runtime.warmup()``, generates the traffic, admits the requests already
in service at the cell's rate and runs the pre-roll's arrivals.  Then the
window: ``--seconds`` of wall-clock traffic through ``try_admit`` and
``decode()``, with no compile allowed inside it.
Afterwards a sample of the finished requests goes through the plain
reference (``harness/check.py``).

The last line of standard output is one JSON object: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  Without a TPU (or with fewer
chips than the cell asks for) the run exits non-zero and prints no result.
``--rehearse`` runs the cell end to end on the CPU at the repo's SMOKE
widths, the SGMV kernel interpreted, and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

from harness import spec  # noqa: E402

REHEARSE_SCALE = 1 / 8       # CPU rehearsal: every length shrinks by this
DRAIN_S = 60.0               # after the window, decode (no admissions) until
#   the correctness sample has finished requests, for at most this long
TRACE_S = 20.0               # a traced run traces the window's first 20 s
#   and reads every per-layer metric over that part: dozens of dispatches,
#   and a trace the reader takes in seconds


class Timer:
    """Named parts of set-up, for the earlier lines of output."""

    def __init__(self):
        self.parts = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        yield
        self.parts[name] = self.parts.get(name, 0.0) + \
            time.perf_counter() - t0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at SMOKE widths; prints no result line")
    return ap.parse_args(argv)


def devices_or_exit(cell, rehearse: bool):
    import jax
    devs = jax.devices()
    if rehearse:
        return devs
    if devs[0].platform != "tpu":
        log(f"run_cell: JAX found no TPU (platform {devs[0].platform!r}); "
            f"no result")
        sys.exit(2)
    if len(devs) < cell.chips:
        log(f"run_cell: the cell needs {cell.chips} chips, JAX found "
            f"{len(devs)}; no result")
        sys.exit(3)
    return devs


def setup(cell, seed: int, seconds: float, rehearse: bool, timer: Timer):
    """Everything before the pre-roll: weights, runtime, adapters, the
    compiled programs, the traffic."""
    from repro.compile_cache import enable_compile_cache
    from harness import model, traffic
    cache_dir = enable_compile_cache()
    conf = cell.config
    system = model.build_system(conf, seed, rehearse=rehearse, timer=timer)
    with timer("warmup"):
        warm = system.runtime.warmup()
    sv = model.serving_shape(conf, rehearse=rehearse)
    spec_tr = cell.traffic
    if spec_tr["adapters"]["count"] > system.n_adapters:
        if not rehearse:
            raise ValueError("the traffic names more adapters than the "
                             "configuration loads")
        spec_tr = dict(spec_tr, adapters=dict(spec_tr["adapters"],
                                              count=system.n_adapters))
    with timer("traffic"):
        tr = traffic.generate(
            spec_tr, seed, vocab=system.cfg.vocab_size,
            max_total=min(cell.traffic["max_total"],
                          sv["max_blocks_per_slot"] * sv["block_size"]),
            seconds=seconds, slots=sv["num_slots"],
            scale=REHEARSE_SCALE if rehearse else 1.0)
    return system, tr, warm, cache_dir


def run_window(system, tr, warm, seconds: float, traced: bool,
               trace_dir: str, timer: Timer, rehearse: bool = False):
    """Pre-roll, then the window under a CompileGuard; the profiler runs
    over the window only in a traced run."""
    import jax
    from repro.serving import CompileGuard
    from harness import check
    from harness.client import Client
    client = Client(system, tr, prefill_t0=warm["prefill_chunk_s"],
                    annotate=traced)
    guard = CompileGuard(max_compiles={"decode": 0, "prefill": 0},
                         max_total=0, runtime=system.runtime)
    opened = {}

    def on_open():
        opened["t"] = time.perf_counter()
        opened["setup_s"] = opened["t"] - T_START
        if traced:
            jax.profiler.start_trace(trace_dir)
        guard.__enter__()

    def on_trace_end():
        jax.profiler.stop_trace()
        opened["traced"] = False

    t0 = time.perf_counter()
    err = None
    try:
        lg = client.run(seconds, on_open=on_open,
                        trace_for=TRACE_S if traced else None,
                        on_trace_end=on_trace_end)
        client.drain(check.SAMPLE, check.min_tokens(rehearse), DRAIN_S)
    finally:
        try:
            guard.__exit__(None, None, None)
        except AssertionError as e:        # a compile inside the window
            err = str(e)
        if traced and opened.get("traced", True) and opened:
            jax.profiler.stop_trace()
    timer.parts["in_service_and_preroll"] = opened["t"] - t0
    return lg, opened["setup_s"], guard, err


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    timer = Timer()
    if args.rehearse:
        from harness.model import rehearse_conf
        cell.config = rehearse_conf(cell.config)
    with timer("import"):
        import jax
        import numpy as np
    with timer("devices"):
        devs = devices_or_exit(cell, args.rehearse)
    from harness import check, model, peaks, readings
    from harness import trace as trace_lib
    pk = None if args.rehearse else peaks.peaks(devs[0].device_kind)
    system, tr, warm, cache_dir = setup(cell, args.seed, args.seconds,
                                        args.rehearse, timer)
    rt = system.runtime
    traced = bool(args.trace)
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tdir:
        lg, setup_s, guard, compile_err = run_window(
            system, tr, warm, args.seconds, traced, tdir, timer,
            args.rehearse)
        stats = devs[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        conf = cell.config
        ref = spec.reference_module(conf)
        run = readings.Run(lg, ref.counts_of(conf),
                           pk or {"bf16_flops_per_s": 1.0,
                                  "hbm_bytes_per_s": 1.0},
                           rt.scfg.prefill_chunk, rt.scfg.decode_chunk,
                           setup_s)
        trace_info = {}
        if traced:
            t0 = time.perf_counter()
            tr_ev = trace_lib.load(tdir)
            if tr_ev.spans and lg.decodes:
                off = trace_lib.host_offset(tr_ev, lg.decodes[0].t0)
                run.trace = trace_lib.reduce(tr_ev, (lg.w0 + off,
                                                     lg.trace_end + off))
            trace_info = {"read_s": time.perf_counter() - t0,
                          "ops": len(tr_ev.ops), "chips": tr_ev.chips}

    # the program's state goes before the reference runs; the weights
    # (the benchmark's own) stay
    rt.cache = None
    rt.params = None
    gc.collect()
    t0 = time.perf_counter()
    min_tokens = check.min_tokens(args.rehearse)
    picked = check.sample(lg.recs, args.seed, min_tokens=min_tokens)
    weights = ref.reference_weights(system.backbone)
    g = check.gaps(conf, ref, weights,
                   lambda a: system.adapter_fn(model.adapter_key(
                       args.seed, a)), picked)
    ref_s = time.perf_counter() - t0

    correct, checks = check.verdict(conf, g.program, g.tokens,
                                    guard.backend_compiles, min_tokens)
    correct = correct and compile_err is None

    # ----------------------------------------------------- earlier lines
    due = run.due_in_window()
    late = np.asarray(run.lateness_s() or [0.0])
    log(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
        f"compile cache {cache_dir}")
    log("setup_s parts: " + ", ".join(
        f"{k} {v:.3f}" for k, v in timer.parts.items())
        + f"; window opened at {setup_s:.3f} s")
    log(f"warmup chunk seconds: {warm}")
    rows = run.rows_per_decode()
    log(f"requests: {len(tr.in_service)} in service at the pre-roll's "
        f"start, {len(tr.items)} arrivals over pre-roll and window")
    log(f"window {run.window_s:.3f} s: requests due/sent {len(due)}, "
        f"first token {sum(1 for r in due if r.times)}, finished "
        f"{sum(1 for r in due if r.done == r.done)}, failed "
        f"{sum(1 for r in due if r.failed)}; requests finished in window "
        f"{sum(1 for r in lg.recs if lg.w0 <= r.done <= lg.w1)}; tokens in "
        f"window {run.tokens_in_window()}; decode calls {len(lg.decodes)}, "
        f"admit calls {len(lg.admits)}")
    log(f"slots running: {lg.active_open} at the window's opening, "
        f"{lg.active_close} at its close, mean "
        f"{np.mean(rows) if rows else 0:.2f} over its decode calls")
    log("candidate metrics: " + json.dumps(e2e_values(run, all_=True)))
    log(f"generator lateness s: p50 {np.percentile(late, 50):.6f} p95 "
        f"{np.percentile(late, 95):.6f} max {late.max():.6f}")
    log(f"counters in window: " + ", ".join(
        f"{k} {run.counter(k, traced=False)}" for k in (
            "prompt_tokens", "shared_tokens", "recomputed_tokens",
            "prefill_chunks", "decode_chunks", "stall_steps", "preemptions",
            "aborted")))
    log(f"peak HBM bytes: {peak}")
    log(f"reference: {g.requests} requests, {g.tokens} served tokens, "
        f"{ref_s:.3f} s")
    if trace_info:
        log(f"trace: {trace_info}")
    if compile_err:
        log(f"compile in window: {compile_err}")

    if args.rehearse:
        vals = e2e_values(run) if not traced else layer_values(cell, run)
        log(f"rehearsal metrics (CPU, not device numbers): {vals}")
        for k, (v, lim, rule) in checks.items():
            log(f"check {k}: {v} {rule} {lim}")
        log(f"rehearsal {'passed' if correct else 'FAILED'}: no result line")
        return 0 if correct else 1

    if traced:
        values = layer_values(cell, run)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
    else:
        values = e2e_values(run)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    metrics = {k: {"value": values[k], "unit": units[k]}
               for k in units if values.get(k) is not None}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": sum(1 for r in due if r.failed), "metrics": metrics,
           "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.traced_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace.top_ops],
            "idle_gaps": [[k, v] for k, v in run.trace.gaps]}
    out["checks"] = {k: [v, lim] for k, (v, lim, _) in checks.items()}
    for k, (v, lim, rule) in checks.items():
        log(f"check {k}: {v} {rule} {lim}")
    print(json.dumps(out), flush=True)
    return 0


def e2e_values(run, all_: bool = False):
    """The end-to-end metrics; ``all_`` adds the candidates that are
    printed on an earlier line and not reported."""
    from harness.readings import pct
    ttft, tpot, itl = run.ttft_s(), run.tpot_s(), run.itl_s()
    ms = lambda v: None if v is None else 1e3 * v    # noqa: E731
    out = {"tpot_p50_ms": ms(pct(tpot, 50)),
           "itl_p95_ms": ms(pct(itl, 95)),
           "setup_s": run.setup_s}
    if all_:
        out.update(ttft_p50_ms=ms(pct(ttft, 50)),
                   ttft_p95_ms=ms(pct(ttft, 95)),
                   tpot_p95_ms=ms(pct(tpot, 95)),
                   itl_p50_ms=ms(pct(itl, 50)),
                   output_tok_per_s=run.tokens_in_window() / run.window_s,
                   ttft_n=len(ttft), tpot_n=len(tpot), itl_n=len(itl))
    return out


def layer_values(cell, run):
    return {m["name"]: spec.metric_reader(m["name"]).read(run)
            for m in cell.per_layer}


if __name__ == "__main__":
    sys.exit(main())
