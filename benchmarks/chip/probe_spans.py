"""Read the program's own spans on a profiler trace of a cell's window.

    python3 benchmarks/chip/probe_spans.py --workload <name> --seed <n> \
        --seconds 51 [--rehearse]

Runs the cell's set-up, pre-roll and window as ``run_cell.py --trace 1``
does, with a ``Telemetry(profiler=True)`` recorder attached to the
runtime over the traced part (attached where the profiler starts,
detached where it stops), so every step of ``try_admit`` and
``decode()`` lands on the trace's host plane beside the device
operations (``harness/spans.py``).  The last line of standard output is
one JSON object: the median of each ``runtime.*`` span, all device idle
time by the innermost span over it (``idle_by_span``), the longest idle
gaps named the same way, the span readings (``decode_plan_ms``,
``decode_stage_ms``, ``decode_emit_ms``, ``admit_plan_ms``), the cell's
per-layer metrics and the candidate end-to-end metrics.  It checks no
correctness and is no cell of the benchmark: ``run_cell.py`` is.
``--rehearse`` runs it on the CPU at SMOKE widths (no device operations
are read there).
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run_cell  # noqa: E402
from harness import spec  # noqa: E402


def main(argv=None) -> int:
    args = run_cell.parse(argv)
    cell = spec.load_cell(args.workload)
    timer = run_cell.Timer()
    if args.rehearse:
        from harness.model import rehearse_conf
        cell.config = rehearse_conf(cell.config)
    import jax
    devs = run_cell.devices_or_exit(cell, args.rehearse)
    from harness import peaks, readings, spans
    from harness import trace as trace_lib
    from harness.client import Client
    from repro.serving import Telemetry
    pk = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0} \
        if args.rehearse else peaks.peaks(devs[0].device_kind)
    system, tr, warm, _ = run_cell.setup(cell, args.seed, args.seconds,
                                         args.rehearse, timer)
    rt = system.runtime
    client = Client(system, tr, prefill_t0=warm["prefill_chunk_s"],
                    annotate=True)
    opened = {}
    with tempfile.TemporaryDirectory(prefix="chipbench_spans_") as tdir:

        def on_open():
            opened["setup_s"] = time.perf_counter() - run_cell.T_START
            rt.telemetry = Telemetry(profiler=True)
            jax.profiler.start_trace(tdir)

        def on_trace_end():
            jax.profiler.stop_trace()
            rt.telemetry = None

        lg = client.run(args.seconds, on_open=on_open,
                        trace_for=run_cell.TRACE_S,
                        on_trace_end=on_trace_end)
        if rt.telemetry is not None:        # a window shorter than TRACE_S
            on_trace_end()
        t0 = time.perf_counter()
        tr_ev = trace_lib.load(tdir)
        rt_spans = spans.load(tdir)
        read_s = time.perf_counter() - t0

    ref = spec.reference_module(cell.config)
    run = readings.Run(lg, ref.counts_of(cell.config), pk,
                       rt.scfg.prefill_chunk, rt.scfg.decode_chunk,
                       opened["setup_s"])
    off = trace_lib.host_offset(tr_ev, lg.decodes[0].t0)
    window = (lg.w0 + off, lg.trace_end + off)
    both = trace_lib.Trace(tr_ev.ops, tr_ev.modules, tr_ev.spans + rt_spans,
                           tr_ev.chips)
    run.trace = trace_lib.reduce(both, window)
    ops = [(e.start, e.end) for e in tr_ev.ops
           if e.name not in trace_lib.CONTAINERS]
    busy = trace_lib.union(trace_lib.clip(ops, *window))
    idle = spans.idle_by_span(busy, both.spans, window)
    durs = spans.span_s(rt_spans, window)
    span_ms = {k: spans.median_ms(v) for k, v in sorted(durs.items())}
    out = {
        "traced_s": window[1] - window[0],
        "busy_s": run.trace.busy_s,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_gaps": [[k, v] for k, v in run.trace.gaps],
        "span_ms_p50": span_ms,
        "span_count": {k: len(v) for k, v in sorted(durs.items())},
        "span_ms_max": {k: 1e3 * max(v) for k, v in sorted(durs.items())},
        "readings": {
            "decode_plan_ms": span_ms.get("runtime.decode.plan"),
            "decode_stage_ms": span_ms.get("runtime.decode.stage"),
            "decode_emit_ms": span_ms.get("runtime.decode.emit"),
            "admit_plan_ms": spans.admit_plan_ms(rt_spans, window)},
        "per_layer": run_cell.layer_values(cell, run),
        "candidates": run_cell.e2e_values(run, all_=True),
        "read_s": read_s,
        "device": devs[0].device_kind,
    }
    run_cell.log("setup_s parts: " + ", ".join(
        f"{k} {v:.3f}" for k, v in timer.parts.items()))
    run_cell.log(f"idle by span (s): {out['idle_by_span']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
