"""Benchmark driver — one module per paper table/figure.  Emits
``name,us_per_call,derived`` CSV and writes results/benchmarks.csv.
A suite that raises gets an ERROR row, the others still run, and the
driver then exits non-zero.

Roofline rows (deliverable g) are appended when dry-run artifacts exist
(run ``python -m repro.launch.dryrun --all`` first).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter simulated traces")
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()
    dur = 600.0 if args.quick else 1800.0

    from benchmarks import (bench_continuous, bench_kernels, fig6_ttft,
                            fig7_tpot, fig8_breakdown, fig11_scalability,
                            fig12_slo, sec69_overhead,
                            table1_cost_effectiveness, table2_throughput,
                            table3_ablation)

    suites = [
        ("fig6_ttft", lambda: fig6_ttft.run(dur)),
        ("fig7_tpot", lambda: fig7_tpot.run(dur)),
        ("fig8_breakdown", lambda: fig8_breakdown.run(dur)),
        ("table1_cost_effectiveness",
         lambda: table1_cost_effectiveness.run(dur)),
        ("table2_throughput", lambda: table2_throughput.run(min(dur, 600.0))),
        ("table3_ablation", lambda: table3_ablation.run(dur)),
        ("fig11_scalability", lambda: fig11_scalability.run(min(dur, 1200.0))),
        ("fig12_slo", lambda: fig12_slo.run(dur)),
        ("sec69_overhead", sec69_overhead.run),
        ("kernels", bench_kernels.run),
        # real-engine serving comparison; also writes the serving metrics
        # snapshot (host-bubble fraction, TTFT/TPOT percentiles, pool
        # gauges) to results/BENCH_serving.json
        ("serving_continuous", lambda: bench_continuous.run_csv(args.quick)),
    ]

    all_rows = ["name,us_per_call,derived"]
    failed = []
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        t0 = time.monotonic()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — record, run the rest, fail
            traceback.print_exc()
            failed.append(name)
            rows = [f"{name}/ERROR,0,{type(e).__name__}:{e}"]
        dt = time.monotonic() - t0
        print(f"# {name} ({dt:.1f}s)", file=sys.stderr)
        for r in rows:
            print(r)
            all_rows.append(r)

    # roofline rows from dry-run artifacts, if present (none -> no rows)
    from benchmarks.roofline import roofline_table
    for r in roofline_table():
        line = (f"roofline/{r['arch']}/{r['shape']},0,"
                f"compute_s={r['compute_s']:.5f} "
                f"memory_s={r['memory_s']:.5f} "
                f"collective_s={r['collective_s']:.5f} "
                f"dominant={r['dominant']} useful={r['useful_ratio']:.2f}")
        print(line)
        all_rows.append(line)

    out = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "benchmarks.csv"), "w") as f:
        f.write("\n".join(all_rows) + "\n")
    if failed:
        raise SystemExit(f"{len(failed)} benchmark suite(s) raised: "
                         + ", ".join(failed))


if __name__ == "__main__":
    main()
