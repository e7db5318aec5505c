"""Look at a profiler trace of a cell's window by hand.

    python3 benchmarks/chip/probe_trace.py --workload <name> --seed <n> \
        --seconds 4 --out results/trace_probe

Runs the cell's set-up and a short traced window (as ``run_cell.py
--trace 1`` does), then writes ``trace_probe.json`` (per plane and line:
event counts, the first events with their stats, the most frequent and
the longest operation names, module names) and ``trace_fixture.json``
(every op, module and harness span of the first decode call: the
recorded trace ``tests/test_trace.py`` checks the reduction on, with the
reduction's numbers at the time it was recorded).
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run_cell  # noqa: E402
from harness import spec  # noqa: E402
from harness import trace as trace_lib  # noqa: E402


def summarize(trace_dir: str):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    out = {"file_bytes": Path(path).stat().st_size, "planes": []}
    for plane in pd.planes:
        pl = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(trace_lib.short(e.name)[:80]
                                        for e in evs)
            longest = collections.defaultdict(float)
            for e in evs:
                longest[trace_lib.short(e.name)[:80]] += e.duration_ns
            pl["lines"].append({
                "name": line.name, "events": len(evs),
                "first": [{"name": e.name[:160], "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": [[k, str(v)[:160]] for k, v in e.stats]}
                          for e in evs[:3]],
                "frequent": names.most_common(15),
                "longest": sorted(longest.items(),
                                  key=lambda kv: -kv[1])[:25]})
        out["planes"].append(pl)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default="results/trace_probe")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    timer = run_cell.Timer()
    run_cell.devices_or_exit(cell, False)
    system, tr, warm, _ = run_cell.setup(cell, args.seed, args.seconds,
                                         False, timer)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="chipbench_probe_") as tdir:
        lg, _, _, _ = run_cell.run_window(system, tr, warm, args.seconds,
                                          True, tdir, timer)
        t0 = time.perf_counter()
        summary = summarize(tdir)
        tr_ev = trace_lib.load(tdir)
        summary["load_s"] = time.perf_counter() - t0
        off = trace_lib.host_offset(tr_ev, lg.decodes[0].t0)
        summary["offset_s"] = off
        summary["window_trace"] = [lg.w0 + off, lg.w1 + off]
        lo = lg.decodes[0].t0 + off
        hi = lg.decodes[0].t1 + off
        pick = lambda evs: [[e.name[:80], e.start - lo, e.dur]  # noqa: E731
                            for e in evs if e.end > lo and e.start < hi]
        fixture = {"window": [0.0, hi - lo], "chips": tr_ev.chips,
                   "ops": pick(tr_ev.ops), "modules": pick(tr_ev.modules),
                   "spans": pick(tr_ev.spans)}
        small = trace_lib.reduce(trace_lib.Trace(
            *[[trace_lib.Ev(n, t, d) for n, t, d in fixture[k]]
              for k in ("ops", "modules", "spans")], tr_ev.chips),
            tuple(fixture["window"]))
        fixture["expected"] = {
            "busy_s": small.busy_s,
            "decode_s": small.program_s.get("decode", 0.0),
            "paged_decode_s": small.kernel_s.get(
                ("decode", "paged_decode"), 0.0),
            "sgmv_decode_s": small.kernel_s.get(("decode", "sgmv"), 0.0)}
        red = trace_lib.reduce(tr_ev, (lg.w0 + off, lg.w1 + off))
        summary["reduced"] = {
            "busy_s": red.busy_s, "program_s": red.program_s,
            "kernel_s": {f"{p}:{k}": v for (p, k), v in red.kernel_s.items()},
            "top_ops": red.top_ops, "gaps": red.gaps,
            "decode_calls": red.decode_calls[:10]}
    (out / "trace_probe.json").write_text(json.dumps(summary, indent=1))
    (out / "trace_fixture.json").write_text(json.dumps(fixture))
    print(json.dumps(summary["reduced"])[:3000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
