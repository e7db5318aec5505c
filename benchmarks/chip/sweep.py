"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow across the window.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --seconds 100 --service-s 95 --rates 0.2 0.25 0.3 0.35

One process: the cell's set-up once, then for each rate the cell's
traffic at that rate, starting from ``rate x --service-s`` requests in
service (Little's law, at most the slots), its pre-roll and a window,
after which every request still running is aborted and the next rate
starts on an empty runtime.  Give the window more seconds than a request
lives.  For each rate one JSON line: requests due and admitted in the
window, the backlog (due but not yet admitted) and the running slots in
its first and last fifths, the mean TTFT and per-request TPOT, and the
mean time from due to last token that these imply (Little's law then
gives the requests in service at that rate).  The cell's traffic file is
then given about four fifths of the knee, and that rate's requests in
service.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run_cell  # noqa: E402
from harness import readings, spec, traffic  # noqa: E402
from harness.client import Client  # noqa: E402


def backlog(recs, t: float) -> int:
    return sum(1 for r in recs if r.due <= t and not (r.admit_call <= t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--service-s", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    run_cell.devices_or_exit(cell, False)
    timer = run_cell.Timer()
    system, _, warm, _ = run_cell.setup(cell, args.seed, args.seconds,
                                        False, timer)
    rt = system.runtime
    sv = rt.scfg
    out_mean = float(np.mean(traffic.lengths(cell.traffic["output"], 4096)))
    for rate in args.rates:
        tr = traffic.generate(
            cell.traffic, args.seed, vocab=system.cfg.vocab_size,
            max_total=cell.traffic["max_total"], seconds=args.seconds,
            slots=sv.num_slots, rate_per_s=rate,
            in_service=round(rate * args.service_s))
        lg = Client(system, tr, prefill_t0=warm["prefill_chunk_s"]).run(
            args.seconds)
        run = readings.Run(lg, None, {}, sv.prefill_chunk, sv.decode_chunk,
                           0.0)
        w0, w1 = lg.w0, lg.w1
        fifth = (w1 - w0) / 5
        due = run.due_in_window()
        ttft, tpot = run.ttft_s(), run.tpot_s()
        rows = [(c.t0, len(c.pos)) for c in lg.decodes]
        print(json.dumps({
            "rate_per_s": rate, "in_service": len(tr.in_service),
            "due": len(due),
            "admitted": sum(1 for r in due if r.admit_call == r.admit_call),
            "backlog_first_fifth": float(np.mean(
                [backlog(lg.recs, w0 + i * fifth / 4) for i in range(5)])),
            "backlog_last_fifth": float(np.mean(
                [backlog(lg.recs, w1 - fifth + i * fifth / 4)
                 for i in range(5)])),
            "slots_first_fifth": float(np.mean(
                [n for t, n in rows if t < w0 + fifth] or [0])),
            "slots_last_fifth": float(np.mean(
                [n for t, n in rows if t >= w1 - fifth] or [0])),
            "slots_open_close": [lg.active_open, lg.active_close],
            "ttft_mean_s": float(np.mean(ttft)) if ttft else None,
            "ttft_p50_s": readings.pct(ttft, 50),
            "tpot_mean_s": float(np.mean(tpot)) if tpot else None,
            "in_service_s": (float(np.mean(ttft) + (out_mean - 1)
                                   * np.mean(tpot)) if ttft and tpot
                             else None),
            "output_tok_per_s": run.tokens_in_window() / run.window_s,
            "counters": {k: run.counter(k, traced=False) for k in (
                "prompt_tokens", "shared_tokens", "prefill_chunks",
                "decode_chunks", "preemptions")}}), flush=True)
        for st in list(rt.slots.active()):
            rt.abort(st.req.req_id)
    return 0


if __name__ == "__main__":
    sys.exit(main())
