"""Readings that the correctness limit of a cell is set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 11 12 13 --seconds 30 [--control]

For each seed, in one process: the cell's set-up with that seed's weights
and traffic, its pre-roll, a window of ``--seconds`` at the cell's own
load and the drain, then the check ``run_cell.py`` makes (the widest gap
of a served token's logit below the reference's best, over the same
sample).  With ``--control`` the same prompts and tokens also go through
the reference in fp8 put in the program's place, and the widest gap of
the tokens it puts first is read too.  Each reading is judged by the rule
and limit of ``run_cell.py`` (``check.verdict``): the program's has to
read correct, the control's not.  One JSON line per seed; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run_cell  # noqa: E402
from harness import check, model, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    if args.rehearse:
        cell.config = model.rehearse_conf(cell.config)
    run_cell.devices_or_exit(cell, args.rehearse)
    ref = spec.reference_module(cell.config)
    for seed in args.seeds:
        t0 = time.perf_counter()
        timer = run_cell.Timer()
        system, tr, warm, _ = run_cell.setup(cell, seed, args.seconds,
                                             args.rehearse, timer)
        lg, _, guard, err = run_cell.run_window(
            system, tr, warm, args.seconds, False, "", timer, args.rehearse)
        rt = system.runtime
        rt.cache = None
        rt.params = None
        gc.collect()
        t1 = time.perf_counter()
        least = check.min_tokens(args.rehearse)
        g = check.gaps(cell.config, ref,
                       ref.reference_weights(system.backbone),
                       lambda a: system.adapter_fn(model.adapter_key(
                           seed, a)),
                       check.sample(lg.recs, seed, min_tokens=least),
                       control=args.control)
        ok, _ = check.verdict(cell.config, g.program, g.tokens,
                              guard.backend_compiles, least)
        ok_c = None
        if args.control:
            ok_c, _ = check.verdict(cell.config, g.control, g.tokens, 0,
                                    least)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_gap": g.program, "program_correct": ok and err is None,
            "control_gap": g.control, "control_correct": ok_c,
            "limit": cell.config["check"]["max_logit_gap"],
            "tokens": g.tokens, "requests": g.requests,
            "compile_error": err, "run_s": t1 - t0,
            "reference_s": time.perf_counter() - t1}), flush=True)
        del system, lg, rt
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
