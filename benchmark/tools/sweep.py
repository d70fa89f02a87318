#!/usr/bin/env python3
"""The knee sweep of a serving cell, made once by hand (not by the driver's
check): one process, one set-up, then windows at rising arrival rates.

    python benchmark/tools/sweep.py --workload <name> --seed <n> \\
        --seconds 25 --rates 2,4,6,8,10

For each rate: requests sent and finished, the share of requests sent that
met TTFT <= 1000 ms and a mean gap <= 100 ms, tails, tokens per second, and
whether a backlog was left at the window's end. The knee is the highest rate
at which 90 % meet both limits and no backlog grows; a cell below the knee
runs at about four fifths of it, written into its traffic file.
"""
import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402

TTFT_LIMIT_MS, GAP_LIMIT_MS = 1000.0, 100.0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell["chips"])
    harness.enable_cache()
    serve = harness.load_module("drivers", "serve")
    ctx = serve.start_engine(cell, args.seed)
    table = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            mix = copy.deepcopy(cell["traffic_json"])
            mix["arrivals"]["rate_per_s"] = rate
            reqs = loadgen.open_loop_requests(
                mix, args.seed, args.seconds,
                int(cell["config_json"]["assumed"]["vocab_real"]))
            w = serve.drive_window(ctx, mix, reqs, args.seconds)
            measured, failed, e2e, extra = serve.window_numbers(
                w["clients"], w["t0"], args.seconds, w["cutoff"])
            met = 0
            for c in measured:
                if not c.finished:
                    continue
                ttft = (c.stamps[0] - c.due) * 1e3
                gap = ((c.stamps[-1] - c.stamps[0]) * 1e3
                       / max(1, len(c.stamps) - 1))
                met += ttft <= TTFT_LIMIT_MS and gap <= GAP_LIMIT_MS
            open_at_end = sum(1 for c in measured if not c.stamps
                              or c.stamps[-1] > w["t_end"])
            row = {"rate_per_s": rate, "sent": len(measured),
                   "failed": len(failed),
                   "met_both_pct": 100.0 * met / len(measured),
                   "open_at_window_end": open_at_end,
                   "queue_at_end": w["queue_at_end"],
                   "drain_s": w["cutoff"] - w["t_end"],
                   "ttft_p95_ms": harness.percentile(e2e.pop("ttft_ms"), 95),
                   **e2e, **extra,
                   "late_p95_ms": harness.percentile(
                       [(c.submitted - c.due) * 1e3 for c in measured
                        if c.submitted], 95)}
            harness.say(row)
            table.append(row)
    finally:
        ctx["engine"].stop()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sweep_{args.workload}.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
