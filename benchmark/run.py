#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it loads the cell's configuration and traffic by name, makes
weights and inputs from the seed, warms this cell's shapes (set-up), measures
for ``--seconds``, checks what the timed path produced against the plain
reference outside the window, and prints one JSON object as its last line.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a few seconds in the middle
of the window. Without a TPU it fails; there is no CPU fallback.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, reduce_trace  # noqa: E402


def per_layer_values(cell, result, summary):
    run = dict(result["record"], peaks=harness.peaks_for(
        result["device"]["kind"]))
    # a reader that finds nothing to read returns None and its metric is
    # left out of the line; one that raises fails the run
    return {m["name"]: harness.load_module("metrics", m["name"]).value(
        run, summary) for m in cell["per_layer"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.require_chips(cell["chips"])   # fails without the accelerator
    cache = harness.enable_cache()
    harness.log(f"[run] {args.workload} seed {args.seed} seconds "
                f"{args.seconds} trace {args.trace}; compile cache {cache}")
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    driver = harness.load_module("drivers", cell["config_json"]["driver"])
    result = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START, trace_dir=trace_dir)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": result["device"]}
    if args.trace:
        if not result.get("trace"):
            raise harness.BenchmarkError("the traced run captured no trace")
        summary = reduce_trace.summarize(
            reduce_trace.read_xplane(reduce_trace.find_xplane(trace_dir)),
            result["trace"])
        if not summary.get("busy_s"):
            raise harness.BenchmarkError(
                f"no device operation in the trace: {summary}")
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:       # by hand only: the trace for a look, not for a metric
            shutil.copytree(trace_dir, os.path.join(
                keep, f"{args.workload}-{args.seed}"), dirs_exist_ok=True)
        harness.say({"trace": {k: summary[k] for k in
                               ("devices", "window_s", "busy_s",
                                "busy_s_per_device", "programs",
                                "collective_ms", "lines")}})
        line["metrics"] = harness.metric_entries(
            cell["per_layer"], per_layer_values(cell, result, summary))
        line["device"].update(busy_s=summary["busy_s"],
                              window_s=summary["window_s"])
        line["breakdown"] = {"device_ops": summary["top_ops"],
                             "idle_gaps": summary["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        line["metrics"] = harness.metric_entries(cell["end_to_end"],
                                                 result["values"])
    # each number compared beside its limit: the run's last lines on
    # standard error and the result line's last key
    line["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                      for r in result["checks"]}
    for r in result["checks"]:
        harness.log(f"[check] {r['name']} {r['value']} limit {r['limit']} "
                    f"{'ok' if r['ok'] else 'NOT OK'}")
    harness.say(line)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchmarkError as e:
        harness.log(f"[run] {e}")
        sys.exit(2)
