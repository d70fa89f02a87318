#!/usr/bin/env python3
"""The readings the JoyAI-LLM-Flash cell's limits of ``correct`` are set from,
made by hand on the chip (the driver's check does not run this): the cell's
comparison numbers over many seeds in ONE process, and beside them seven
controls read over a run's own compared rows and put through the cell's
limits (``compare.row``): each must come out NOT correct, or is written down
as one the check cannot see (PERF.md section 7).

    python benchmark/tools/joyai_controls.py --workload joyai-reask-steady \\
        --seeds 11,12,13 --seconds 51 [--control-seeds 11] [--controls a,b]

Six plant a departure from the layer equations in the plain reference
(``benchmark/reference/joyai_llm_flash.py FAULTS``) and put it in the
program's place: ``bias_ignored`` (the top-8 is of ``s``, not of ``s + b``),
``gates_from_biased`` (``g_i`` from ``s_i + b_i``), ``sum_over_held`` (the
normalising sum over the held chosen only), ``no_scaling`` (the factor 2.5
dropped), ``no_rope_score`` (the rotary part of the score left out, what an
absorbed decode path that forgets ``q_rope . k_rope`` computes) and
``latent_int8`` (a token's cache row rounded to 255 steps: the nearest
precision below the bfloat16 the configuration states for the CACHE).
``reference_int8``: the reference with every weight matrix rounded to int8
steps (255 per output row; ``benchmark/weights.py``): the nearest precision
below the bfloat16 the configuration states for the WEIGHTS.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, weights  # noqa: E402
from benchmark.tools.olmo_controls import control_numbers, verdict  # noqa: E402

INT8 = "reference_int8"


def controls(config, seed, compared, only=None):
    """{control: {number: value}} over one run's compared rows; ``only``
    names the controls to read (all seven if None)."""
    import jax

    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    rows, spans = compared["rows"], compared["spans"]
    ref = compared["reference_logits"]
    w = adapter.weights(config, seed)
    wanted = [c for c in reference.FAULTS + (INT8,)
              if only is None or c in only]
    out = {}
    for fault in wanted:
        if fault == INT8:
            continue
        low = reference.forward(w, rows, config, fault=fault)
        out[fault] = control_numbers(ref, low, spans)
        del low
        gc.collect()
    if INT8 in wanted:
        # last, and leaf by leaf: two copies of the weights do not fit
        leaves, treedef = jax.tree.flatten(w)
        del w
        for i in range(len(leaves)):
            leaves[i] = weights.rounded(leaves[i])
        low = reference.forward(jax.tree.unflatten(treedef, leaves), rows,
                                config)
        out[INT8] = control_numbers(ref, low, spans)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--controls", default="")
    args = ap.parse_args(argv)
    only = [c for c in args.controls.split(",") if c] or None
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell["chips"])
    harness.enable_cache()
    driver = harness.load_module("drivers", cell["config_json"]["driver"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    sound, ctl = [], []
    for seed in seeds:
        cell = harness.load_cell(args.workload)
        res = driver.run(cell, seed, args.seconds, False, time.perf_counter())
        sound.append({"seed": seed, "correct": res["correct"],
                      "attempted": res["attempted"], "failed": res["failed"],
                      **{r["name"]: r["value"] for r in res["checks"]},
                      **res["values"]})
        harness.say({"sound": sound[-1]})
        if seed in control:
            limits = cell["config_json"]["check"]["limits"]
            for name, numbers in controls(cell["config_json"], seed,
                                          res["compared"], only).items():
                ok, fails = verdict(numbers, limits)
                ctl.append({"seed": seed, "control": name, **numbers,
                            "correct": ok, "fails": fails})
                harness.say({"control": ctl[-1]})
        del res
        gc.collect()
    harness.say({"all_correct": all(s["correct"] for s in sound),
                 "controls_passing_as_correct": sorted(
                     {c["control"] for c in ctl if c["correct"]})})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls_{args.workload}.json"), "w") as f:
        json.dump({"sound": sound, "control": ctl}, f, indent=1)


if __name__ == "__main__":
    main()
