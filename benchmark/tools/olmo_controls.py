#!/usr/bin/env python3
"""The readings the hybrid decoder's limits of ``correct`` are set from, made
by hand on the chip (the driver's check does not run this): the cell's
comparison numbers over many seeds in ONE process, and beside them two
lower-precision controls and one planted fault, read over a run's own
compared rows and put through the cell's limits (``compare.row``): each must
come out NOT correct.

    python benchmark/tools/olmo_controls.py --workload olmoh-docqa-steady \\
        --seeds 11,12,13 --seconds 12 [--control-seeds 11,12]

``reference_state_bf16``: the plain reference with the recurrent state S
rounded to bfloat16 after every token (the nearest precision below the
float32 the configuration states), put in the program's place.
``reference_int8``: the plain reference with every weight matrix rounded to
int8 steps (255 per output row; ``benchmark/weights.py``).
``reference_stale_state``: the plain reference whose linear layers leave
their recurrent state as it was at one served token in ``STALE_EVERY`` (what
a decode step that skips a lane's state write does), in the place of the
engine's decode step alone: its picks are held to the sound reference's
logits and to the served model's own (the prefill pass, which the fault does
not touch, so the logit error is the sound run's). Each run's rows are
printed; the summary gives, per number, the sound runs' largest and each
control's smallest. ``tools/seeds.py``'s serving controls run GPT-2's int8
program paths, which this architecture refuses; the sound runs' half of that
tool is this one's too.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import compare, harness, weights  # noqa: E402


#: the fault control leaves the state unchanged at every this-many-th served
#: token of a row, the first served token included
STALE_EVERY = 32


def pick_gaps(under, picks, spans):
    """The gaps, under the logits ``under``, of the tokens that the logits
    ``picks`` put first at the served positions."""
    return np.concatenate([compare.argmax_gaps(under[i], picks[i], a, b)
                           for i, (a, b) in enumerate(spans)])


def control_numbers(ref, low, spans):
    """Rows in, numbers out: the control's logits ``low`` in the program's
    place against the sound reference's ``ref``."""
    gaps = pick_gaps(ref, low, spans)
    return {"served_token_gap_max_rel": float(gaps.max()),
            "served_token_gap_mean_rel": float(gaps.mean()),
            "own_logits_error_rel_rms": compare.logit_error_rel_rms(
                low, ref, spans)}


def verdict(numbers, limits):
    """A control's numbers through the cell's limits, as a run's are:
    ``(correct, names of the limits it fails)``."""
    rows = [compare.row(name, value, limits[name])
            for name, value in numbers.items()]
    return all(r["ok"] for r in rows), [r["name"] for r in rows
                                        if not r["ok"]]


def controls(config, seed, compared):
    """{control: {number: value}} over one run's compared rows."""
    import jax.numpy as jnp

    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    rows, spans = compared["rows"], compared["spans"]
    ref = compared["reference_logits"]
    out = {}
    low = reference.forward(adapter.weights(config, seed), rows, config,
                            state_dtype=jnp.bfloat16)
    out["reference_state_bf16"] = control_numbers(ref, low, spans)
    del low
    gc.collect()
    low = reference.forward(weights.rounded(adapter.weights(config, seed)),
                            rows, config)
    out["reference_int8"] = control_numbers(ref, low, spans)
    del low
    gc.collect()
    stale = np.zeros(rows.shape, bool)
    for i, (a, b) in enumerate(spans):
        # logits[t] choose token t + 1: the steps that chose served tokens
        stale[i, a - 1:b - 1:STALE_EVERY] = True
    low = reference.forward(adapter.weights(config, seed), rows, config,
                            stale=stale)
    gaps = pick_gaps(ref, low, spans)
    own = compared["paged_logits"]
    out["reference_stale_state"] = {
        "served_token_gap_max_rel": float(gaps.max()),
        "served_token_gap_mean_rel": float(gaps.mean()),
        "served_token_gap_under_own_logits_max_rel": float(
            pick_gaps(own, low, spans).max()),
        "own_logits_error_rel_rms": compare.logit_error_rel_rms(
            own, ref, spans)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
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
                      "failed": res["failed"],
                      **{r["name"]: r["value"] for r in res["checks"]},
                      **res["values"]})
        harness.say({"sound": sound[-1]})
        if seed in control:
            limits = cell["config_json"]["check"]["limits"]
            for name, numbers in controls(cell["config_json"], seed,
                                          res["compared"]).items():
                ok, fails = verdict(numbers, limits)
                ctl.append({"seed": seed, "control": name, **numbers,
                            "correct": ok, "fails": fails})
                harness.say({"control": ctl[-1]})
        del res
        gc.collect()
    names = [k for k in sound[0] if k not in ("seed", "correct", "failed")]
    summary = {n: {"sound_max": max(s[n] for s in sound),
                   "control_min": {
                       c: min(r[n] for r in ctl if r["control"] == c)
                       for c in sorted({r["control"] for r in ctl
                                        if n in r})}}
               for n in names}
    harness.say({"summary": summary,
                 "all_correct": all(s["correct"] for s in sound),
                 "controls_not_correct": all(not c["correct"] for c in ctl)})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls_{args.workload}.json"), "w") as f:
        json.dump({"sound": sound, "control": ctl, "summary": summary}, f,
                  indent=1)


if __name__ == "__main__":
    main()
