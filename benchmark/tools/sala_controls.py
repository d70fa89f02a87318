#!/usr/bin/env python3
"""The readings the MiniCPM-SALA cell's limits of ``correct`` are set from,
made by hand on the chip (the driver's check does not run this): the cell's
comparison numbers over many seeds in ONE process, and beside them four
controls read over a run's own compared rows and put through the cell's
limits (``compare.row``).

    python benchmark/tools/sala_controls.py --workload sala-longdoc-steady \\
        --seeds 11,12,13 --seconds 51 [--control-seeds 11] [--controls a,b]

``reference_dense``: the plain reference whose sparse layers attend to every
earlier token at every position (no selection), put in the program's place.
``reference_forced_only``: the reference whose sparse layers take the first
block and the window and nothing else (no top-k). A limit that lets these two
through does not see the mechanism. ``reference_state_bf16``: the reference
with the lightning state rounded to bfloat16 after every token (the nearest
precision below the float32 the configuration states for the STATE; on the
chip it reads UNDER the sound runs: a fixed-decay recurrence feeds no error
back into itself, and a state rounded once a token carries less noise than
the program's own bfloat16 operands; PERF.md section 4). ``reference_int8``:
the reference with every weight matrix rounded to int8 steps (255 per output
row; ``benchmark/weights.py``): the nearest precision below the bfloat16 the
configuration states for the WEIGHTS. Beside them
``selection_agreement``: over the longest compared row, the share of the
blocks the reference takes BY SCORE (the forced ones left out) that it still
takes with its scoring operands (q, K before it is compressed) rounded to
bfloat16 as the program keeps them: how far rounding alone moves the choice.
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

from benchmark import harness, weights  # noqa: E402
from benchmark.tools.olmo_controls import control_numbers, verdict  # noqa: E402

CONTROLS = {"reference_dense": {"sparse_rule": "dense"},
            "reference_forced_only": {"sparse_rule": "forced"},
            "reference_state_bf16": {"state_dtype": "bfloat16"},
            "reference_int8": {"int8_weights": True}}


def by_score(taken, z):
    """Of one row's selections (layers, G, T, NB), the blocks taken by score:
    past ``dense_len``, neither a first block nor one of the window's."""
    c = z["sparse_config"]
    t = np.arange(taken.shape[2])
    blk = np.arange(taken.shape[3])
    window = np.maximum(t - c["window_size"] + 1, 0) // c["block_size"]
    free = ((blk[None] >= c["init_blocks"]) & (blk[None] < window[:, None])
            & (t >= c["dense_len"])[:, None])
    return taken & free[None, None]


def selection_agreement(reference, w, rows, spans, config):
    """Share of the longest row's score-taken blocks that survive rounding
    the scoring operands to bfloat16; None where no position selects."""
    import jax.numpy as jnp

    i = int(np.argmax([b for _, b in spans]))
    row = rows[i:i + 1, :spans[i][1]]
    exact, rounded = [], []
    reference.forward(w, row, config, taken=exact)
    reference.forward(w, row, config, taken=rounded,
                      select_dtype=jnp.bfloat16)
    a = by_score(exact[0], config["sizes"])
    b = by_score(rounded[0], config["sizes"])
    return float((a & b).sum() / a.sum()) if a.sum() else None


def controls(config, seed, compared, only=None):
    """{control: {number: value}} over one run's compared rows; ``only``
    names the controls to read (all of them and the agreement if None)."""
    import jax
    import jax.numpy as jnp

    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    rows, spans = compared["rows"], compared["spans"]
    ref = compared["reference_logits"]
    w = adapter.weights(config, seed)
    wanted = lambda name: only is None or name in only
    out = {}
    for name, kw in CONTROLS.items():
        if not wanted(name) or "int8_weights" in kw:
            continue
        if "state_dtype" in kw:
            kw = {"state_dtype": jnp.dtype(kw["state_dtype"])}
        low = reference.forward(w, rows, config, **kw)
        out[name] = control_numbers(ref, low, spans)
        del low
        gc.collect()
    if wanted("selection_agreement"):
        out["selection_agreement"] = {"share": selection_agreement(
            reference, w, rows, spans, config)}
    if wanted("reference_int8"):
        # last, and leaf by leaf: two copies of the weights do not fit
        leaves, treedef = jax.tree.flatten(w)
        del w
        for i in range(len(leaves)):
            leaves[i] = weights.rounded(leaves[i])
        low = reference.forward(jax.tree.unflatten(treedef, leaves), rows,
                                config)
        out["reference_int8"] = control_numbers(ref, low, spans)
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
                if name == "selection_agreement":
                    ctl.append({"seed": seed, "control": name, **numbers})
                else:
                    ok, fails = verdict(numbers, limits)
                    ctl.append({"seed": seed, "control": name, **numbers,
                                "correct": ok, "fails": fails})
                harness.say({"control": ctl[-1]})
        del res
        gc.collect()
    judged = [c for c in ctl if "correct" in c]
    harness.say({"all_correct": all(s["correct"] for s in sound),
                 "controls_not_correct": all(not c["correct"]
                                             for c in judged)})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls_{args.workload}.json"), "w") as f:
        json.dump({"sound": sound, "control": ctl}, f, indent=1)


if __name__ == "__main__":
    main()
