#!/usr/bin/env python3
"""The readings a limit of ``correct`` is set from, made by hand on the chip
(the driver's check does not run this): a cell's comparison numbers over many
seeds in ONE process, and the lower-precision controls' beside them.

    python benchmark/tools/seeds.py --workload <name> --seeds 11,12,13 \\
        --seconds 12 [--control-seeds 11,12,13]

Serving controls, read over a run's own compared rows. ``reference_int8`` /
``reference_fp8``: the plain reference with every weight matrix rounded to
int8 steps (255 per output row) / through float8 e4m3, put in the program's
place. ``program_int8``: the program's own int8 path (``Quantizer`` weights,
int8 page pool) through the same paged pass the check reads the served model
with; ``program_kv_int8``: only the pool in int8. Training control: the plain
reference with parameters, inputs and activations in bfloat16, put in the
program's place. Each run's rows are printed; the summary gives, per number,
the sound runs' largest and each control's smallest.
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

from benchmark import harness  # noqa: E402


def rows_of(result, key="checks"):
    return {r["name"]: r["value"] for r in result[key]}


def control_only(cell, driver, seeds):
    """The training control without the program: the plain reference over
    the first steps of the cell's own batches, in float32 and in bfloat16."""
    import jax.numpy as jnp

    from benchmark import loadgen

    config, mix, chips = cell["config_json"], cell["traffic_json"], cell["chips"]
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    batch = int(mix["batch_per_chip"]) * chips
    for seed in seeds:
        x, y = loadgen.synthetic_dataset(mix, seed)
        idx = [(i * batch + j) % len(x) for i in range(driver.CHECK_STEPS)
               for j in range(batch)]
        batches = [(x[idx[i * batch:(i + 1) * batch]],
                    y[idx[i * batch:(i + 1) * batch]])
                   for i in range(driver.CHECK_STEPS)]
        ref = driver.reference_steps(config, seed, batches, chips, adapter,
                                     reference)
        rows = driver.control_rows(
            config, seed, chips, {"batches": batches, "reference": ref,
                                  "window_losses": []}, jnp.bfloat16)
        harness.say({"control": {"seed": seed,
                                 **{r["name"]: r["value"] for r in rows}}})


def serving_controls(cell, seed, compared):
    """{control: {number: value}} over one run's compared rows."""
    import jax

    from benchmark import compare, weights
    from bigdl_tpu.nn.quantized import Quantizer

    serve = harness.load_module("drivers", "serve")
    config = cell["config_json"]
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    rows, spans = compared["rows"], compared["spans"]
    ref = compared["reference_logits"]

    def numbers(low):
        gaps = np.concatenate([compare.argmax_gaps(ref[i], low[i], a, b)
                               for i, (a, b) in enumerate(spans)])
        return {"served_token_gap_max_rel": float(gaps.max()),
                "served_token_gap_mean_rel": float(gaps.mean()),
                "own_logits_error_rel_rms": compare.logit_error_rel_rms(
                    low, ref, spans)}

    out = {}
    for name, fn in (("reference_int8", weights.rounded),
                     ("reference_fp8", weights.rounded_fp8)):
        out[name] = numbers(serve.reference_logits(config, seed, rows, fn))
    model = adapter.build(config, seed)
    out["program_kv_int8"] = numbers(
        adapter.paged_logits(model, "int8", config, rows))
    quantized = Quantizer.quantize(model)
    del model
    gc.collect()
    out["program_int8"] = numbers(
        adapter.paged_logits(quantized, "int8", config, rows))
    del quantized
    gc.collect()
    jax.clear_caches()
    return out


def own_logits_on_random_rows(cell, seeds):
    """``own_logits_error_rel_rms`` without the engine, for more seeds than
    windows are affordable for: the seed's model through the check's paged
    pass over ``sample_requests`` rows of random tokens (lengths 256 up to
    the context), against the reference. Served rows read the same number
    (PERF.md sets the two side by side)."""
    from benchmark import compare

    serve = harness.load_module("drivers", "serve")
    config = cell["config_json"]
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    t = int(adapter.cache_geometry(config)["max_positions"])
    for seed in seeds:
        rng = np.random.RandomState(seed % (2 ** 32))
        rows = np.zeros((int(config["check"]["sample_requests"]), t), np.int32)
        spans = []
        for row in rows:
            n = int(rng.randint(min(256, t // 2), t + 1))
            row[:n] = rng.randint(0, int(config["assumed"]["vocab_real"]), n)
            spans.append((n // 2, n))
        model = adapter.build(config, seed)
        own = adapter.paged_logits(model, None, config, rows)
        del model
        gc.collect()
        ref = serve.reference_logits(config, seed, rows)
        harness.say({"random_rows": {
            "seed": seed, "own_logits_error_rel_rms":
            compare.logit_error_rel_rms(own, ref, spans)}})
        del own, ref
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--random-rows-seeds", default="",
                    help="serving: seeds read without the engine, see "
                         "own_logits_on_random_rows")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control-only", action="store_true",
                    help="training: only the reference and its bfloat16 "
                         "control at the cell's own size, no program (one chip "
                         "is enough for a four-chip cell's control)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(1 if args.control_only else cell["chips"])
    harness.enable_cache()
    kind = cell["config_json"]["driver"]
    driver = harness.load_module("drivers", kind)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    sound, ctl = [], []
    if args.control_only:
        control_only(cell, driver, control or seeds)
        return
    for seed in seeds:
        cell = harness.load_cell(args.workload)
        res = driver.run(cell, seed, args.seconds, False, time.perf_counter())
        sound.append({"seed": seed, "correct": res["correct"],
                      "failed": res["failed"], **rows_of(res),
                      **res["values"]})
        harness.say({"sound": sound[-1]})
        if seed in control:
            if kind == "train":
                import jax.numpy as jnp

                found = {"reference_bfloat16": {
                    r["name"]: r["value"] for r in driver.control_rows(
                        cell["config_json"], seed, cell["chips"],
                        res["compared"], jnp.bfloat16)}}
            else:
                found = serving_controls(cell, seed, res["compared"])
            for name, numbers in found.items():
                ctl.append({"seed": seed, "control": name, **numbers})
                harness.say({"control": ctl[-1]})
        del res
        gc.collect()
    if args.random_rows_seeds:
        own_logits_on_random_rows(
            cell, [int(s) for s in args.random_rows_seeds.split(",") if s])
    names = [k for k in sound[0] if k not in ("seed", "correct", "failed")]
    summary = {n: {"sound_max": max(s[n] for s in sound),
                   "control_min": {
                       c: min(r[n] for r in ctl if r["control"] == c)
                       for c in sorted({r["control"] for r in ctl
                                        if n in r})}}
               for n in names}
    harness.say({"summary": summary,
                 "all_correct": all(s["correct"] for s in sound)})
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"seeds_{args.workload}.json"), "w") as f:
        json.dump({"sound": sound, "control": ctl, "summary": summary}, f,
                  indent=1)


if __name__ == "__main__":
    main()
