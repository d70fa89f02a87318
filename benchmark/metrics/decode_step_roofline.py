"""Kernels (the decode program as XLA emits it): the least time one decode
step needs for the rows and cached tokens live in the traced window (weights
read once, each live token's K and V read once; ``benchmark/counts.py``)
over the step's measured device time."""
from benchmark import counts
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("decode_step", []))
    live = run.get("live_in_trace")
    if not ms or not live or not live["rows"]:
        return None
    z = run["sizes"]
    flops, data = counts.gpt2_decode_step(
        z["n_embd"], z["n_layer"], z["vocab_size"], live["rows"],
        live["tokens"])
    least, _ = counts.least_seconds(flops, data, run["peaks"])
    return 100.0 * least / (ms / 1e3)
