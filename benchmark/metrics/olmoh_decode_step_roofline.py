"""Kernels (the hybrid decoder's decode program as XLA emits it): the least
time one decode step needs for the rows and cached tokens live in the traced
window (weights read once, each live token's K and V of the full-attention
layers once, each live lane's recurrent state read and written once;
``benchmark/olmo_hybrid_counts.py``) over the step's measured device time."""
from benchmark import counts, olmo_hybrid_counts
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("decode_step", []))
    live = run.get("live_in_trace")
    if not ms or not live or not live["rows"]:
        return None
    flops, data = olmo_hybrid_counts.decode_step(
        run["sizes"], live["rows"], live["tokens"])
    least, _ = counts.least_seconds(flops, data, run["peaks"])
    return 100.0 * least / (ms / 1e3)
