"""Kernels (the JoyAI-LLM-Flash cut's decode program as XLA emits it): the
least time one decode step needs for the rows live in the traced window (the
weights every token uses and the head read once, the held experts some row
TOUCHED once, each cached latent row once a layer, the absorbed products;
``benchmark/joyai_counts.py``) over the step's measured device time. Rows,
assignments and experts touched are the medians of the
``serving/decode_dispatch`` spans' attributes (the step's program counts its
routing), the cached tokens are the client-side stamps' (``live_in_trace``);
a program without the routing attributes reads nothing."""
from benchmark import counts, harness, joyai_counts
from benchmark.harness import median
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("decode_step", []))
    live = run.get("live_in_trace")
    if not ms or not live:
        return None
    spans = harness.load_module(
        "metrics", "experts_touched_pct").routing_spans(run)
    if not spans:
        return None
    flops, data = joyai_counts.decode_step(
        run["sizes"], median([a["rows"] for a in spans]),
        median([a["assignments_held"] for a in spans]),
        median([a["experts_touched"] for a in spans]), live["tokens"])
    least, _ = counts.least_seconds(flops, data, run["peaks"])
    return 100.0 * least / (ms / 1e3)
