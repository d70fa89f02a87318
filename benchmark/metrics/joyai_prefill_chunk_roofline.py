"""Kernels (the JoyAI-LLM-Flash cut's prefill program as XLA emits it): the
least time a prefill dispatch needs for the real tokens and rows it advanced
(medians of the ``serving/prefill_dispatch`` spans' ``tokens`` and ``rows``
in the traced window; ``benchmark/joyai_counts.py``: every held expert read
once) over the chunk program's median device time. A ragged dispatch (a last
chunk, an idle row) runs the whole compiled shape for fewer tokens, and
reads lower for it; the cached tokens a chunk attends before itself, and the
keys and values it expands for them, count for nothing."""
from benchmark import counts, joyai_counts, program_spans
from benchmark.harness import median
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("prefill_chunk", []))
    t = program_spans.serving(run) if ms else None
    if not ms or not t or "router_experts" not in run.get("sizes", {}):
        return None
    spans = [r for r in t["inside"] if r["name"] == "serving/prefill_dispatch"]
    tokens = median([r["attrs"].get("tokens", 0) for r in spans])
    rows = median([r["attrs"].get("rows", 0) for r in spans])
    if not tokens or not rows:
        return None
    flops, data = joyai_counts.prefill_chunk(run["sizes"], tokens, rows)
    least, _ = counts.least_seconds(flops, data, run["peaks"])
    return 100.0 * least / (ms / 1e3)
