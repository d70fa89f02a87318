"""Model step: median device time of the prefill-chunk program."""
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    return program_median_ms(trace, run["programs"].get("prefill_chunk", []))
