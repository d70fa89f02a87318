"""Model step: median device time of the train-step program in the trace."""
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    return program_median_ms(trace, run["programs"].get("train_step", []))
