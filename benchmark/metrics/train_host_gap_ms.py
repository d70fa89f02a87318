"""Train loop: median iteration wall (hook to hook) minus the step program's
median device time: fence, bookkeeping, summary, next batch."""
from benchmark.harness import median
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("train_step", []))
    walls = run.get("iteration_ms", [])
    if ms is None or not walls:
        return None
    return median(walls) - ms
