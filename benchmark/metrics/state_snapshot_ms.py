"""Cache manager: median host time of ``serving/state_snapshot`` in the
traced window: one lane's state copied into the store behind a prefill
dispatch (dispatched and not waited for: what the loop pays)."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.serving(run)
    return t and median(program_spans.durations_ms(
        t["inside"], "serving/state_snapshot"))
