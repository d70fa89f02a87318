"""Train loop: median ``train/data_wait`` span, the loop's wait in
``next(data_iter)`` for the producer thread's batch."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and median(program_spans.durations_ms(
        t["inside"], "train/data_wait"))
