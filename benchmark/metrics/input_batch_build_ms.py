"""Input pipeline: median ``input/batch`` span of the producer thread: pulling
a minibatch out of the dataset iterator (stack) and placing it on the device.
Busy time: the wait on a full queue lies outside the span."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and median(program_spans.durations_ms(t["inside"], "input/batch"))
