"""Train loop: median ``train/arguments`` span: the step's learning rates and
key, made on the device by small programs of their own and one fetch, between
the batch and the call into the jitted step."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and median(program_spans.durations_ms(
        t["inside"], "train/arguments"))
