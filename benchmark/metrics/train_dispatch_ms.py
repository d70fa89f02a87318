"""Train loop: median ``train/dispatch`` span: the call into the jitted step,
from the enqueue to the outputs handed back (the device starts inside it)."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and median(program_spans.durations_ms(
        t["inside"], "train/dispatch"))
