"""Device: the share of the device's idle time in the traced window that lies
under a span of the train loop's thread (``program_spans.named_gaps``)."""
from benchmark import program_spans


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and t["named_pct"]
