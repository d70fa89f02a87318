"""Train loop: the longest ``host/gc`` span (a full garbage collection, on
whatever thread ran it) inside the window's iterations; 0 if there was none."""
from benchmark import program_spans


def value(run, trace):
    t = program_spans.training(run, trace)
    return t and max(program_spans.durations_ms(t["inside"], "host/gc"),
                     default=0.0)
