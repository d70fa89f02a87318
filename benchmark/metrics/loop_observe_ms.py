"""Serving host loop: median ``serving/observe`` span per iteration in the
traced window: what the telemetry itself costs on the decode thread."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.serving(run)
    return t and median(program_spans.durations_ms(
        t["inside"], "serving/observe"))
