"""Scheduler / admission: the engine's own admitted_at - submitted_at."""
from benchmark.harness import percentile


def value(run, trace):
    return percentile(run.get("queue_wait_ms", []), 95)
