"""Load generator: how late it sent (actual submit minus due time)."""
from benchmark.harness import percentile


def value(run, trace):
    return percentile(run.get("late_ms", []), 95)
