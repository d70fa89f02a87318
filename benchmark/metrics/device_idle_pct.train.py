"""Device: 1 - union of device-operation intervals over the traced window."""
from benchmark.reduce_trace import device_idle_pct


def value(run, trace):
    return device_idle_pct(trace)
