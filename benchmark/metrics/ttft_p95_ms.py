"""Scheduler and admission, as the client sees them: 95th percentile, over
the requests due in the window, of first streamed token minus DUE time (a
failed request counts its wait to the cut-off). Too few requests fit a window
of this cell for the tail to hold a bound (PERF.md), so it is read here."""
from benchmark.harness import percentile


def value(run, trace):
    return percentile(run.get("ttft_ms", []), 95)
