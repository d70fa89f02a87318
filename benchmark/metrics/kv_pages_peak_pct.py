"""Cache manager: most pages in use at once (polled in the traced run) over
the pool's pages.

A reading, not a goal: the manifest has to give every metric a ``better`` and
this one's ("higher") says nothing. Below the knee most of the peak is what
the prefix index retains, not what live requests hold (54.4 % before and
after an engine 1.5 times as fast), and at a fixed rate a faster engine holds
FEWER live pages; what it is read for is how far the traffic is from a full
pool (PERF.md sections 3 and 7 (b))."""


def value(run, trace):
    if not run.get("max_pages") or run.get("pages_peak") is None:
        return None
    return 100.0 * run["pages_peak"] / run["max_pages"]
