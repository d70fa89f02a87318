"""Cache manager: most pages in use at once (polled in the traced run) over
the pool's pages."""


def value(run, trace):
    if not run.get("max_pages") or run.get("pages_peak") is None:
        return None
    return 100.0 * run["pages_peak"] / run["max_pages"]
