"""Train loop: per iteration, the ``train/bookkeeping`` span (state, metrics,
the log line, instruments, summary hooks) plus the iteration's self time (what
no child span names); the median over the window's iterations."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.training(run, trace)
    if not t:
        return None
    book = {}
    for r in t["inside"]:
        if r["name"] == "train/bookkeeping":
            book[r["parent_id"]] = book.get(r["parent_id"], 0.0) \
                + program_spans.dur_ms(r)
    return median([book.get(it["span_id"], 0.0)
                   + t["self_ns"][it["span_id"]] / 1e6
                   for it in t["iterations"]])
