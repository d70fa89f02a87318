"""Cache manager: median host time of ``serving/state_restore`` in the traced
window, over the admissions that copied a snapshot into their lane (the copy
is dispatched and not waited for: this is what the loop pays, not the
device)."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.serving(run)
    return t and median([program_spans.dur_ms(r) for r in t["inside"]
                         if r["name"] == "serving/state_restore"
                         and r["attrs"].get("bytes")])
