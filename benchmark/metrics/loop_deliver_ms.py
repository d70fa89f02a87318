"""Serving host loop: median self time of ``serving/deliver`` per iteration
in the traced window: sampling transfers and stream delivery beside the decode
dispatch, which is the span's child."""
from benchmark import program_spans
from benchmark.harness import median


def value(run, trace):
    t = program_spans.serving(run)
    return t and median([t["self_ns"][r["span_id"]] / 1e6 for r in t["inside"]
                         if r["name"] == "serving/deliver"])
