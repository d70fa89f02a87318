"""Kernels (the MiniCPM-SALA cut's decode program as XLA emits it): the least
time one decode step needs for the rows live in the traced window (weights
read once, the SELECTED tokens' K and V and the visible compressed keys once,
each live lane's recurrent state read and written once;
``benchmark/minicpm_sala_counts.py``) over the step's measured device time.
Rows, tokens attended and tokens cached are the medians of the
``serving/decode_dispatch`` spans' attributes (the program reckons them from
the rows' positions at dispatch); a program without them reads nothing."""
from benchmark import counts, minicpm_sala_counts, program_spans
from benchmark.harness import median
from benchmark.reduce_trace import program_median_ms


def value(run, trace):
    ms = program_median_ms(trace, run["programs"].get("decode_step", []))
    t = program_spans.serving(run)
    if not ms or not t:
        return None
    spans = [r["attrs"] for r in t["inside"]
             if r["name"] == "serving/decode_dispatch"
             and "attended_tokens" in r["attrs"]]
    if not spans:
        return None
    flops, data = minicpm_sala_counts.decode_step(
        run["sizes"], median([a["rows"] for a in spans]),
        median([a["attended_tokens"] for a in spans]),
        median([a["cached_tokens"] for a in spans]))
    least, _ = counts.least_seconds(flops, data, run["peaks"])
    return 100.0 * least / (ms / 1e3)
