"""Cache manager: of the tokens the decode rows of the traced window held in
the cache, the share whose K and V pages the block-sparse layers' decode step
gathered (a layer's; the ``gathered_tokens`` over the ``cached_tokens`` of the
``serving/decode_dispatch`` spans, which the program reckons at dispatch from
the rows' positions and the shapes of the step's two gathers: the selection's
pages for every row, and for a row under ``dense_len`` every further block it
may take, scratch pages among them). What the RULE attends is the spans'
``attended_tokens``, which the roofline reads. A program whose layers read
everything has no such attributes and reads nothing."""
from benchmark import program_spans


def value(run, trace):
    t = program_spans.serving(run)
    if not t:
        return None
    spans = [r["attrs"] for r in t["inside"]
             if r["name"] == "serving/decode_dispatch"
             and "gathered_tokens" in r["attrs"]]
    cached = sum(a.get("cached_tokens", 0) for a in spans)
    if not cached:
        return None
    return 100.0 * sum(a["gathered_tokens"] for a in spans) / cached
