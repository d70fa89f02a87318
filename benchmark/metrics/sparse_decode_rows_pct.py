"""Cache manager: the share of the traced window's decode rows that stood at
or over the length from which the block-sparse layers select (under it they
attend to everything): whether the traffic reached the mechanism. The
``selecting_rows`` over the ``rows`` of the ``serving/decode_dispatch``
spans; a program without the attribute reads nothing."""
from benchmark import program_spans


def value(run, trace):
    t = program_spans.serving(run)
    if not t:
        return None
    spans = [r["attrs"] for r in t["inside"]
             if r["name"] == "serving/decode_dispatch"
             and "selecting_rows" in r["attrs"]]
    rows = sum(a.get("rows", 0) for a in spans)
    if not rows:
        return None
    return 100.0 * sum(a["selecting_rows"] for a in spans) / rows
