"""Cache manager: of the prompt tokens the traced window's admissions matched
in the prefix cache (chunk-aligned), the share that was prefilled again
because no state snapshot stood at the match: the ``matched_tokens`` and
``resumed_tokens`` attributes of the ``serving/state_restore`` spans (one an
admission with a match, also where nothing could be resumed)."""
from benchmark import program_spans


def value(run, trace):
    t = program_spans.serving(run)
    if not t:
        return None
    spans = [r["attrs"] for r in t["inside"]
             if r["name"] == "serving/state_restore"]
    matched = sum(a.get("matched_tokens", 0) for a in spans)
    if not matched:
        return None
    return 100.0 * (matched - sum(a.get("resumed_tokens", 0)
                                  for a in spans)) / matched
