"""Kernels: of the experts held (layers x experts, ``expert_slots``), the
share some live row chose, over the decode steps of the traced window (the
``experts_touched`` the step's program counts over its routed layers and the
engine records on the ``serving/decode_dispatch`` span). The step reads every
held expert's weights whatever this reads; a program without routed experts
has no such attributes and reads nothing."""
from benchmark import program_spans


def routing_spans(run):
    """The attributes of the traced window's decode dispatches that carry
    routing counts; [] where there are none."""
    t = program_spans.serving(run)
    return [r["attrs"] for r in (t or {}).get("inside", ())
            if r["name"] == "serving/decode_dispatch"
            and "expert_slots" in r["attrs"]]


def value(run, trace):
    spans = routing_spans(run)
    slots = sum(a["expert_slots"] for a in spans)
    if not slots:
        return None
    return 100.0 * sum(a["experts_touched"] for a in spans) / slots
