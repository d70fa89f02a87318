"""Kernels: the fullest held expert's rows over the mean expert's, a routed
layer, over the decode steps of the traced window (``expert_load_max`` is
summed over a step's layers, ``assignments_held`` over ``expert_slots`` is
the mean; the ``serving/decode_dispatch`` spans' attributes). 1 is an even
load; the products are batched at the fullest expert's rows. A program
without routed experts reads nothing."""
from benchmark import harness


def value(run, trace):
    spans = harness.load_module(
        "metrics", "experts_touched_pct").routing_spans(run)
    held = int(run.get("sizes", {}).get("n_routed_experts", 0))
    assigned = sum(a["assignments_held"] for a in spans)
    if not assigned or not held:
        return None
    layers = sum(a["expert_slots"] for a in spans) / held
    return (sum(a["expert_load_max"] for a in spans) / layers) / (
        assigned / (layers * held))
