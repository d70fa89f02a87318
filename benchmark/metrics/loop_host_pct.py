"""Serving host loop: the non-dispatch phases' share of the loop's accounted
wall, over the window (difference of two ``stats()["loop"]`` readings). Host
time attribution; it is NOT the device's idle share."""

DISPATCH = ("prefill_dispatch", "decode_dispatch")


def value(run, trace):
    a, b = run.get("loop_before"), run.get("loop_after")
    if not a or not b:
        return None
    d = {k: b["phases"][k] - a["phases"].get(k, 0.0) for k in b["phases"]}
    total = sum(d.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in d.items() if k not in DISPATCH) / total
