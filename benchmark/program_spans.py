"""The program's own spans beside the device trace, on one clock.

``bigdl_tpu.observability.trace`` stamps its spans with ``time.time_ns()``.
A profiler capture's events count from the capture's start, and its
``Task Environment`` plane says when that was in Unix nanoseconds
(``profile_start_time``, there at ``host_tracer_level`` 0 too). So
``profile_start_time + event.start_ns`` puts a device operation on the spans'
clock without one host event in the file: what the training cell needs, which
cannot record host events (PERF.md). Here the spans are brought to the
capture's base instead (``since``), which is the same thing in small numbers.

The readers under ``metrics/`` that read program spans build on this file:
``training(run, trace)`` / ``serving(run)`` hand them the window's spans and
log, in every traced run, what the spans say about the window as a whole: the
device's idle gaps by the span the loop was in (``named_gaps``), whether every
run of the step program lies inside its iteration's dispatch and fence
(``clock_check``: the proof that the two clocks are one), and how the spans'
sums close against the numbers taken from outside. A program without
``trace.export`` (before PR 26) gives None everywhere and nothing is logged.
"""

import glob
import json
import os

import numpy as np

from benchmark import harness, reduce_trace

_CAPTURES = {}      # path -> what read_capture gave: one read a process


def kept(run, key, make):
    """One value a run: its readers are all handed the same ``run`` and
    share what is kept on it."""
    store = run.setdefault("_program_spans", {})
    if key not in store:
        store[key] = make()
    return store[key]


def program_spans():
    """The completed spans of the process's own tracer as flat records,
    oldest first; None where the program cannot hand them out."""
    try:
        from bigdl_tpu.observability import trace
    except ImportError:
        return None
    export = getattr(trace, "export", None)
    return export() if export else None


def dur_ms(r):
    return (r["end_ns"] - r["start_ns"]) / 1e6


def durations_ms(records, name):
    return [dur_ms(r) for r in records if r["name"] == name]


def self_ns(records):
    """{span_id: a span's nanoseconds less what its children among
    ``records`` cover} (choosing-metrics, section 4)."""
    out = {r["span_id"]: r["end_ns"] - r["start_ns"] for r in records}
    for r in records:
        if r["parent_id"] in out:
            out[r["parent_id"]] -= r["end_ns"] - r["start_ns"]
    return out


# ----------------------------------------------------------- the capture
def newest_xplane(root=None):
    found = glob.glob(os.path.join(root or harness.ROOT, ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read_capture(path):
    """What the span readers need of one ``.xplane.pb``: the capture's start
    in Unix nanoseconds, the harness's marker span if host events were
    recorded, and the first device's program runs and operations as
    ``(name, start_ns, duration_ns)``, all still counted from the capture's
    start. None without a ``profile_start_time``."""
    from jax.profiler import ProfileData

    start, marker, first = None, None, None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        elif plane.name == "/host:CPU" and marker is None:
            marker = next(((float(e.start_ns), float(e.start_ns + e.duration_ns))
                           for ln in plane.lines for e in ln.events
                           if e.name == reduce_trace.MARKER
                           and e.duration_ns > 0), None)
        else:
            m = reduce_trace.DEVICE_PLANE.match(plane.name)
            if m and (first is None or int(m.group(1)) < first[0]):
                lines = {ln.name: [(e.name, float(e.start_ns),
                                    float(e.duration_ns)) for e in ln.events]
                         for ln in plane.lines
                         if ln.name in (reduce_trace.MODULES_LINE,
                                        reduce_trace.OPS_LINE)}
                first = (int(m.group(1)), lines)
    if start is None:
        return None
    lines = first[1] if first else {}
    return {"start_ns": int(start), "marker": marker,
            "modules": lines.get(reduce_trace.MODULES_LINE, []),
            "ops": lines.get(reduce_trace.OPS_LINE, [])}


def traced():
    """The newest capture, each file read once: ``{"start_ns", "window",
    "ops", "modules"}``. ``window`` is the traced window ``(lo, hi)`` (the
    marker's ends, else ``reduce_trace.steady_window``'s); it and the events
    count from ``start_ns`` as the file has them, because a float64 holds a
    Unix time in nanoseconds only to 256 ns: ``since`` brings spans to that
    base, in whole numbers. None where there is no capture or no window."""
    path = newest_xplane()
    if path not in _CAPTURES:
        capture = read_capture(path) if path else None
        if capture:
            capture["window"] = capture.pop("marker") or \
                reduce_trace.steady_window(capture["modules"])
        _CAPTURES[path] = capture if capture and capture["window"] else None
    return _CAPTURES[path]


def since(spans, start_ns):
    """``spans`` with their stamps counted from ``start_ns``."""
    return [dict(r, start_ns=r["start_ns"] - start_ns,
                 end_ns=r["end_ns"] - start_ns) for r in spans]


# ------------------------------------------------------------- the gaps
def idle_intervals(ops, lo, hi):
    """The parts of [lo, hi] in which no operation of ``ops`` ran."""
    busy = reduce_trace.merged(
        (s, s + d) for _, s, d in reduce_trace.clip(ops, lo, hi))
    edges = [lo] + [t for b in busy for t in b] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def named_gaps(ops, spans, lo, hi):
    """``reduce_trace.idle_gaps``' rule over the program's spans: every idle
    interval of the device inside [lo, hi] goes to the innermost (shortest)
    span of ``spans`` that covers its middle, else to ``unattributed``.
    Returns ``({name: seconds}, named share of the idle time in %)``; the
    share is None for a device that never idled."""
    gaps = np.asarray(idle_intervals(ops, lo, hi), np.float64).reshape(-1, 2)
    if not len(gaps):
        return {}, None
    spans = [r for r in spans if r["end_ns"] >= lo and r["start_ns"] <= hi]
    length = gaps[:, 1] - gaps[:, 0]
    label = np.full(len(gaps), -1)
    if spans:
        s = np.asarray([r["start_ns"] for r in spans], np.float64)
        e = np.asarray([r["end_ns"] for r in spans], np.float64)
        mid = gaps.mean(axis=1)
        for i in range(0, len(gaps), 4096):      # bounded memory
            m = mid[i:i + 4096, None]
            width = np.where((s <= m) & (m <= e), e - s, np.inf)
            best = width.argmin(axis=1)
            label[i:i + 4096] = np.where(
                np.isfinite(width[np.arange(len(best)), best]), best, -1)
    by = {}
    for k, ns in zip(label, length):
        name = spans[k]["name"] if k >= 0 else "unattributed"
        by[name] = by.get(name, 0.0) + float(ns) / 1e9
    named = sum(v for k, v in by.items() if k != "unattributed")
    return (dict(sorted(by.items(), key=lambda kv: -kv[1])),
            100.0 * named / (float(length.sum()) / 1e9))


def clock_check(modules, spans, names):
    """Are the device's events and the spans on one clock? The loop is
    synchronous, so a run of the step program (``names``) starts after its
    iteration's ``train/dispatch`` began and ends before that iteration's
    ``train/fence`` ended. Each run is matched to the iteration whose dispatch
    began nearest its start. Returns, in milliseconds, how long after its
    dispatch's START each run started and how long before its fence's END it
    ended (least, median, most), how many runs lie ``outside`` the two, and
    ``device_clock_early_ms``: the least and the most by which the device's
    clock can be early against the spans' for every run to lie inside (one
    clock if that interval holds 0; no run can be right if it is empty)."""
    by_id = {r["span_id"]: r for r in spans}

    def iteration_of(r):
        # LocalOptimizer's fence is a child of the step, DistriOptimizer's
        # of the iteration's bookkeeping: walk up
        while r is not None and r["name"] != "train/iteration":
            r = by_id.get(r["parent_id"])
        return r and r["span_id"]

    ends = {}    # iteration id -> [dispatch start, fence end]
    for r in spans:
        if r["name"] == "train/dispatch":
            ends.setdefault(iteration_of(r), [None, None])[0] = r["start_ns"]
        elif r["name"] == "train/fence":
            ends.setdefault(iteration_of(r), [None, None])[1] = r["end_ns"]
    pairs = np.asarray(sorted(v for v in ends.values() if None not in v),
                       np.float64).reshape(-1, 2)
    # the capture's two ends can cut a run short: its first and last are
    # left out
    runs = sorted((s, s + d) for name, s, d in modules
                  if reduce_trace.program_name(name) in names)[1:-1]
    if not len(pairs) or not runs:
        return None
    lead, tail = [], []
    for s, e in runs:
        a, b = pairs[np.abs(pairs[:, 0] - s).argmin()]
        lead.append(float(s - a) / 1e6)
        tail.append(float(b - e) / 1e6)
    spread = lambda xs: [min(xs), harness.median(xs), max(xs)]
    return {"steps": len(runs),
            "outside": sum(1 for x, y in zip(lead, tail) if x < 0 or y < 0),
            "start_after_dispatch_start_ms": spread(lead),
            "end_before_fence_end_ms": spread(tail),
            "device_clock_early_ms": [max(0.0, -min(lead)), min(tail)]}


def loop_thread_spans(spans, root):
    """The spans of the thread that ran the loop (its ``root`` spans): what
    the device waits for. The producer thread's spans overlap them."""
    threads = {r["thread"] for r in spans if r["name"] == root}
    return [r for r in spans if r["thread"] in threads]


# ------------------------------------------------------------- training
def training(run, trace):
    """The window's iterations: the newest ``len(run["iteration_ms"])``
    ``train/iteration`` spans (the loop ends with the window) and every span
    inside their extent, ``{"iterations", "inside", "self_ns", "gaps",
    "named_pct"}``. None without spans."""
    return kept(run, "training", lambda: _training(run, trace))


def _training(run, trace):
    n = len(run.get("iteration_ms") or [])
    spans = program_spans()
    its = [r for r in spans or [] if r["name"] == "train/iteration"][-n:]
    if not n or not its:
        return None
    lo, hi = its[0]["start_ns"], its[-1]["end_ns"]
    inside = [r for r in spans if lo <= r["start_ns"] and r["end_ns"] <= hi]
    own = self_ns(inside)
    out = {"iterations": its, "inside": inside, "self_ns": own,
           "gaps": None, "named_pct": None}
    med = lambda name: harness.median(durations_ms(inside, name))
    table = {"iterations": len(its), "median_ms": dict(
        {k: med("train/" + k) for k in
         ("iteration", "data_wait", "arguments", "dispatch", "fence",
          "bookkeeping")},
        iteration_self=harness.median(
            [own[r["span_id"]] / 1e6 for r in its]),
        input_batch=med("input/batch"), input_stack=med("input/stack"),
        input_place=med("input/place")),
        "gc_ms": durations_ms(inside, "host/gc")}
    step_ms = reduce_trace.program_median_ms(
        trace, run["programs"].get("train_step", []))
    if step_ms is not None:
        # closure: what the named spans say the host added to the device's
        # step, an iteration at a time (its children's sum; the medians of
        # the parts do not add up, since a late dispatch shortens its fence),
        # beside train_host_gap_ms (hook to hook, less the same step)
        table["host_gap_from_spans_ms"] = harness.median(
            [dur_ms(r) - own[r["span_id"]] / 1e6 for r in its]) - step_ms
        table["train_host_gap_ms"] = harness.median(
            run["iteration_ms"]) - step_ms
    cap = traced()
    if cap:
        loop = since(loop_thread_spans(spans, "train/iteration"),
                     cap["start_ns"])
        out["gaps"], out["named_pct"] = named_gaps(
            cap["ops"], loop, *cap["window"])
        table["gaps"] = out["gaps"]
        table["idle_named_pct"] = out["named_pct"]
        table["clock"] = clock_check(
            cap["modules"], loop, run["programs"].get("train_step", []))
    harness.log(f"[spans] {json.dumps(table)}")
    return out


# -------------------------------------------------------------- serving
PHASES = {"sweep": "serving/sweep", "admission": "serving/admission",
          "prefill_dispatch": "serving/prefill_dispatch",
          "decode_dispatch": "serving/decode_dispatch",
          "deliver": "serving/deliver", "observe": "serving/observe"}
SELF_TIME = ("admission", "deliver")   # their dispatches are their children


def phase_seconds(spans, own):
    """Per phase of ``stats()["loop"]``, the seconds its spans hold: a
    span's duration, or its self time where the phase's dispatches are its
    children."""
    return {p: sum((own[r["span_id"]] if p in SELF_TIME
                    else r["end_ns"] - r["start_ns"]) / 1e9
                   for r in spans if r["name"] == name)
            for p, name in PHASES.items()}


def serving(run):
    """The spans inside the traced window, ``{"inside", "self_ns", "gaps",
    "named_pct"}``. None without spans or without a capture."""
    return kept(run, "serving", lambda: _serving(run))


def _serving(run):
    spans, cap = program_spans(), traced()
    if not spans or not cap:
        return None
    own = self_ns(spans)
    spans = since(spans, cap["start_ns"])
    lo, hi = cap["window"]
    inside = [r for r in spans if lo <= r["start_ns"] and r["end_ns"] <= hi]
    out = {"inside": inside, "self_ns": own}
    loop = loop_thread_spans(spans, "serving/iteration")
    out["gaps"], out["named_pct"] = named_gaps(cap["ops"], loop, lo, hi)
    table = {"iterations_in_trace": len(durations_ms(
        inside, "serving/iteration")), "gaps": out["gaps"],
        "idle_named_pct": out["named_pct"]}
    # closure: the spans' per-phase sums over the window's iterations beside
    # the differences of the two stats()["loop"] readings (each reading
    # falls inside an iteration, so they part by up to one iteration's worth)
    a, b = run.get("loop_before"), run.get("loop_after")
    roots = [r for r in spans if r["name"] == "serving/iteration"]
    from bigdl_tpu.observability.tracing import MAX_ROOTS

    whole = sum(r["parent_id"] is None for r in spans) < MAX_ROOTS
    if a and b and whole and len(roots) >= b["iterations"]:
        # nothing has left the ring, so root i is the loop's iteration i
        ids = {r["span_id"] for r in roots[a["iterations"]:b["iterations"]]}
        phases = [r for r in spans if r["parent_id"] in ids]
        ids = {r["span_id"] for r in phases}
        got = phase_seconds(
            phases + [r for r in spans if r["parent_id"] in ids], own)
        table["closure_s"] = {p: [got[p], b["phases"][p] - a["phases"][p]]
                              for p in PHASES}
    harness.log(f"[spans] {json.dumps(table)}")
    return out
