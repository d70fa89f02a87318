"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read: device busy and idle share, device time per program, the operations
that took most, the longest idle gaps and what the host was doing in each.

The reduction works on plain lists of ``(name, start_ns, duration_ns)``, so
that it can be checked on a hand-built trace; ``read_xplane`` is the only part
that touches the profiler's file.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Modules`` has one
event per run of a compiled program, named ``<jit name>(<fingerprint>)``; its
line ``XLA Ops`` has one event per operation. Host threads are lines of the
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
MARKER = "bench/window"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path):
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return planes


def merged(intervals):
    """Sorted, non-overlapping [start, end] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(events):
    return sum(e - s for s, e in merged((s, s + d) for _, s, d in events))


def program_name(event_name):
    """``jit_step(1234567)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return None if not n else (xs[n // 2] if n % 2
                               else (xs[n // 2 - 1] + xs[n // 2]) / 2.0)


def programs(module_events):
    """Per program: runs, median and total device milliseconds."""
    by = {}
    for name, _, d in module_events:
        by.setdefault(program_name(name), []).append(d)
    return {k: {"runs": len(v), "median_ms": _median(v) / 1e6,
                "total_ms": sum(v) / 1e6} for k, v in by.items()}


def short_name(op):
    """An operation's event name is its whole HLO line on a TPU:
    ``%fusion.9 = f32[256,56]{...} fusion(...), kind=kLoop``. Keep the name,
    the opcode and the result's shape."""
    if " = " not in op:
        return op[:96]
    name, rest = op.split(" = ", 1)
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    code = re.search(r"[}\])] ([a-z][a-z0-9\-]*)\(", rest)
    return " ".join(x for x in (name.lstrip("%"),
                                code.group(1) if code else "",
                                shape.group(1) if shape else "") if x)[:96]


def top_operations(op_events, n=10):
    by = {}
    for name, _, d in op_events:
        by[name] = by.get(name, 0.0) + d
    return [[short_name(k), v / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def is_collective(op_name):
    head = short_name(op_name).split(" ")
    return any(part.startswith(COLLECTIVES) for part in head[:2])


def idle_gaps(op_events, host_lines, n=10, skip=("$",)):
    """The longest gaps between device operations, each labelled with the
    innermost host span (any line of the host plane) that covers the gap's
    middle, else ``unattributed``. Returns [[label, seconds], ...], gaps of
    one label summed, at most ``n`` labels."""
    busy = merged((s, s + d) for _, s, d in op_events)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:200]
    spans = [(s, s + d, name) for events in host_lines.values()
             for name, s, d in events
             if d > 0 and not name.startswith(skip) and name != MARKER]
    by = {}
    for length, start, end in gaps:
        mid = (start + end) / 2.0
        cover = [(e - s, name) for s, e, name in spans if s <= mid <= e]
        label = min(cover)[1] if cover else "unattributed"
        by[label] = by.get(label, 0.0) + length
    return [[k[:96], v / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def clip(events, lo, hi):
    """The events' parts inside [lo, hi]."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def marker_window(planes):
    """[start, end] of the ``bench/window`` span the harness wrote into the
    trace around its steady part; None if the trace has none."""
    for events in planes.get("/host:CPU", {}).values():
        for name, s, d in events:
            if name == MARKER and d > 0:
                return s, s + d
    return None


def steady_window(module_events):
    """Without a marker (host spans not recorded): from the first to the
    last start of the program that ran most often, so whole periods of the
    steady loop and none of the profiler's own start and stop."""
    by = {}
    for name, s, _ in module_events:
        by.setdefault(program_name(name), []).append(s)
    starts = max(by.values(), key=len, default=[])
    return (min(starts), max(starts)) if len(starts) > 2 else None


def summarize(planes, window_s=None):
    """The trace summary the metric readers get, over the steady part of
    the trace: under the harness's marker span where host spans are
    recorded, else ``steady_window`` (the profiler's own start and stop
    stall the host and lie outside both). A trace with neither: all of it,
    ``window_s`` long."""
    devices = sorted((int(m.group(1)), name) for name in planes
                     for m in [DEVICE_PLANE.match(name)] if m)
    if not devices:
        return {"devices": 0, "window_s": window_s, "busy_s": 0.0,
                "planes": sorted(planes)}
    win = marker_window(planes) or steady_window(
        planes[devices[0][1]].get(MODULES_LINE, []))
    if win is not None:
        lo, hi = win
        window_s = (hi - lo) / 1e9
    else:
        lo, hi = float("-inf"), float("inf")
    busy = [union_ns(clip(planes[name].get(OPS_LINE, []), lo, hi)) / 1e9
            for _, name in devices]
    first = planes[devices[0][1]]
    ops = clip(first.get(OPS_LINE, []), lo, hi)
    whole = [e for e in first.get(MODULES_LINE, [])
             if e[1] >= lo and e[1] + e[2] <= hi]
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "programs": programs(whole),
        "top_ops": top_operations(ops),
        "idle_gaps": idle_gaps(ops, planes.get("/host:CPU", {})),
        "collective_ms": sum(d for name, _, d in ops
                             if is_collective(name)) / 1e6,
        "lines": {name: sorted(planes[name]) for _, name in devices[:1]},
    }


def device_idle_pct(summary):
    if not summary or not summary.get("devices") or not summary["window_s"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def program_median_ms(summary, names):
    """Median device milliseconds of the first program of ``names`` the
    trace holds."""
    for n in names:
        hit = (summary or {}).get("programs", {}).get(n)
        if hit:
            return hit["median_ms"]
    return None
