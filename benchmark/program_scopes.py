"""Device time by scope: a capture's operations summed by the
``jax.named_scope`` names the programs gave their parts.

    python -m benchmark.program_scopes <dir or .xplane.pb> \
        [--program jit_step] [--by shape] [--config configs/<config>.json]

The programs open scopes from one vocabulary
(``bigdl_tpu.observability.tracing.DEVICE_SCOPES``; ``GROUPS`` below is that
vocabulary as this reader knows it, so that it reads a program from before
the tuple too), and ``Module.__call__`` opens the layer's class. A
configuration whose program opens a scope ``GROUPS`` lacks says so in its own
file, ``"scopes": {"<scope>": "<group>"}``: for that configuration's runs the
scope is vocabulary as a row of ``GROUPS`` is, read under the group it names,
which may be a new one (``vocabulary``). A declaration adds and never
re-groups: one that names a scope ``GROUPS`` has is refused. XLA keeps
the path of scopes an operation was traced under as the ``op_name`` of its
HLO metadata, a fusion that of its root instruction. A TPU capture holds it:
its ``/host:metadata`` plane carries every program's optimized HLO module as
a serialized ``HloProto`` (stat ``Hlo Proto``), which
``jax.profiler.ProfileData`` hands out no part of (an ``XLA Ops`` event's
``stats`` are its offset and duration, its name the whole HLO line), so this
file reads those few protobuf fields from the file's bytes itself
(``hlo_op_names``). An operation of the ``XLA Ops`` line is joined to its
instruction by its name (``%fusion.61 = ...`` is ``fusion.61``) within the
program whose run (``XLA Modules``, same plane, same clock) contains it.

An operation is charged to the INNERMOST vocabulary scope on its path;
where the path holds none, to the innermost module class; else it is
unscoped. Transform wrappers (``jit(..)``, ``jvp(..)``, ``transpose(..)``,
``vmap(..)``, ``checkpoint(..)``, any ``name(..)``) and ``while/body``,
``cond/branch_*`` name nothing. A ``while`` or a conditional is one event
with its body's operations inside it on the line: every event is charged its
SELF time (its duration less what the events nested in it cover), so nothing
counts twice. Only whole runs inside the window the other readers use count
(the ``bench/window`` marker, else ``reduce_trace.steady_window``).

This is the committed successor of the ``bykind.py`` scripts that PR 30, 32,
34 and 38 each wrote by hand: ``--by shape`` prints their table (kind of
operation and result shape) within each scope.
"""

import bisect
import json
import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness, program_spans, reduce_trace  # noqa: E402

#: the vocabulary (scope -> the group the per-layer metrics read)
GROUPS = {
    "embed": "dense", "attn/qkv": "dense", "attn/out": "dense",
    "mlp": "dense", "norm": "dense", "head": "dense", "sample": "dense",
    "attn/kv_write": "kv_pages", "attn/kv_gather": "kv_pages",
    "attn/attend": "attend", "sparse/attend": "attend",
    "sparse/select": "select",
    "gdn/step": "recurrent", "gdn/chunk": "recurrent",
    "lightning/step": "recurrent", "lightning/chunk": "recurrent",
    "optim/loss": "other", "optim/update": "other",
    "bigdl/grad_reduce_scatter": "other", "bigdl/weight_all_gather": "other",
}
#: a module class's group by what its name holds; any other class: "other"
CLASS_GROUPS = (("Convolution", "conv"), ("BatchNormalization", "bn"))
UNSCOPED = "unscoped"
#: the step programs of a cell, by the role ``run["programs"]`` gives them
STEP_ROLES = ("decode_step", "prefill_chunk", "train_step")

_JIT = re.compile(r"(?:^|/)p?jit\([^()/]*\)")
_WRAPPER = re.compile(r"[\w.\-]+\(")
_CLASS = re.compile(r"^[A-Z][A-Za-z0-9_]*$")
_DECLARED_SCOPE = re.compile(r"^[\w.\-]+(/[\w.\-]+)?$")
_DECLARED_GROUP = re.compile(r"^\w+$")


# ------------------------------------------------------------- the names
def vocabulary(declared=None):
    """``GROUPS`` with the scopes a configuration's file declares
    (``"scopes": {scope: group}``) beside them; ``GROUPS`` itself where it
    declares none. A declared scope is a one- or two-component name (a
    module class's name may be one), its group any name, a new one too.
    Refused: a scope ``GROUPS`` has or a class ``CLASS_GROUPS`` reads (a
    declaration adds, it never moves time out of a metric an accepted cell
    reports), and a name the rule could not find on a path."""
    if not declared:
        return GROUPS
    for scope, group in declared.items():
        if not (_DECLARED_SCOPE.match(scope) and isinstance(group, str)
                and _DECLARED_GROUP.match(group)) or group == UNSCOPED:
            raise harness.BenchmarkError(
                f"declared scope {scope!r}: {group!r}: a scope is one or two "
                "components of letters, digits, _ . -, its group a name "
                f"other than {UNSCOPED!r}")
        if scope in GROUPS or group_of(scope) != "other":
            raise harness.BenchmarkError(
                f"declared scope {scope!r} is read under "
                f"{group_of(scope)!r} already: a configuration adds scopes, "
                "it re-groups none")
    return {**GROUPS, **declared}


def scope_of(op_name, groups=GROUPS):
    """The scope an operation with this ``op_name`` is charged to: a scope
    of ``groups`` (a ``vocabulary``), else a module class, else None."""
    if not op_name:
        return None
    parts = [c for c in _WRAPPER.sub("", _JIT.sub("", op_name))
             .replace(")", "").split("/") if c]
    for i in range(len(parts) - 1, -1, -1):
        if i and parts[i - 1] + "/" + parts[i] in groups:
            return parts[i - 1] + "/" + parts[i]
        if parts[i] in groups:
            return parts[i]
    return next((c for c in reversed(parts) if _CLASS.match(c)), None)


def group_of(scope, groups=GROUPS):
    if scope in (None, UNSCOPED):
        return UNSCOPED
    if scope in groups:
        return groups[scope]
    return next((g for part, g in CLASS_GROUPS if part in scope), "other")


def instruction_name(event_name):
    """``%fusion.61 = bf16[..] fusion(..)`` -> ``fusion.61``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# ------------------------------------------------- the capture's HLO protos
def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _sub(buf, number):
    return (v for f, v in _fields(buf) if f == number
            and not isinstance(v, int))


def _text(buf, number):
    return next((bytes(v).decode("utf-8", "replace")
                 for v in _sub(buf, number)), None)


def hlo_op_names(path):
    """``{program event name: {instruction name: op_name or None}}`` from
    the ``Hlo Proto`` stats of the capture's ``/host:metadata`` plane: the
    optimized module of every program that ran, under the name its runs have
    on the ``XLA Modules`` line (``jit_step(<fingerprint>)``). Field numbers
    of tsl's ``xplane.proto`` (XSpace.planes 1; XPlane.name 2,
    .event_metadata 4; XEventMetadata.name 2, .stats 5; XStat.bytes_value 6)
    and XLA's ``hlo.proto`` (HloProto.hlo_module 1; HloModuleProto
    .computations 3; HloComputationProto.instructions 2; HloInstructionProto
    .name 1, .metadata 7; OpMetadata.op_name 2). {} where the capture holds
    no such plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):                  # map<int64, metadata>
            for meta in _sub(entry, 2):
                names = {}
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            for comp in _sub(module, 3):
                                for ins in _sub(comp, 2):
                                    names[_text(ins, 1)] = next(
                                        (_text(m, 2) for m in _sub(ins, 7)),
                                        None)
                if names:
                    out[_text(meta, 2)] = names
    return out


# ------------------------------------------------------------ the events
def self_times(ops):
    """``ops`` sorted by start (the longer first of two that start
    together) and, beside them, each one's nanoseconds less what the events
    nested in it cover."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    own = [d for _, _, d in ops]
    stack = []                                  # (end, index) of open events
    for i, (_, s, d) in enumerate(ops):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= d
        stack.append((s + d, i))
    return ops, own


def runs_in_window(modules, ops, window):
    """``{program: [run]}`` over the runs that lie wholly inside
    ``window``: a run is ``{"event": the module event's name, "ms": its
    device milliseconds, "ops": [(operation's event name, self ns)]}``. An
    operation belongs to the run that contains its start; one outside every
    run is dropped. The capture's two ends can cut a run short (a window
    without a marker opens at the first run's start): its first and last
    runs are left out, as ``program_spans.clock_check`` leaves them."""
    lo, hi = window
    mods = sorted(modules, key=lambda e: e[1])
    starts = [s for _, s, _ in mods]
    runs = [{"event": name, "ms": d / 1e6, "ops": []}
            if s >= lo and s + d <= hi and 0 < i < len(mods) - 1 else None
            for i, (name, s, d) in enumerate(mods)]
    for (name, s, _), own in zip(*self_times(ops)):
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and runs[j] is not None and s < mods[j][1] + mods[j][2]:
            runs[j]["ops"].append((name, own))
    by = {}
    for run in filter(None, runs):
        by.setdefault(reduce_trace.program_name(run["event"]),
                      []).append(run)
    return by


def shape_kind(event_name):
    """``fusion bf16[16,4096,3840]``: ``reduce_trace.short_name`` without
    the instruction's number, what the by-hand tables grouped by."""
    parts = reduce_trace.short_name(event_name).split(" ")
    return " ".join([re.sub(r"[.\d]+$", "", parts[0])] + parts[2:])


def program_table(runs, op_names, by_shape=False, groups=GROUPS):
    """One program's runs against its ``{instruction: op_name}``: the
    median per-run milliseconds by scope and by group of ``groups`` (a
    ``vocabulary``), the closure, and the costliest unscoped operations.
    ``op_names`` None: the capture holds no HLO for the program."""
    scope_cache = {}

    def scope(event):
        if event not in scope_cache:
            scope_cache[event] = scope_of(
                (op_names or {}).get(instruction_name(event)), groups)
        return scope_cache[event]

    per_run, totals, unscoped, shapes = [], [], {}, {}
    for run in runs:
        by = {}
        for event, ns in run["ops"]:
            sc = scope(event) or UNSCOPED
            by[sc] = by.get(sc, 0.0) + ns
            if sc == UNSCOPED:
                unscoped[event] = unscoped.get(event, 0.0) + ns
            if by_shape:
                key = (sc, shape_kind(event))
                shapes[key] = shapes.get(key, 0.0) + ns
        per_run.append(by)
        totals.append(sum(by.values()))
    n = len(runs)
    med = lambda xs: harness.median(xs) / 1e6

    def medians(dicts):
        """{key: median ms over the runs}, a run without the key at 0."""
        return {k: med([d.get(k, 0.0) for d in dicts])
                for k in sorted({k for d in dicts for k in d})}

    def grouped(by):
        out = {}
        for sc, ns in by.items():
            g = group_of(sc, groups)
            out[g] = out.get(g, 0.0) + ns
        return out

    by_scope = medians(per_run)
    by_group = medians([grouped(by) for by in per_run])
    if op_names is None:
        why = "the capture holds no HLO for this program"
    elif not any(op_names.values()):
        why = "the program's HLO carries no op_name"
    elif set(by_scope) <= {UNSCOPED}:
        why = ("executable carries no scopes (compiled before the programs "
               "named their parts, or read from a compile cache that was)")
    else:
        why = None
    unscoped_ns = sum(by.get(UNSCOPED, 0.0) for by in per_run)
    out = {
        "runs": n, "module_median_ms": harness.median(
            [r["ms"] for r in runs]),
        "ops_median_ms": med(totals),
        # the closure, in mean milliseconds a run: scopes + unscoped = ops
        "mean_ms": {"ops": sum(totals) / n / 1e6,
                    "scoped": (sum(totals) - unscoped_ns) / n / 1e6,
                    UNSCOPED: unscoped_ns / n / 1e6},
        "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "by_group": by_group, "no_scopes": why,
        "unscoped_top": [[reduce_trace.short_name(k), v / n / 1e6]
                         for k, v in sorted(unscoped.items(),
                                            key=lambda kv: -kv[1])[:5]],
        "_ns": {"ops": sum(totals), UNSCOPED: unscoped_ns},
    }
    if by_shape:
        out["by_shape"] = sorted(((sc, kind, ns / n / 1e6)
                                  for (sc, kind), ns in shapes.items()),
                                 key=lambda r: -r[2])
    return out


def tables(capture, op_names, programs=None, by_shape=False, groups=GROUPS):
    """``{program: program_table}`` for the programs of ``capture`` (what
    ``program_spans.read_capture`` gives, with its ``window``) that ran
    whole inside the window; ``programs`` keeps those named."""
    by = runs_in_window(capture["modules"], capture["ops"],
                        capture["window"])
    out = {}
    for prog, runs in by.items():
        if programs is None or prog in programs:
            # one program, one fingerprint: its runs share the event name
            out[prog] = program_table(
                runs, op_names.get(runs[0]["event"]), by_shape, groups)
    return out


# ------------------------------------------------- what the metrics read
def scopes(run, trace):
    """The traced window's step programs by role (``run["programs"]``'
    names), ``{role: program_table}``, by the vocabulary of the run's
    configuration (``run["scopes"]``: what its file declares), read once a
    run and logged as the ``[scopes]`` line. None without a trace (nothing
    is read then: a stale capture on disk is not this run's) or without a
    capture."""
    if trace is None:
        return None
    return program_spans.kept(run, "scopes", lambda: _scopes(run))


def _scopes(run):
    t0 = time.perf_counter()
    cap, path = program_spans.traced(), program_spans.newest_xplane()
    if not cap or not path:
        harness.log("[scopes] no capture to read")
        return None
    roles = {role: names for role, names in run.get("programs", {}).items()
             if role in STEP_ROLES}
    wanted = {n for names in roles.values() for n in names}
    found = tables(cap, hlo_op_names(path), wanted,
                   groups=vocabulary(run.get("scopes")))
    out = {}
    for role, names in roles.items():
        hit = next((n for n in names if n in found), None)
        if hit:
            out[role] = dict(found[hit], program=hit)
    line = {role: {k: v for k, v in t.items() if not k.startswith("_")}
            for role, t in out.items()}
    line["unscoped_pct"] = unscoped_pct(out)
    line["read_s"] = round(time.perf_counter() - t0, 3)
    line["capture_bytes"] = os.path.getsize(path)
    harness.log(f"[scopes] {json.dumps(line)}")
    return out


def group_ms(run, trace, role, group):
    """Median milliseconds a run of ``role``'s program under ``group``'s
    scopes (a group of ``GROUPS`` or one the run's configuration declares);
    None where the program shows no operation under them."""
    t = (scopes(run, trace) or {}).get(role)
    return t["by_group"].get(group) if t else None


def unscoped_pct(by_role):
    """Operations' self time under no scope over all operations' self
    time, over the step programs' runs in the window, in %: 100 for a
    capture whose programs carry no scopes."""
    ops = sum(t["_ns"]["ops"] for t in (by_role or {}).values())
    if not ops:
        return None
    return 100.0 * sum(t["_ns"][UNSCOPED] for t in by_role.values()) / ops


# ---------------------------------------------------------------- by hand
def render(prog, t):
    lines = [f"{prog}: {t['runs']} runs, module median "
             f"{t['module_median_ms']:.3f} ms, operations "
             f"{t['ops_median_ms']:.3f} ms a run; mean a run: scoped "
             f"{t['mean_ms']['scoped']:.3f} + unscoped "
             f"{t['mean_ms'][UNSCOPED]:.3f} = {t['mean_ms']['ops']:.3f}"]
    if t["no_scopes"]:
        lines.append(f"  NO SCOPES: {t['no_scopes']}")
    lines.append("  by group: " + "  ".join(
        f"{g} {ms:.3f}" for g, ms in t["by_group"].items()))
    lines += [f"  {ms:10.3f}  {sc}" for sc, ms in t["by_scope"].items()]
    lines += [f"  unscoped: {ms:8.3f}  {name}"
              for name, ms in t["unscoped_top"]]
    for sc in t["by_scope"] if "by_shape" in t else ():
        rows = [r for r in t["by_shape"] if r[0] == sc]
        lines.append(f"  -- {sc}")
        lines += [f"  {ms:10.3f}  {kind}" for _, kind, ms in rows[:12]]
        if rows[12:]:
            lines.append(f"  {sum(r[2] for r in rows[12:]):10.3f}  "
                         f"({len(rows) - 12} further kinds)")
    return "\n".join(lines)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="a trace directory or an .xplane.pb")
    ap.add_argument("--program", help="one program (jit_step); default all")
    ap.add_argument("--by", choices=("scope", "shape"), default="scope")
    ap.add_argument("--config", help="a configuration's file: the scopes it "
                    "declares are read as its runs read them")
    args = ap.parse_args(argv)
    groups = vocabulary(harness.load_json(args.config).get("scopes")
                        if args.config else None)
    path = (reduce_trace.find_xplane(args.path)
            if os.path.isdir(args.path) else args.path)
    cap = program_spans.read_capture(path)
    if cap is None:
        raise SystemExit(f"{path}: no profile_start_time, not a capture")
    cap["window"] = cap.pop("marker") or reduce_trace.steady_window(
        cap["modules"])
    if cap["window"] is None:
        raise SystemExit(f"{path}: no window (no marker, too few runs)")
    found = tables(cap, hlo_op_names(path),
                   args.program and {args.program}, args.by == "shape",
                   groups)
    if not found:
        raise SystemExit(f"{path}: no whole run of "
                         f"{args.program or 'any program'} in the window")
    for prog, t in sorted(found.items(),
                          key=lambda kv: -kv[1]["mean_ms"]["ops"]):
        print(render(prog, t))
    return 0


if __name__ == "__main__":
    sys.exit(main())
