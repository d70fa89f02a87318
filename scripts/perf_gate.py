#!/usr/bin/env python3
"""CI perf gate over ``bench_history.jsonl`` serving rows.

Reads the TWO newest *comparable* serving rows (same metric, same
workload signature — request count, arrival rate, template config) and
fails (exit 1) when the newer row regressed by more than
``--threshold`` (default 20%) against the previous one on ANY gated
measure: p99 TTFT, p99 inter-token latency (the per-request mean
decode gap — the steady-state streaming experience TTFT cannot see),
or the engine's goodput (delivered tokens per device-second from the
usage ledger — higher is better, so the regression direction flips).
Anything that prevents a comparison — no history, a single row,
unparsable lines, rows without the measurement — exits 0 with an
explanation: the gate blocks measured regressions, it never blocks the
first run of a new workload, rows predating a field (inter-token,
goodput) gate on what both rows actually measured, and a row whose
only workload-matching history ran on a different device kind is
skipped with a printed notice — a CPU-fallback round never gates
against a TPU baseline (or vice versa).

Serving rows come from ``bench.py --serving`` (percentiles under
``detail.engine.{ttft,inter_token}.p99``), ``bench.py --serving
--shared-prefix`` (``detail.cached.*``), ``bench.py --serving
--speculative`` (``detail.spec.*`` — the speculative path's
inter-token p99 is exactly the measure speculation exists to improve,
so it gates like any other), and ``bench.py --serving --tp``
(``detail.sharded.*`` — the tensor-parallel engine's latencies, gated
against the previous sharded run of the same mesh width), and
``bench.py --serving --shared-prefix --working-set N``
(``detail.tiered.*`` plus ``detail.headline.tiered_hit_rate`` — the
tiered prefix-cache sweep additionally gates the headline hit rate,
higher-is-better, and the tiered leg's p50 TTFT), and ``bench.py
--serving --fleet N`` (``detail.affinity.*`` — the multi-replica A/B
additionally gates the fleet-wide prefix hit rate run-to-run, the
mean per-request ``rpc_submit`` hop from the fleet-tracing
decomposition — the pipe-RPC overhead must not creep — and the
affinity-vs-round-robin TTFT p50 speedup as an absolute floor: the
speedup is itself a within-run A/B ratio, so it must stay >= 1.0
rather than within a band of the previous row's value. Fleet rows
also carry the telemetry-plane stamps: ``detail.capacity.headroom``
bands run-to-run — the capacity model's sustainable-rate estimate
must not silently collapse — and ``detail.slo_budget.remaining_min``
floors absolutely at 0.5: a calm storm that spends half its SLO
error budget has a latency tail, not noise), and
``bench.py --serving --quantized`` (``detail.quantized.*`` — the
int8-KV/int8-weight engine's latencies gate run-to-run like any
other leg; the fp leg rides along as ``detail.fp_baseline`` under a
name deliberately OUTSIDE the path precedence so the quantized leg is
what gates. The quantized row additionally carries the numerics
quality gate, enforced as absolute ceilings rather than run-to-run
bands: the per-token logit divergence relative to the fp logit scale
must stay under ``_QUANT_LOGIT_DIV_CEILING`` and the speculative
acceptance-rate delta between the int8-KV and fp-KV engines — signed,
one-sided: only an acceptance LOSS gates — must stay under
``_QUANT_ACCEPT_DELTA_CEILING``; a numerics regression fails CI, not
prod), and ``bench.py --serving --qos`` (``detail.qos.*`` — the QoS
storm's high-class TTFT bands run-to-run like any other leg, and the
row additionally gates three within-run verdicts: the storm-vs-
uncontended high-class TTFT p50 ratio as an absolute ceiling
(``_QOS_TTFT_P50_RATIO_CEILING`` — the ratio is already a within-run
A/B, so like the fleet speedup it gates against its own meaningful
scale, not as a band around the previous row's equally-noisy ratio;
the p99 ratio rides along ungated, a max over a handful of samples),
every QoS mechanism having actually fired (shed / preempted /
rate-limited counts > 0 — a storm that exercised nothing measured
nothing), and outcome conservation (every submission ended in exactly
one terminal state — a silent drop is a correctness failure, not a
perf number)); all eight shapes are understood.

Two incident-autopilot gates ride along (skip-if-absent for rows
predating the fields): the newest plain ``--serving`` row's
``detail.incidents.count`` must be ZERO (detector warmup + hysteresis
must keep a calm Poisson storm incident-free), and the newest
``serve.py --chaos`` drill row (``detail.chaos_drill``) must show
every fault class — slo / stall / crash — converted into >= 1
classified incident bundle. Stdlib only — runnable from any CI step
without the package installed.

Usage::

    python scripts/perf_gate.py [--history bench_history.jsonl]
                                [--threshold 0.20] [--metric NAME]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

#: detail keys that hold a serving result with a ``ttft`` percentile
#: block, in precedence order (--serving vs --serving --shared-prefix
#: vs --serving --speculative vs --serving --tp vs --serving
#: --shared-prefix --working-set vs --serving --fleet vs --serving
#: --quantized vs --serving --qos — each row shape carries exactly
#: one; the quantized row's fp leg is named ``fp_baseline`` and the
#: qos row's contention-free leg ``uncontended`` so they stay out of
#: this scan)
_TTFT_PATHS = ("engine", "cached", "spec", "sharded", "tiered",
               "affinity", "quantized", "qos")

#: absolute quality ceilings for --serving --quantized rows: int8
#: numerics must stay this close to fp on the same seeds. Ceilings,
#: not run-to-run bands — a quality number has a meaningful absolute
#: scale, unlike a latency that shifts with the host.
_QUANT_LOGIT_DIV_CEILING = 0.25
_QUANT_ACCEPT_DELTA_CEILING = 0.05

#: absolute ceiling for --serving --qos rows: under the mixed-priority
#: storm the high class's MEDIAN TTFT may cost at most this multiple
#: of its uncontended self (the issue's acceptance bar). The p50 is
#: the gated statistic — the p99 over a handful of high-class samples
#: is a max, and host jitter swings it ±50% run to run.
_QOS_TTFT_P50_RATIO_CEILING = 1.25


def _p99(row: dict, measure: str):
    detail = row.get("detail") or {}
    for key in _TTFT_PATHS:
        block = detail.get(key) or {}
        p99 = (block.get(measure) or {}).get("p99")
        if p99 is not None:
            return float(p99)
    return None


def ttft_p99(row: dict):
    """The row's p99 TTFT in seconds, or None when the row carries no
    TTFT measurement (training rows, failed runs)."""
    return _p99(row, "ttft")


def inter_token_p99(row: dict):
    """The row's p99 per-request mean inter-token gap in seconds, or
    None (rows predating the measurement, training rows)."""
    return _p99(row, "inter_token")


def goodput_tokens_per_device_second(row: dict):
    """The row's engine goodput (delivered tokens per device-dispatch
    second, from the usage ledger), or None for rows predating the
    field. Higher is better — the gate inverts the direction."""
    detail = row.get("detail") or {}
    for key in _TTFT_PATHS:
        block = detail.get(key) or {}
        g = (block.get("goodput") or {}).get("tokens_per_device_second")
        if g is not None:
            return float(g)
    return None


def tiered_hit_rate(row: dict):
    """The tiered prefix-cache sweep row's headline hit rate (host
    tier ON, at the deepest working-set point past the device budget),
    or None for every other row shape and for rows predating the
    sweep. Higher is better — the gate inverts the direction."""
    head = (row.get("detail") or {}).get("headline") or {}
    hr = head.get("tiered_hit_rate")
    return float(hr) if hr is not None else None


def tiered_ttft_p50(row: dict):
    """The tiered row's p50 TTFT in seconds (the latency the promoted
    rows must keep buying), or None for rows without a tiered leg."""
    block = (row.get("detail") or {}).get("tiered") or {}
    p50 = (block.get("ttft") or {}).get("p50")
    return float(p50) if p50 is not None else None


def fleet_ttft_speedup(row: dict):
    """The fleet A/B row's affinity-vs-round-robin client TTFT p50
    speedup (>1.0: content-aware routing lands first tokens sooner),
    or None for every other row shape. Keyed off the ``affinity`` leg
    block — shared-prefix rows carry a ``ttft_p50_speedup`` too, but
    it measures cache-on-vs-off, not routing. Gated as a floor (must
    stay >= 1.0), not run-to-run: the value is already a within-run
    A/B ratio, so comparing it against the previous row's ratio
    double-normalizes two noisy small-sample p50s."""
    detail = row.get("detail") or {}
    if not detail.get("affinity"):
        return None
    sp = detail.get("ttft_p50_speedup")
    return float(sp) if sp is not None else None


def fleet_hit_rate(row: dict):
    """The fleet A/B row's fleet-wide prefix hit rate on the affinity
    leg (hits over lookups summed across replicas), or None for every
    other row shape and for rows predating the field. Higher is
    better — the gate inverts the direction."""
    fl = ((row.get("detail") or {}).get("affinity") or {}).get("fleet") \
        or {}
    hr = fl.get("hit_rate")
    return float(hr) if hr is not None else None


def fleet_rpc_submit_mean(row: dict):
    """The fleet A/B row's mean per-request ``rpc_submit`` hop (the
    parent->worker pipe submit cost from the hop decomposition,
    affinity leg) — the fleet-tracing overhead signal banded
    run-to-run. None for every other row shape and for rows predating
    the ``hops`` stamp."""
    hops = ((row.get("detail") or {}).get("affinity") or {}
            ).get("hops") or {}
    v = hops.get("rpc_submit")
    return float(v) if v is not None else None


def fleet_capacity_headroom(row: dict):
    """The fleet A/B row's capacity-model headroom (1 - observed/
    sustainable request rate on the affinity leg, fleet-wide), or
    None for every other row shape and for rows predating the
    ``detail.capacity`` stamp. Banded run-to-run, higher is better:
    the same calm storm on the same hardware must keep the same
    slack — a collapsing headroom means the sustainable-rate estimate
    (device-seconds + host-seconds per request) regressed."""
    cap = (row.get("detail") or {}).get("capacity")
    if not isinstance(cap, dict) or not cap.get("ready"):
        return None
    hr = cap.get("headroom")
    return float(hr) if hr is not None else None


#: a calm fleet storm must keep at least half its SLO error budget —
#: below this, the latency tail is real, not sampling noise
_FLEET_BUDGET_REMAINING_FLOOR = 0.5


def fleet_budget_remaining(row: dict):
    """The fleet A/B row's worst per-replica SLO error-budget
    remaining fraction (``detail.slo_budget.remaining_min`` — the
    affinity leg's generous-threshold TTFT objective), or None for
    every other row shape and for rows predating the field. Gated as
    an absolute floor, not run-to-run: remaining is already a
    normalized fraction of the budget window, and a calm storm should
    sit near 1.0."""
    sb = (row.get("detail") or {}).get("slo_budget")
    if not isinstance(sb, dict):
        return None
    rm = sb.get("remaining_min")
    return float(rm) if rm is not None else None


def quantized_logit_div_rel(row: dict):
    """The quantized A/B row's quality-gate headline: max per-token
    logit divergence of the int8 engine vs fp on identical seeds,
    relative to the fp logit scale (scale-free, so one ceiling holds
    across model sizes). None for every other row shape and for rows
    predating the field."""
    detail = row.get("detail") or {}
    if not detail.get("quantized"):
        return None
    dv = (detail.get("quality") or {}).get("logit_div_rel")
    return float(dv) if dv is not None else None


def quantized_acceptance_delta(row: dict):
    """The quantized A/B row's speculative acceptance-rate delta —
    SIGNED, fp-KV minus int8-KV under the same int8 draft and
    workload, so positive means quantizing the cache LOST acceptance.
    The ceiling is one-sided on purpose: shared rounding noise
    correlates the int8 draft with an int8-cached target, so
    acceptance typically rises under quantization — a win the gate
    must not punish. None for every other row shape and for rows
    predating the field."""
    detail = row.get("detail") or {}
    if not detail.get("quantized"):
        return None
    dv = (detail.get("quality") or {}).get("acceptance_delta")
    return float(dv) if dv is not None else None


def qos_ttft_p50_ratio(row: dict):
    """The QoS storm row's storm-vs-uncontended high-class TTFT p50
    ratio (~1.0: shedding + preemption held the top class at its
    uncontended self), or None for every other row shape. Keyed off
    the ``qos`` leg block — gated as an absolute ceiling
    (``_QOS_TTFT_P50_RATIO_CEILING``), not run-to-run: the value is
    already a within-run A/B ratio with a meaningful scale."""
    detail = row.get("detail") or {}
    if not detail.get("qos"):
        return None
    ratio = detail.get("high_ttft_p50_ratio")
    return float(ratio) if ratio is not None else None


def qos_mechanism_counts(row: dict):
    """The QoS storm row's {shed, preempted, rate_limited} counts, or
    None for every other row shape. Each must be > 0: the storm is
    BUILT to trip all three mechanisms, so a zero means the workload
    drifted and the headline ratio no longer measures the QoS stack
    at work."""
    detail = row.get("detail") or {}
    if not detail.get("qos"):
        return None
    return {k: detail.get(k) for k in
            ("shed", "preempted", "rate_limited")}


def qos_conservation_ok(row: dict):
    """The QoS storm row's outcome-conservation verdict (every
    submission ended in exactly one of finished / shed / rate-limited
    / cancelled / timed-out, client-side AND engine-side), or None for
    every other row shape / rows predating the field."""
    detail = row.get("detail") or {}
    if not detail.get("qos"):
        return None
    return detail.get("conservation_ok")


#: fault classes the ``serve.py --chaos`` drill must each convert
#: into exactly >= 1 correctly-classified incident bundle (the
#: incident-autopilot acceptance bar)
_CHAOS_REQUIRED_KINDS = ("slo", "stall", "crash")


def calm_incident_count(row: dict):
    """The calm serving row's incident count — a plain ``bench.py
    --serving`` Poisson replay stamps ``detail.incidents`` with
    ``calm: true``, and a healthy storm must record ZERO incidents
    (warmup + hysteresis exist so ordinary load never trips the
    detectors). None for rows predating the field and for
    non-calm row shapes (the qos storm legitimately sheds)."""
    inc = (row.get("detail") or {}).get("incidents")
    if not isinstance(inc, dict) or not inc.get("calm"):
        return None
    c = inc.get("count")
    return int(c) if c is not None else None


def chaos_incident_kinds(row: dict):
    """The chaos-drill row's per-kind incident counts (``serve.py
    --chaos`` appends one ``serving_chaos_incidents`` row per drill),
    or None for every other row shape and for rows predating the
    field. Each fault class in ``_CHAOS_REQUIRED_KINDS`` must have
    minted >= 1 bundle — a drill that stops converting faults into
    classified incidents has lost its detection coverage."""
    detail = row.get("detail") or {}
    if not detail.get("chaos_drill"):
        return None
    inc = detail.get("incidents")
    if not isinstance(inc, dict):
        return None
    return {k: int(v) for k, v in (inc.get("by_kind") or {}).items()}


def signature(row: dict):
    """What must match for two rows to be comparable: the metric name
    plus the workload shape (request count, rate, template config,
    slot/staging widths). Device intentionally included — a CPU
    fallback row must never gate against a TPU row."""
    detail = row.get("detail") or {}
    wl = detail.get("workload") or {}
    return (row.get("metric"), detail.get("device"),
            tuple(sorted((k, v) for k, v in wl.items()
                         if isinstance(v, (int, float, str)))))


def load_rows(path: str):
    rows = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rows.append(json.loads(ln))
            except ValueError:
                continue  # torn line: skip, never crash the gate
    return rows


def main(argv=None) -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser(
        description="Fail CI on a serving p99-TTFT regression between "
                    "the two newest comparable bench_history rows.")
    p.add_argument("--history",
                   default=os.environ.get(
                       "BIGDL_BENCH_HISTORY",
                       os.path.join(here, "bench_history.jsonl")))
    p.add_argument("--threshold", type=float, default=0.20,
                   help="allowed fractional p99-TTFT regression "
                        "(0.20 = +20%%)")
    p.add_argument("--metric", default=None,
                   help="only gate rows with this metric name "
                        "(default: any serving row carrying a TTFT)")
    p.add_argument("--lint", dest="lint", action="store_true",
                   default=None,
                   help="run the graftlint --changed preflight before "
                        "gating (default: only for the repo's own "
                        "history file)")
    p.add_argument("--no-lint", dest="lint", action="store_false",
                   help="skip the graftlint preflight")
    args = p.parse_args(argv)

    # static-analysis preflight: a perf row must not buy its numbers
    # with a new jit hazard or race. Runs by default only for the
    # repo's own history (tests/tools gating ad-hoc histories pass
    # --history and keep their exact exit-code contracts); emits the
    # graftlint_report.json CI artifact next to the history file.
    default_history = os.path.join(here, "bench_history.jsonl")
    want_lint = (args.lint if args.lint is not None
                 else os.path.abspath(args.history)
                 == os.path.abspath(default_history))
    if want_lint:
        report = os.path.join(here, "graftlint_report.json")
        r = subprocess.run(
            [sys.executable, os.path.join(here, "scripts",
                                          "graftlint.py"),
             "--changed", "--report", report],
            cwd=here, capture_output=True, text=True)
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            print("[perf-gate] FAIL: graftlint preflight found new "
                  f"non-baselined findings (report: {report})")
            return 1
        print(f"[perf-gate] graftlint preflight clean "
              f"(report: {report})")

    try:
        rows = load_rows(args.history)
    except OSError as e:
        print(f"[perf-gate] no history ({e}); nothing to gate")
        return 0

    serving = [r for r in rows if ttft_p99(r) is not None
               and (args.metric is None or r.get("metric") == args.metric)]
    if not serving:
        print("[perf-gate] no serving rows with a TTFT in "
              f"{args.history}; nothing to gate")
        return 0

    newest = serving[-1]
    sig = signature(newest)
    prev = next((r for r in reversed(serving[:-1])
                 if signature(r) == sig), None)
    if prev is None:
        # a workload match on DIFFERENT hardware is not a regression
        # baseline — say so explicitly (a CPU-fallback round after a TPU
        # round would otherwise read as a mystery "first run")
        cross = next((r for r in reversed(serving[:-1])
                      if (signature(r)[0], signature(r)[2])
                      == (sig[0], sig[2])), None)
        if cross is not None:
            print(f"[perf-gate] skip: newest {newest.get('metric')} row "
                  f"ran on {sig[1]!r} but the only comparable history is "
                  f"from {signature(cross)[1]!r} — cross-device_kind "
                  "comparison refused; gate passes")
            return 0
        print(f"[perf-gate] no earlier row comparable to "
              f"{newest.get('metric')} (signature {sig}); first run "
              "passes")
        return 0

    span = f"[{prev.get('ts', '?')} -> {newest.get('ts', '?')}]"
    failed = False
    # (label, reader, unit scale, unit, higher_is_better)
    measures = (
        ("p99 TTFT", ttft_p99, 1e3, "ms", False),
        ("p99 inter-token", inter_token_p99, 1e3, "ms", False),
        ("goodput", goodput_tokens_per_device_second, 1.0,
         "tok/dev-s", True),
        # tiered prefix-cache sweep rows only (skip-if-absent, like
        # every field younger than the history): the host tier must
        # keep buying its hit rate AND the promoted rows must keep
        # buying their TTFT
        ("tiered hit rate", tiered_hit_rate, 100.0, "%", True),
        ("tiered p50 TTFT", tiered_ttft_p50, 1e3, "ms", False),
        # fleet A/B rows only (skip-if-absent): the fleet must keep
        # buying its affinity hit rate (deterministic per workload, so
        # run-to-run ratio gating is stable)
        ("fleet hit rate", fleet_hit_rate, 100.0, "%", True),
        # the per-hop stamp: the pipe-RPC submit cost must not creep
        ("fleet rpc_submit mean", fleet_rpc_submit_mean, 1e3, "ms",
         False),
        # the capacity-model stamp: the calm storm's fleet headroom
        # must not collapse run-to-run (a shrinking sustainable-rate
        # estimate is a capacity-model regression, not load)
        ("fleet capacity headroom", fleet_capacity_headroom, 100.0,
         "%", True),
    )
    for label, reader, scale, unit, higher_better in measures:
        new_v, old_v = reader(newest), reader(prev)
        if new_v is None or old_v is None:
            # older rows predate the field (inter-token, goodput):
            # gate on what both rows actually measured
            print(f"[perf-gate] skip: {label} absent from one of the "
                  f"compared rows {span}")
            continue
        ratio = new_v / old_v if old_v else float("inf")
        verdict = (f"{label} {old_v * scale:.2f}{unit} -> "
                   f"{new_v * scale:.2f}{unit} ({ratio:.3f}x) for "
                   f"{newest.get('metric')} {span}")
        # a regression is a ratio above budget for latencies, below
        # the inverse budget for throughput-like measures
        regressed = (ratio < 1.0 / (1.0 + args.threshold)
                     if higher_better else
                     ratio > 1.0 + args.threshold)
        if regressed:
            print(f"[perf-gate] FAIL: {verdict} exceeds the "
                  f"+{args.threshold:.0%} budget")
            failed = True
        else:
            print(f"[perf-gate] ok: {verdict} within the "
                  f"+{args.threshold:.0%} budget")
    # fleet A/B rows: the speedup is already a within-run ratio
    # (affinity vs round-robin on the same storm), so it gates as an
    # absolute floor — affinity must still beat round-robin — instead
    # of a band around the previous row's equally-noisy ratio
    sp = fleet_ttft_speedup(newest)
    if sp is not None:
        verdict = (f"fleet TTFT speedup {sp:.3f}x for "
                   f"{newest.get('metric')} {span}")
        if sp < 1.0:
            print(f"[perf-gate] FAIL: {verdict} — affinity routing no "
                  "longer beats round-robin (floor 1.0x)")
            failed = True
        else:
            print(f"[perf-gate] ok: {verdict} clears the 1.0x floor")
    # fleet A/B rows: the SLO error budget is a normalized fraction
    # with its own meaningful scale (1.0 = untouched), so the calm
    # storm gates as an absolute floor rather than run-to-run
    br = fleet_budget_remaining(newest)
    if br is not None:
        verdict = (f"fleet SLO budget remaining {br:.3f} for "
                   f"{newest.get('metric')} {span}")
        if br < _FLEET_BUDGET_REMAINING_FLOOR:
            print(f"[perf-gate] FAIL: {verdict} — the calm storm "
                  f"spent past the {_FLEET_BUDGET_REMAINING_FLOOR} "
                  "floor; the TTFT tail breaches the objective")
            failed = True
        else:
            print(f"[perf-gate] ok: {verdict} clears the "
                  f"{_FLEET_BUDGET_REMAINING_FLOOR} floor")
    # QoS storm rows: the p50 ratio is a within-run A/B with its own
    # meaningful scale, so it gates as an absolute ceiling; the
    # mechanism counts and conservation verdict are deterministic
    # pass/fail facts about the run, not trends
    qr = qos_ttft_p50_ratio(newest)
    if qr is not None:
        verdict = (f"qos high-class TTFT p50 ratio {qr:.3f}x for "
                   f"{newest.get('metric')} {span}")
        if qr > _QOS_TTFT_P50_RATIO_CEILING:
            print(f"[perf-gate] FAIL: {verdict} exceeds the absolute "
                  f"{_QOS_TTFT_P50_RATIO_CEILING}x ceiling — the storm "
                  "is pricing the high class above its uncontended "
                  "self")
            failed = True
        else:
            print(f"[perf-gate] ok: {verdict} under the absolute "
                  f"{_QOS_TTFT_P50_RATIO_CEILING}x ceiling")
    counts = qos_mechanism_counts(newest)
    if counts is not None:
        for name, n in counts.items():
            if not n:
                print(f"[perf-gate] FAIL: qos storm fired 0 "
                      f"{name} for {newest.get('metric')} {span} — the "
                      "workload no longer exercises that mechanism, so "
                      "the headline ratio measures nothing")
                failed = True
            else:
                print(f"[perf-gate] ok: qos storm fired {n} {name}")
    cons = qos_conservation_ok(newest)
    if cons is not None:
        if cons is not True:
            print(f"[perf-gate] FAIL: qos outcome conservation broke "
                  f"for {newest.get('metric')} {span} — a submission "
                  "ended in zero or two terminal states")
            failed = True
        else:
            print("[perf-gate] ok: qos outcomes conserve (every "
                  "submission reached exactly one terminal state)")
    # quantized A/B rows: numerics quality gates as absolute ceilings
    # (a quality number has a meaningful scale of its own; gating it
    # against the previous row would let a slow drift walk the
    # numerics off a cliff one ok-sized step at a time)
    for label, reader, ceiling in (
            ("quantized logit divergence", quantized_logit_div_rel,
             _QUANT_LOGIT_DIV_CEILING),
            ("quantized spec acceptance delta",
             quantized_acceptance_delta, _QUANT_ACCEPT_DELTA_CEILING)):
        qv = reader(newest)
        if qv is None:
            continue
        verdict = (f"{label} {qv:.4f} for {newest.get('metric')} "
                   f"{span}")
        if qv > ceiling:
            print(f"[perf-gate] FAIL: {verdict} exceeds the absolute "
                  f"{ceiling} ceiling")
            failed = True
        else:
            print(f"[perf-gate] ok: {verdict} under the absolute "
                  f"{ceiling} ceiling")
    # incident autopilot, calm side: a plain Poisson replay must have
    # recorded zero incidents — detector warmup + hysteresis exist
    # precisely so ordinary load never trips them (skip-if-absent for
    # rows predating the field)
    cc = calm_incident_count(newest)
    if cc is not None:
        if cc > 0:
            print(f"[perf-gate] FAIL: calm serving storm recorded "
                  f"{cc} incident(s) for {newest.get('metric')} {span}"
                  " — the anomaly detectors fire on healthy load")
            failed = True
        else:
            print("[perf-gate] ok: calm serving storm recorded zero "
                  "incidents")
    # incident autopilot, chaos side: the newest drill row (no TTFT,
    # so it lives outside the serving-row selection above) must show
    # every fault class converted into >= 1 classified bundle
    chaos_row = next((r for r in reversed(rows)
                      if chaos_incident_kinds(r) is not None), None)
    if chaos_row is not None:
        kinds = chaos_incident_kinds(chaos_row)
        cspan = f"[{chaos_row.get('ts', '?')}]"
        for kind in _CHAOS_REQUIRED_KINDS:
            n = kinds.get(kind, 0)
            if n < 1:
                print(f"[perf-gate] FAIL: chaos drill minted 0 "
                      f"kind={kind} incidents {cspan} — the "
                      f"{kind} fault class is no longer detected and "
                      "captured")
                failed = True
            else:
                print(f"[perf-gate] ok: chaos drill minted {n} "
                      f"kind={kind} incident(s) {cspan}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
