"""The fleet A/B: prefix-affinity routing vs round-robin, plus the
mid-storm drain drill.

``run_fleet_comparison`` is the hermetic multi-process bench behind
``bench.py --serving --fleet N``: one Poisson storm over shared-prefix
templates is replayed through a fleet of ``N`` spawn-worker replicas
(each its own process, model, engine, prefix trie) twice —

- **affinity**: the ``PrefixAffinityRouter`` hashes each prompt's
  first chunk onto the ring, so every template's KV accumulates on
  exactly one replica;
- **round_robin**: the control leg — the same storm sprayed evenly,
  every replica forced to cache every template.

Each replica's prefix pool is sized to hold its affinity SHARE of the
templates (the ring's largest per-replica template count, +1 slack —
capacity provisioned for content-aware routing), so the control leg
LRU-thrashes exactly the way a fleet of budget-bound tries does when
routing ignores content: the affinity leg wins on fleet-wide hit rate
AND on client TTFT p50 (a hit prefills only the random tail; a miss
prefills the whole template). Both legs' outputs are checked token-identical to a
single in-process reference engine replaying the same workload on the
same seed — routing must never change what anyone decodes.

The third leg re-runs the affinity storm and, mid-storm, DRAINS one
replica (the degraded-replica drill: router routes away, in-flight
finishes) and later rejoins it — zero lost requests and the same
token parity is the acceptance bar.
"""

from __future__ import annotations

import threading
import time
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from bigdl_tpu.observability.fleettrace import (
    FLEET_HOPS, hop_breakdown,
)
from bigdl_tpu.serving.benchmark import (
    _append_itl, _engine_replay, _percentiles, _replay,
    shared_prefix_workload,
)
from bigdl_tpu.serving.fleet.router import PrefixAffinityRouter
from bigdl_tpu.serving.fleet.supervisor import ReplicaSupervisor
from bigdl_tpu.serving.fleet.worker import spawn_worker_fleet

__all__ = ["run_fleet_comparison"]

#: the bench's model recipe — every worker builds exactly this (same
#: seed => bit-identical params => any replica's greedy output is the
#: fleet's greedy output)
_MODEL = dict(vocab_size=64, embed_dim=16, num_heads=4, num_kv_heads=2,
              num_layers=2, max_len=96, use_rope=True)


def _fleet_replay(sup: ReplicaSupervisor, workload,
                  on_submitted=None) -> dict:
    """Open-loop replay of ``workload`` through ``sup.submit`` (the
    shared ``_replay`` pacer). TTFT is CLIENT-side — routing + IPC +
    queue + prefill, stamped at first-token receipt in this process.
    ``on_submitted(i)`` fires after the i-th request is handed to a
    replica (the drain drill's trigger point). Each finished request
    is decomposed into the seven fleet hops (``hop_breakdown`` on the
    supervisor-measured route/rpc_submit timings plus the replica
    timeline); the leg block reports the per-hop MEANS under
    ``hops``."""
    ttft: List[float] = []
    itl: List[float] = []
    rows: Dict[int, list] = {}
    count = {"n": 0}
    t0s: Dict[int, float] = {}
    hop_sums = dict.fromkeys(FLEET_HOPS, 0.0)
    hop_n = [0]
    lock = threading.Lock()

    def submit(req):
        t0 = time.monotonic()
        routed = sup.submit(req["prompt"], req["n"],
                            tenant=req.get("tenant"))
        with lock:
            t0s[id(req)] = t0
            count["n"] += 1
            i = count["n"]
        if on_submitted is not None:
            on_submitted(i)
        return routed

    def collect(routed, req):
        toks = routed.handle.result(timeout=300)
        done = time.monotonic()
        h = routed.handle
        with lock:
            rows[id(req)] = [int(t) for t in toks]
            if h.first_token_at is not None:
                ttft.append(h.first_token_at - h.submitted_at)
            _append_itl(itl, h)
            t0 = t0s.pop(id(req), None)
            if t0 is not None:
                tl = h.timeline() if hasattr(h, "timeline") else {}
                hops = hop_breakdown(tl or {}, routed.route_s,
                                     routed.rpc_submit_s, done - t0)
                for k, v in hops.items():
                    hop_sums[k] += v
                hop_n[0] += 1
        return len(toks)

    res = _replay(workload, submit, collect)
    res["ttft"] = _percentiles(ttft)
    res["inter_token"] = _percentiles(itl)
    res["rows"] = rows
    res["hops"] = {k: (hop_sums[k] / hop_n[0]) for k in FLEET_HOPS} \
        if hop_n[0] else None
    return res


def _capacity_stamp(cap: dict) -> dict:
    """Compress ``fleet_capacity()`` into the bench-row block
    ``perf_gate`` bands: fleet headroom/replicas-needed plus each
    replica's role split (prefill vs decode device-wall fractions)."""
    roles = {}
    for rid, rc in (cap.get("replicas") or {}).items():
        r = rc.get("roles") or {}
        if r:
            roles[rid] = {
                "bound": r.get("bound"),
                "prefill_fraction":
                    (r.get("prefill") or {}).get("wall_fraction"),
                "decode_fraction":
                    (r.get("decode") or {}).get("wall_fraction"),
                "disaggregation_speedup_bound":
                    r.get("disaggregation_speedup_bound"),
            }
    return {
        "ready": bool(cap.get("ready")),
        "headroom": cap.get("headroom"),
        "utilization": cap.get("utilization"),
        "observed_rps": cap.get("observed_rps"),
        "sustainable_rps": cap.get("sustainable_rps"),
        "replicas_needed": cap.get("replicas_needed"),
        "roles": roles or None,
    }


def _budget_stamp(budgets: dict) -> dict:
    """Compress the per-replica SLO error-budget ledgers into the
    bench-row block ``perf_gate`` floors: the fleet-worst remaining
    fraction plus the per-replica minima."""
    per = {rid: led.get("remaining_min")
           for rid, led in budgets.items() if isinstance(led, dict)}
    known = [v for v in per.values() if v is not None]
    return {
        "remaining_min": min(known) if known else None,
        "per_replica": per or None,
    }


def _leg(workload, n_replicas, engine_cfg, seed, policy, chunk, log,
         label, drain_at: Optional[int] = None,
         rejoin_at: Optional[int] = None, victim: str = "r0") -> dict:
    """One fleet leg: fresh worker processes (cold tries — the legs
    must not share cache state), warm each replica's executables
    outside the measurement, replay, aggregate, tear down."""
    replicas = spawn_worker_fleet(
        n_replicas, _MODEL, engine=engine_cfg, seed=seed)
    sup = ReplicaSupervisor(replicas, policy=policy, chunk=chunk,
                            poll_interval=0.05,
                            fleet_name=f"bench-{label}")
    log(f"[fleet-bench] {label}: spawning {n_replicas} workers...")
    with sup:
        res_device = replicas[0].device
        warm = np.arange(1, 9, dtype=np.int32)
        for rep in replicas:
            rep.submit(warm, 4).result(timeout=300)

        def trigger(i):
            if drain_at is not None and i == drain_at:
                log(f"[fleet-bench] {label}: draining {victim} "
                    f"mid-storm (request {i})")
                sup.drain(victim, reason="degraded")
            if rejoin_at is not None and i == rejoin_at:
                sup.rejoin(victim)

        log(f"[fleet-bench] {label}: replaying "
            f"{len(workload)} requests...")
        res = _fleet_replay(
            sup, workload,
            on_submitted=trigger if drain_at is not None else None)
        stats = sup.stats()
        # capacity + error-budget read must happen before the
        # supervisor exits (workers are gone after teardown)
        cap = sup.fleet_capacity()
        budgets = cap.pop("slo_budget", None) or {}
        res["capacity"] = _capacity_stamp(cap)
        res["slo_budget"] = _budget_stamp(budgets)
        res["fleet"] = {
            "policy": policy,
            "replicas": n_replicas,
            "prefix_cache": stats["prefix_cache"],
            "hit_rate": stats["prefix_cache"]["hit_rate"],
            "routing": {k: stats["routing"][k]
                        for k in ("decisions", "per_replica",
                                  "draining")},
            "per_replica_finished": {
                rid: (s.get("finished") if isinstance(s, dict)
                      else None)
                for rid, s in stats["replicas"].items()},
        }
        if drain_at is not None:
            res["fleet"]["drained"] = victim
    res["device"] = res_device
    return res


def run_fleet_comparison(n_replicas: int = 2, n_requests: int = 36,
                         rate_hz: float = 30.0,
                         n_templates: Optional[int] = None,
                         template_len: int = 48, max_slots: int = 4,
                         prefill_chunk: int = 8, prefill_rows: int = 2,
                         seed: int = 0, model_seed: int = 7,
                         drain_drill: bool = True,
                         log=print) -> dict:
    """The ``--serving --fleet N`` A/B. Returns the affinity and
    round-robin leg blocks (client TTFT / latency / inter-token
    percentiles, throughput, fleet hit rate, routing tallies), the
    drain-drill block, the headline ratios, the affinity leg's
    capacity/what-if stamp (fleet headroom, replicas-needed, per-role
    device-wall split) and SLO error-budget floor (worst
    ``remaining_min`` across replicas — ``perf_gate`` gates calm runs
    on it), and the token-parity verdict against a single-replica
    reference replay."""
    if not 2 <= n_replicas <= 4:
        raise ValueError("the fleet bench runs 2-4 replicas")
    if n_templates is None:
        n_templates = 2 * n_replicas
    # pick a workload whose template heads SPREAD over the ring — the
    # A/B measures the routing policy, not one seed's hash luck. The
    # search only hashes prompt heads (no engine), is deterministic,
    # and the chosen seed is recorded in the result's workload block
    probe = PrefixAffinityRouter(
        [f"r{i}" for i in range(n_replicas)], chunk=prefill_chunk)
    for wl_seed in range(seed, seed + 64):
        workload = shared_prefix_workload(
            n_requests, rate_hz, _MODEL["vocab_size"],
            n_templates=n_templates, template_len=template_len,
            tail_lens=(2, 6), decode_lens=(4, 10), seed=wl_seed,
            template_order="random")
        keys = {probe.key_for(req["prompt"]) for req in workload}
        owned = Counter(probe.owner(k) for k in keys)
        if (len(owned) == n_replicas
                and max(owned.values()) - min(owned.values()) <= 1):
            seed = wl_seed
            break
    else:
        raise RuntimeError(
            "no balanced template->replica assignment within 64 seeds "
            "— widen n_templates or the seed range")
    # size each replica's prefix pool for its AFFINITY share (+1
    # slack): affinity fits its owned templates; round-robin needs ALL
    # templates on every replica and thrashes its LRU
    share_rows = max(owned.values()) + 1
    engine_cfg = dict(max_slots=max_slots, prefill_chunk=prefill_chunk,
                      prefill_rows=prefill_rows,
                      prefix_cache_rows=share_rows,
                      # generous TTFT objective: calm legs keep the
                      # error budget ~full, so perf_gate can floor
                      # detail.slo_budget.remaining_min; chaos drills
                      # are what spend it
                      slo_objectives=[dict(
                          name="ttft", metric="ttft",
                          threshold_s=5.0, target=0.9,
                          window_s=60.0, min_count=3)])

    aff = _leg(workload, n_replicas, engine_cfg, model_seed,
               "affinity", prefill_chunk, log, "affinity")
    rr = _leg(workload, n_replicas, engine_cfg, model_seed,
              "round_robin", prefill_chunk, log, "round-robin")
    d = None
    if drain_drill:
        d = _leg(workload, n_replicas, engine_cfg, model_seed,
                 "affinity", prefill_chunk, log, "drain-drill",
                 drain_at=max(2, n_requests // 3),
                 rejoin_at=max(3, (2 * n_requests) // 3))

    # single-replica reference on the same seed: the parity oracle for
    # every fleet leg (and the routing-never-changes-tokens contract).
    # It runs in THIS process, so it runs last: no device is opened here
    # while a worker process may still need it.
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(model_seed)
    ref_model = TransformerLM(**_MODEL)
    ref_model.evaluate()
    ref = _engine_replay(
        ref_model, workload,
        warm_prompt=np.arange(1, 9, dtype=np.int32),
        warm_tokens=4, stats_keys=("prefix_cache",), log=log,
        label="fleet-reference", service_name="fleet-ref",
        max_slots=max_slots, prefill_chunk=prefill_chunk,
        prefill_rows=prefill_rows)
    oracle = {id(req): [int(t) for t in
                        ref["rows"][id(req)][len(req["prompt"]):]]
              for req in workload}

    def parity(rows: Dict[int, list]) -> bool:
        return all(rows.get(id(req)) == oracle[id(req)]
                   for req in workload)

    aff_par, rr_par = parity(aff["rows"]), parity(rr["rows"])

    drain = None
    if d is not None:
        drain = {
            "completed": d["requests"],
            "lost": n_requests - len(d["rows"]),
            "token_parity": parity(d["rows"]),
            "drained": d["fleet"].get("drained"),
            "routing": d["fleet"]["routing"],
            "ttft": d["ttft"],
        }

    worker_device = aff["device"]
    for leg in (aff, rr):
        leg.pop("rows", None)  # ndarray-free JSON row
        leg.pop("device", None)
    # the affinity leg is the headline: its capacity/what-if block and
    # error-budget floor become the row's detail.capacity /
    # detail.slo_budget (the control leg's copies add nothing)
    capacity = aff.pop("capacity", None)
    slo_budget = aff.pop("slo_budget", None)
    rr.pop("capacity", None)
    rr.pop("slo_budget", None)

    a50, r50 = aff["ttft"]["p50"], rr["ttft"]["p50"]
    ratios = {
        # > 1.0: the affinity leg's median first token lands sooner
        "ttft_p50_speedup": (round(r50 / a50, 4)
                             if a50 and r50 else None),
        # additive: round-robin's hit rate can legitimately be ~0 here
        "hit_rate_gain": round(
            aff["fleet"]["hit_rate"] - rr["fleet"]["hit_rate"], 4),
    }
    return {
        "affinity": aff,
        "round_robin": rr,
        "drain": drain,
        "capacity": capacity,
        "slo_budget": slo_budget,
        **ratios,
        "token_parity": bool(aff_par and rr_par),
        # the device the WORKERS report — the row's stamp (the parent
        # opened none while they ran)
        "worker_device": worker_device,
        "workload": {
            "kind": "fleet_shared_prefix",
            "replicas": n_replicas,
            "requests": n_requests,
            "rate_hz": rate_hz,
            "templates": n_templates,
            "template_len": template_len,
            "prefix_rows_per_replica": share_rows,
            "max_slots": max_slots,
            "prefill_rows": prefill_rows,
            "prefill_chunk": prefill_chunk,
            "seed": seed,
        },
    }
