"""Poisson-arrival serving benchmark: engine vs ``GenerationService``.

Replays ONE sampled open-loop workload (exponential inter-arrival gaps,
mixed prompt/decode lengths) against both serving paths and reports the
numbers a serving SLO is written in: per-request latency p50/p99, TTFT
p50/p99 (engine only — the batch service has no streaming), and
aggregate delivered tokens/sec. Engine rows also carry the usage
ledger's GOODPUT block (device-seconds by dispatch kind, padding-waste
mean, occupancy-weighted utilization, tokens per device-second) and a
per-tenant token / device-second breakdown — the workload submits
round-robin under three tenant names (one per template on the
shared-prefix variant) so attribution is exercised under load.
``bench.py --serving`` emits the result into ``bench_history.jsonl``
and the Prometheus snapshot so the serving perf trajectory is tracked
alongside the training headline.

``--serving --shared-prefix`` runs the PREFIX-HEAVY variant instead
(:func:`run_shared_prefix_comparison`): Poisson arrivals over N shared
prompt templates, replayed through the engine with its prefix cache
enabled vs disabled — the O(prompt) → O(novel-suffix) TTFT claim,
measured, with greedy token parity asserted between the two paths.

``--serving --speculative`` runs the SPECULATIVE A/B
(:func:`run_speculative_comparison`): one repeated-text Poisson
workload replayed through the engine with an int8-clone draft
proposing ``gamma`` tokens per fused round vs the plain one-token
decode — inter-token p50/p99 both ways, the draft acceptance rate,
and greedy token parity (a draft must never change the output, only
how many dispatches it costs).

``--serving --quantized`` runs the QUANTIZED A/B
(:func:`run_quantized_comparison`): one repeated-text Poisson workload
replayed through the engine with int8 KV pools + int8 weights vs full
precision — inter-token p50/p99 both ways, the cost model's
membw-utilization pair (decode is memory-bound, so halved bytes is
the claim), physical row bytes both ways, and the QUALITY gate: a
deterministic teacher-forced per-token logit-divergence report
(:func:`quantized_quality_report`) plus the speculative
acceptance-rate delta between fp-KV and int8-KV runs under the same
int8 draft.

``--serving --tp N`` runs the TENSOR-PARALLEL A/B
(:func:`run_tp_comparison`): the same Poisson workload replayed
through the engine sharded over an ``N``-way model-axis device mesh
(``engine(mesh=...)`` — Megatron param split, heads-sharded KV pools,
SPMD dispatches) vs the plain single-device engine — TTFT and
inter-token percentiles both ways, the sharded run's mesh/pool block,
and greedy token parity (a mesh changes where the math runs, never
the tokens). Hermetic on a CPU host-device mesh; the same call
measures real ICI scaling on hardware.

``--serving --qos`` runs the MIXED-PRIORITY STORM
(:func:`run_qos_storm`): one Poisson storm of interactive high-class
requests, normal-class traffic, long-decode low-class batch jobs, and
an over-budget ``greedy`` tenant, replayed into a deliberately
undersized engine with a hair-trigger TTFT SLO objective — so the
burn-rate shedder, the KV-donating preemption path, and the
per-tenant token bucket all fire on REAL machinery, not mocks — vs an
uncontended replay of only the high-class requests through the same
engine config. The headline is the high-class p99 TTFT ratio
storm/uncontended (the acceptance bar is <= 1.25x: the class buys
isolation), alongside the shed / preempted / rate-limited counts, the
outcome-conservation verdict (every submit ends in exactly one
terminal outcome — no silent drops), and the per-tenant ledger
breakdown.

``scripts/perf_gate.py`` turns consecutive rows of any variant into a
CI regression gate.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np


def poisson_workload(n_requests: int, rate_hz: float, vocab: int,
                     prompt_lens=(4, 16), decode_lens=(4, 24),
                     seed: int = 0,
                     tenants=("tenant-a", "tenant-b", "tenant-c")
                     ) -> List[dict]:
    """Sample an open-loop workload: each request gets an arrival OFFSET
    (cumulative exponential gaps at ``rate_hz``), a random prompt, a
    random decode length, and a round-robin ``tenant`` (the usage
    ledger's attribution key) — the same list replays against every
    serving path under comparison."""
    r = np.random.RandomState(seed)
    at = np.cumsum(r.exponential(1.0 / rate_hz, n_requests))
    out = []
    for i in range(n_requests):
        t0 = int(r.randint(prompt_lens[0], prompt_lens[1] + 1))
        out.append({
            "arrival_s": float(at[i]),
            "prompt": r.randint(0, vocab, (t0,)).astype(np.int32),
            "n": int(r.randint(decode_lens[0], decode_lens[1] + 1)),
            "tenant": tenants[i % len(tenants)] if tenants else None,
        })
    return out


def _percentiles(xs) -> dict:
    if not xs:
        return {"p50": None, "p99": None}
    return {"p50": round(float(np.percentile(xs, 50)), 6),
            "p99": round(float(np.percentile(xs, 99)), 6)}


def _append_itl(itl: List[float], handle) -> None:
    """Record the request's mean inter-token gap (decode wall time over
    the decoded-token count) — the per-request figure whose p99 the
    perf gate tracks next to TTFT."""
    tl = handle.timeline()
    if tl["decode_s"] is not None and tl["tokens"] > 1:
        itl.append(tl["decode_s"] / (tl["tokens"] - 1))


def _usage_blocks(stats: dict) -> dict:
    """Compress ``engine.stats()["usage"]`` into the bench-row shape:
    the goodput block verbatim plus a per-tenant token /
    device-second breakdown (the columns a capacity planner reads)."""
    u = stats.get("usage") or {}
    tenants = {
        t: {"requests": a["requests"],
            "prefill_tokens": a["prefill_tokens"],
            "decode_tokens": a["decode_tokens"],
            "device_s": a["device_s"],
            "tokens_per_device_second": a["tokens_per_device_second"]}
        for t, a in (u.get("tenants") or {}).items()}
    return {"goodput": u.get("goodput"), "tenants": tenants}


def _engine_replay(model, workload, warm_prompt, warm_tokens,
                   stats_keys, log, label, after_warm=None,
                   **engine_kw) -> dict:
    """One ENGINE leg of an A/B comparison (the speculative,
    shared-prefix, and tensor-parallel variants all replay the same
    way): build the engine, warm every executable outside the
    measurement window, open-loop replay the workload, and return the
    standard result block — latency / TTFT / inter-token percentiles,
    delivered-token throughput, the usage/goodput blocks, alerts, the
    per-request output rows (keyed by ``id(req)``, for the caller's
    token-parity check), plus the ``engine.stats()`` entries named by
    ``stats_keys``. ``after_warm(engine)`` runs between the warm
    request and the replay — a probe point for baselines that must
    exclude warmup (e.g. the jit-compile gauge the tiered-cache sweep
    asserts flat across demote/promote traffic)."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    engine = ContinuousBatchingEngine(model, **engine_kw)
    ttft: List[float] = []
    itl: List[float] = []
    rows: dict = {}
    tlock = threading.Lock()

    def collect(handle, req):
        row = handle.result()
        with tlock:
            rows[id(req)] = row
            if handle.first_token_at is not None:
                ttft.append(handle.first_token_at - handle.submitted_at)
            _append_itl(itl, handle)
        return row.shape[0] - req["prompt"].shape[0]

    log(f"[serving-bench] {label} replay ({engine.service_name})...")
    with engine:
        engine.submit(warm_prompt, warm_tokens).result(timeout=300)
        if after_warm is not None:
            after_warm(engine)
        res = _replay(
            workload,
            lambda req: engine.submit(req["prompt"], req["n"],
                                      tenant=req.get("tenant")),
            collect)
        stats = engine.stats()
    res["ttft"] = _percentiles(ttft)
    res["inter_token"] = _percentiles(itl)
    for key in stats_keys:
        res[key] = stats[key]
    res.update(_usage_blocks(stats))
    res["cost"] = stats.get("cost")
    res["loop"] = stats.get("loop")
    res["alerts"] = stats["alerts"]
    res["rows"] = rows
    return res


def _replay(workload, submit_fn, collect_fn) -> dict:
    """Open-loop replay: a pacer thread submits each request at its
    arrival offset (late submissions go immediately — arrival times are
    an offered load, not a synchronization barrier); ``collect_fn``
    blocks per request and returns delivered token count."""
    lat: List[float] = []
    toks: List[int] = []
    errs: List[BaseException] = []
    lock = threading.Lock()
    t_start = time.monotonic()

    def one(req):
        try:
            t_sub = time.monotonic()
            pending = submit_fn(req)
            n_tok = collect_fn(pending, req)
            dt = time.monotonic() - t_sub
            with lock:
                lat.append(dt)
                toks.append(n_tok)
        except BaseException as e:
            with lock:
                errs.append(e)

    threads = []
    for req in workload:
        delay = t_start + req["arrival_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one, args=(req,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    if errs:
        raise errs[0]
    return {"latency": _percentiles(lat),
            "tokens_per_sec": round(sum(toks) / max(wall, 1e-9), 2),
            "wall_s": round(wall, 3), "requests": len(workload)}


def shared_prefix_workload(n_requests: int, rate_hz: float, vocab: int,
                           n_templates: int = 4, template_len: int = 96,
                           tail_lens=(4, 12), decode_lens=(4, 16),
                           seed: int = 0,
                           template_order: str = "random") -> List[dict]:
    """Sample a PREFIX-HEAVY open-loop workload: every prompt is one of
    ``n_templates`` shared heads (a system prompt / few-shot template)
    followed by a short random tail — the traffic shape the engine's
    prefix cache exists for. Same arrival/replay semantics as
    :func:`poisson_workload`. ``template_order="cycle"`` visits the
    templates round-robin instead of uniformly at random — the LRU
    worst case (every revisit is exactly ``n_templates`` requests
    away), which the working-set sweep uses to expose the device-only
    hit-rate cliff."""
    if template_order not in ("random", "cycle"):
        raise ValueError(
            f"template_order must be 'random' or 'cycle', "
            f"got {template_order!r}")
    r = np.random.RandomState(seed)
    templates = [r.randint(0, vocab, (template_len,)).astype(np.int32)
                 for _ in range(n_templates)]
    at = np.cumsum(r.exponential(1.0 / rate_hz, n_requests))
    out = []
    for i in range(n_requests):
        ti = (i % n_templates if template_order == "cycle"
              else int(r.randint(0, n_templates)))
        tail = r.randint(0, vocab, (int(r.randint(
            tail_lens[0], tail_lens[1] + 1)),)).astype(np.int32)
        out.append({
            "arrival_s": float(at[i]),
            "prompt": np.concatenate([templates[ti], tail]),
            "n": int(r.randint(decode_lens[0], decode_lens[1] + 1)),
            # one tenant per template — the usage table then shows
            # which shared prompt is eating the device
            "tenant": f"tpl-{ti}",
        })
    return out


def repeated_text_workload(n_requests: int, rate_hz: float, vocab: int,
                           motif_len: int = 4, prompt_lens=(8, 16),
                           decode_lens=(8, 24), seed: int = 0,
                           tenants=("tenant-a", "tenant-b", "tenant-c")
                           ) -> List[dict]:
    """Sample a REPEATED-TEXT open-loop workload: each prompt tiles a
    short random motif (boilerplate, markup, table rows — the
    self-similar traffic a draft model predicts well), so speculative
    decoding gets a fair shot at a high acceptance rate while prompts
    stay distinct enough that the prefix cache is not the thing being
    measured. Same arrival/replay semantics as
    :func:`poisson_workload`."""
    r = np.random.RandomState(seed)
    at = np.cumsum(r.exponential(1.0 / rate_hz, n_requests))
    out = []
    for i in range(n_requests):
        motif = r.randint(0, vocab, (motif_len,)).astype(np.int32)
        t0 = int(r.randint(prompt_lens[0], prompt_lens[1] + 1))
        reps = -(-t0 // motif_len)
        out.append({
            "arrival_s": float(at[i]),
            "prompt": np.tile(motif, reps)[:t0],
            "n": int(r.randint(decode_lens[0], decode_lens[1] + 1)),
            "tenant": tenants[i % len(tenants)] if tenants else None,
        })
    return out


def run_speculative_comparison(model, draft=None, n_requests: int = 24,
                               rate_hz: float = 30.0,
                               max_slots: int = 4,
                               prefill_chunk: int = 8,
                               prefill_rows: int = 2,
                               gamma: int = 4,
                               eos_id: Optional[int] = None,
                               seed: int = 0, registry=None,
                               log=None) -> dict:
    """Replay ONE repeated-text Poisson workload through the engine
    twice — speculative decoding ON (``draft`` proposing ``gamma``
    tokens per fused round; default: the int8-quantized clone of
    ``model``, PERF.md's draft construction) vs OFF, everything else
    identical — and report inter-token/TTFT/latency percentiles for
    both, the speculative run's acceptance rate, the inter-token
    p50/p99 speedups, and whether the two paths produced
    token-identical greedy outputs (they must: a draft changes dispatch
    count, never tokens). This is the decode-throughput claim of
    speculative serving, measured."""
    log = log or (lambda *a, **k: None)
    if draft is None:
        from bigdl_tpu.nn.quantized import Quantizer

        log("[serving-bench] quantizing the int8 draft clone...")
        draft = Quantizer.quantize(model)
        draft.evaluate()
    vocab = model.vocab_size
    window = (model.max_len // prefill_chunk) * prefill_chunk
    decode_hi = max(8, min(24, window // 2 - 16))
    wl = repeated_text_workload(
        n_requests, rate_hz, vocab,
        prompt_lens=(8, min(16, window - decode_hi - 1)),
        decode_lens=(min(8, decode_hi), decode_hi), seed=seed)
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(0, vocab, (12,)),
        np.int32)

    def run_path(name: str, **engine_kw) -> dict:
        return _engine_replay(
            model, wl, warm_prompt, 4,
            ("speculation", "jit_compiles"), log, "speculative",
            max_slots=max_slots, prefill_chunk=prefill_chunk,
            prefill_rows=prefill_rows, eos_id=eos_id,
            registry=registry, service_name=name, **engine_kw)

    spec = run_path("bench_spec_on", draft=draft, spec_gamma=gamma)
    nospec = run_path("bench_spec_off")
    parity = all(
        np.array_equal(spec["rows"][id(req)], nospec["rows"][id(req)])
        for req in wl)
    for r in (spec, nospec):
        del r["rows"]

    def ratio(key):
        a, b = nospec["inter_token"][key], spec["inter_token"][key]
        return round(a / b, 4) if a and b else None

    return {"spec": spec, "nospec": nospec,
            "inter_token_p50_speedup": ratio("p50"),
            "inter_token_p99_speedup": ratio("p99"),
            "acceptance_rate":
                spec["speculation"].get("acceptance_rate"),
            "token_parity": bool(parity),
            "workload": {"kind": "speculative",
                         "requests": n_requests, "rate_hz": rate_hz,
                         "seed": seed, "max_slots": max_slots,
                         "prefill_rows": prefill_rows,
                         "gamma": gamma}}


def quantized_quality_report(model, prompts=None, horizon: int = 16,
                             kv_dtype: str = "int8",
                             weights_dtype: Optional[str] = "int8",
                             n_prompts: int = 6, prompt_len: int = 8,
                             seed: int = 0) -> dict:
    """Per-token numerics gate for quantized serving: roll the FLOAT
    model greedily for ``horizon`` tokens per prompt, then (a)
    teacher-force the quantized path (int8 KV cache via
    ``kv_dtype``, optionally the int8 ``Quantizer`` weight clone) down
    the SAME trajectory and measure per-token logit divergence, and
    (b) free-run the quantized path greedily and measure how long its
    output prefix agrees with the float rollout. Deterministic per
    (model, prompts, horizon) — :func:`quantize_kv` rounds the same
    floats to the same bytes every time — so the figures gate cleanly
    run-to-run in ``perf_gate.py``.

    Returns ``logit_div_max`` / ``logit_div_mean`` (absolute),
    ``logit_div_rel`` (max divergence over the float run's own max
    |logit| — the scale-free ceiling the gate reads), and
    ``greedy_match_fraction`` (mean common-prefix length / horizon)."""
    import jax.numpy as jnp

    model.evaluate()
    if weights_dtype is not None and str(weights_dtype) == "int8":
        from bigdl_tpu.nn.quantized import Quantizer

        qmodel = Quantizer.quantize(model)
    else:
        qmodel = model
    qmodel.evaluate()
    vocab = model.vocab_size
    window = model.max_len
    horizon = max(2, min(horizon, window - prompt_len - 1))
    if prompts is None:
        r = np.random.RandomState(seed)
        prompts = [r.randint(0, vocab, (prompt_len,)).astype(np.int32)
                   for _ in range(n_prompts)]

    def greedy_roll(m, ids, kv, forced=None):
        """Greedy rollout (or teacher-forced when ``forced`` is the
        token list to feed) returning (tokens, per-step logits)."""
        c = m.init_cache(1, window, kv_dtype=kv)
        lg, c = m.prefill(ids, c)
        logits = [np.asarray(lg).reshape(-1)]
        toks = [int(np.argmax(logits[-1]))]
        pos = ids.shape[1]
        for i in range(horizon - 1):
            nxt = forced[i] if forced is not None else toks[-1]
            lg, c = m.decode_step(jnp.asarray([nxt]), jnp.int32(pos), c)
            logits.append(np.asarray(lg).reshape(-1))
            toks.append(int(np.argmax(logits[-1])))
            pos += 1
        return toks, logits

    div_max, fp_scale = 0.0, 0.0
    div_means, match = [], []
    for p in prompts:
        ids = jnp.asarray(np.asarray(p, np.int32))[None]
        fp_toks, fp_logits = greedy_roll(model, ids, None)
        fp_scale = max(fp_scale,
                       max(float(np.max(np.abs(l))) for l in fp_logits))
        _, q_logits = greedy_roll(qmodel, ids, kv_dtype,
                                  forced=fp_toks)
        d = [float(np.max(np.abs(a - b)))
             for a, b in zip(fp_logits, q_logits)]
        div_max = max(div_max, max(d))
        div_means.append(float(np.mean(d)))
        q_toks, _ = greedy_roll(qmodel, ids, kv_dtype)
        k = 0
        for a, b in zip(fp_toks, q_toks):
            if a != b:
                break
            k += 1
        match.append(k / len(fp_toks))
    return {
        "kv_dtype": kv_dtype,
        "weights_dtype": (weights_dtype or "fp"),
        "prompts": len(prompts), "horizon": horizon,
        "vocab": vocab,
        "logit_div_max": round(div_max, 6),
        "logit_div_mean": round(float(np.mean(div_means)), 6),
        "logit_div_rel": (round(div_max / fp_scale, 6)
                          if fp_scale else 0.0),
        "greedy_match_fraction": round(float(np.mean(match)), 4),
    }


def run_quantized_comparison(model, n_requests: int = 24,
                             rate_hz: float = 30.0,
                             max_slots: int = 4,
                             prefill_chunk: int = 8,
                             prefill_rows: int = 2,
                             gamma: int = 4,
                             eos_id: Optional[int] = None,
                             seed: int = 0, registry=None,
                             log=None) -> dict:
    """Replay ONE repeated-text Poisson workload through the engine
    twice — int8 KV pools + int8 weights (``kv_dtype=weights_dtype=
    "int8"``) vs full precision, everything else identical — and
    report inter-token/TTFT/latency percentiles for both, the
    membw-utilization pair the cost model attributes (decode is
    memory-bound, so halving the streamed bytes is exactly what this
    row must show), the capacity block (physical row bytes both ways),
    and the QUALITY gate: the per-token logit-divergence report
    (:func:`quantized_quality_report`, deterministic) plus the
    speculative acceptance-rate delta measured by replaying the same
    workload under an int8 draft with fp vs int8 KV (the draft must
    keep agreeing with the target when the cache quantizes). Token
    parity is asserted WITHIN each numerics regime — speculation must
    not change tokens whether the cache is fp or int8 — never across
    regimes (int8 rounds differently; the quality report bounds that
    drift instead)."""
    log = log or (lambda *a, **k: None)
    from bigdl_tpu.nn.quantized import Quantizer

    vocab = model.vocab_size
    window = (model.max_len // prefill_chunk) * prefill_chunk
    decode_hi = max(8, min(24, window // 2 - 16))
    wl = repeated_text_workload(
        n_requests, rate_hz, vocab,
        prompt_lens=(8, min(16, window - decode_hi - 1)),
        decode_lens=(min(8, decode_hi), decode_hi), seed=seed)
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(0, vocab, (12,)),
        np.int32)
    log("[serving-bench] quantizing the int8 draft clone...")
    draft = Quantizer.quantize(model)
    draft.evaluate()

    def run_path(name: str, **engine_kw) -> dict:
        return _engine_replay(
            model, wl, warm_prompt, 4,
            ("speculation", "quantization", "jit_compiles"), log,
            "quantized", max_slots=max_slots,
            prefill_chunk=prefill_chunk, prefill_rows=prefill_rows,
            eos_id=eos_id, registry=registry, service_name=name,
            **engine_kw)

    quant = run_path("bench_quant_on", kv_dtype="int8",
                     weights_dtype="int8")
    fp = run_path("bench_quant_off")
    # acceptance-delta probe: the SAME draft over the SAME workload,
    # fp KV vs int8 KV (weights fp in both, so the cache is the ONLY
    # thing that moves) — quantizing the cache must not change how
    # often the target agrees with its draft (delta ~ 0). The plain
    # kv-only leg exists so each spec leg has a same-numerics
    # non-speculative twin to assert token parity against.
    kv8 = run_path("bench_quant_kv_only", kv_dtype="int8")
    spec_fp = run_path("bench_quant_spec_fp", draft=draft,
                       spec_gamma=gamma)
    spec_q = run_path("bench_quant_spec_int8", draft=draft,
                      spec_gamma=gamma, kv_dtype="int8")
    parity_fp = all(
        np.array_equal(fp["rows"][id(r)], spec_fp["rows"][id(r)])
        for r in wl)
    parity_q = all(
        np.array_equal(kv8["rows"][id(r)], spec_q["rows"][id(r)])
        for r in wl)
    for r in (quant, fp, kv8, spec_fp, spec_q):
        del r["rows"]
    log("[serving-bench] quantized quality report "
        "(teacher-forced logit divergence)...")
    quality = quantized_quality_report(model, horizon=min(16, window // 2))
    acc_fp = spec_fp["speculation"].get("acceptance_rate")
    acc_q = spec_q["speculation"].get("acceptance_rate")
    quality["acceptance_rate_fp"] = acc_fp
    quality["acceptance_rate_int8"] = acc_q
    # SIGNED, positive = the int8 cache LOST acceptance. One-sided by
    # design: shared rounding noise correlates the int8 draft with an
    # int8-cached target, so acceptance typically RISES under
    # quantization — a throughput win the gate must not punish; only a
    # drop (the draft disagreeing with what it will serve) is a
    # regression
    quality["acceptance_delta"] = (round(acc_fp - acc_q, 4)
                                   if acc_fp is not None
                                   and acc_q is not None else None)

    def ratio(key, base=None, new=None):
        a = (base or fp)["inter_token"][key]
        b = (new or quant)["inter_token"][key]
        return round(a / b, 4) if a and b else None

    def membw(leg):
        return ((leg.get("cost") or {}).get("overall")
                or {}).get("membw_util")

    qz = quant["quantization"]
    return {"quantized": quant, "fp_baseline": fp, "kv_only": kv8,
            "spec_fp": spec_fp, "spec_int8": spec_q,
            "inter_token_p50_speedup": ratio("p50"),
            "inter_token_p99_speedup": ratio("p99"),
            # the full quantized stack under its draft vs the fp stack
            # under the same draft: a risen acceptance rate turns into
            # longer accepted bursts, so the int8 cache can improve the
            # inter-token TAIL even where raw int8 math doesn't pay
            # (CPU)
            "spec_inter_token_p50_speedup":
                ratio("p50", base=spec_fp, new=spec_q),
            "spec_inter_token_p99_speedup":
                ratio("p99", base=spec_fp, new=spec_q),
            "membw_util": {"fp": membw(fp), "quantized": membw(quant)},
            "capacity": {
                "kv_row_bytes": qz["kv_row_bytes"],
                "fp_row_bytes": qz["fp_row_bytes"],
                "row_bytes_ratio": qz["row_bytes_ratio"],
                "capacity_multiplier":
                    (round(qz["fp_row_bytes"] / qz["kv_row_bytes"], 4)
                     if qz["kv_row_bytes"] else None)},
            "quality": quality,
            "token_parity_spec_fp": bool(parity_fp),
            "token_parity_spec_int8": bool(parity_q),
            "workload": {"kind": "quantized",
                         "requests": n_requests, "rate_hz": rate_hz,
                         "seed": seed, "max_slots": max_slots,
                         "prefill_rows": prefill_rows,
                         "gamma": gamma}}


def run_shared_prefix_comparison(model, n_requests: int = 24,
                                 rate_hz: float = 30.0,
                                 max_slots: int = 4,
                                 prefill_chunk: int = 8,
                                 prefill_rows: int = 2,
                                 n_templates: int = 4,
                                 template_len: int = 96,
                                 eos_id: Optional[int] = None,
                                 seed: int = 0, registry=None,
                                 log=None) -> dict:
    """Replay ONE shared-prefix Poisson workload through the engine
    twice — prefix cache ENABLED vs DISABLED, everything else identical
    — and report TTFT/latency percentiles for both, the cached run's
    hit-rate block, the p50/p99 TTFT speedups, and whether the two
    paths produced token-identical greedy outputs (they must). This is
    the O(prompt) → O(novel-suffix) TTFT claim, measured."""
    log = log or (lambda *a, **k: None)
    vocab = model.vocab_size
    # fit tail + decode inside the ENGINE's serving window: a sampled
    # prompt of template + tail_hi plus decode_hi tokens must never
    # overflow it (engine.submit would reject it mid-replay). The
    # window is the model context rounded DOWN to a chunk multiple
    # when it doesn't divide evenly — mirror engine.__init__'s cap.
    window = (model.max_len // prefill_chunk) * prefill_chunk
    room = window - template_len
    if room < 2:
        raise ValueError(
            f"template_len {template_len} leaves only {room} of the "
            f"engine's {window}-token serving window for tail + decode")
    tail_hi = max(1, min(12, room // 2))
    decode_hi = max(1, min(16, room - tail_hi))
    wl = shared_prefix_workload(
        n_requests, rate_hz, vocab, n_templates=n_templates,
        template_len=template_len,
        tail_lens=(min(4, tail_hi), tail_hi),
        decode_lens=(min(4, decode_hi), decode_hi),
        seed=seed)
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(
            0, vocab, (template_len,)), np.int32)

    def run_path(name: str, **engine_kw) -> dict:
        # the warm prompt is a NON-template one, so the compile cost
        # lands outside the measurement and the template cache starts
        # cold for both paths
        return _engine_replay(
            model, wl, warm_prompt, 2, ("prefix_cache",), log,
            "shared-prefix",
            max_slots=max_slots, prefill_chunk=prefill_chunk,
            prefill_rows=prefill_rows, eos_id=eos_id,
            registry=registry, service_name=name, **engine_kw)

    cached = run_path("bench_prefix_on")
    uncached = run_path("bench_prefix_off", prefix_cache_bytes=0)
    parity = all(
        np.array_equal(cached["rows"][id(req)], uncached["rows"][id(req)])
        for req in wl)
    for r in (cached, uncached):
        del r["rows"]

    def ratio(key):
        a, b = uncached["ttft"][key], cached["ttft"][key]
        return round(a / b, 4) if a and b else None

    return {"cached": cached, "uncached": uncached,
            "ttft_p50_speedup": ratio("p50"),
            "ttft_p99_speedup": ratio("p99"),
            "token_parity": bool(parity),
            "workload": {"kind": "shared_prefix",
                         "requests": n_requests, "rate_hz": rate_hz,
                         "seed": seed, "max_slots": max_slots,
                         "prefill_rows": prefill_rows,
                         "n_templates": n_templates,
                         "template_len": template_len}}


def run_working_set_sweep(model, working_sets=(2, 8),
                          device_rows: int = 2,
                          requests_per_template: int = 3,
                          rate_hz: float = 40.0, max_slots: int = 4,
                          prefill_chunk: int = 8,
                          prefill_rows: int = 2,
                          template_len: int = 16,
                          eos_id: Optional[int] = None, seed: int = 0,
                          registry=None, log=None) -> dict:
    """Sweep the shared-prefix WORKING SET past the device budget and
    measure where each cache tier's hit rate falls off. Each point
    replays one round-robin template workload (``working_set``
    templates ≫ ``device_rows`` pool rows is the LRU worst case: every
    revisit is exactly ``working_set`` requests away) through THREE
    engines — host tier sized to the working set, device-only, and
    cache-disabled — everything else identical. The device-only leg
    collapses once the working set exceeds ``device_rows`` (LRU
    thrashes: a template is always evicted before its revisit); the
    tiered leg holds the hit rate because evictions demote to host RAM
    and revisits promote back. Per point the sweep also checks the
    invariants the tiers must not bend: token parity of both cached
    legs against the cache-disabled oracle, the jit-compile gauge flat
    from warmup through every demote/promote, and usage-ledger
    device-seconds conservation (per-tenant sums == measured dispatch
    total) with promotions in flight."""
    log = log or (lambda *a, **k: None)
    vocab = model.vocab_size
    window = (model.max_len // prefill_chunk) * prefill_chunk
    room = window - template_len
    if room < 2:
        raise ValueError(
            f"template_len {template_len} leaves only {room} of the "
            f"engine's {window}-token serving window for tail + decode")
    tail_hi = max(1, min(4, room // 2))
    decode_hi = max(1, min(8, room - tail_hi))
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(
            0, vocab, (template_len,)), np.int32)

    def leg(name, wl, probe, **engine_kw):
        res = _engine_replay(
            model, wl, warm_prompt, 2,
            ("prefix_cache", "jit_compiles"), log, name,
            after_warm=probe, max_slots=max_slots,
            prefill_chunk=prefill_chunk, prefill_rows=prefill_rows,
            eos_id=eos_id, registry=registry, service_name=name,
            **engine_kw)
        tenant_s = sum(t["device_s"] for t in res["tenants"].values())
        total_s = res["goodput"]["device_seconds"]["total"]
        res["ledger_conserved"] = bool(
            abs(tenant_s - total_s) <= 1e-6 * max(total_s, 1e-9))
        return res

    points = []
    for ws in working_sets:
        n_req = max(int(ws) * max(2, requests_per_template), 8)
        wl = shared_prefix_workload(
            n_req, rate_hz, vocab, n_templates=int(ws),
            template_len=template_len,
            tail_lens=(min(2, tail_hi), tail_hi),
            decode_lens=(min(4, decode_hi), decode_hi),
            seed=seed + int(ws), template_order="cycle")
        baseline = {}

        def probe(eng, _b=baseline):
            _b["jit"] = eng.stats()["jit_compiles"]

        legs = {}
        # the host tier absorbs the DONATION working set: every request
        # donates its own template+tail entry (the trie matches revisits
        # against any same-template predecessor's head), so the hot set
        # is the request count, not the template count
        for name, kw in (
                ("tiered", {"prefix_cache_rows": device_rows,
                            "prefix_host_rows": n_req}),
                ("device_only", {"prefix_cache_rows": device_rows}),
                ("disabled", {"prefix_cache_bytes": 0})):
            baseline.clear()
            r = leg(f"ws{ws}_{name}", wl, probe, **kw)
            r["jit_flat"] = bool(r["jit_compiles"] == baseline["jit"])
            legs[name] = r
        parity = all(
            np.array_equal(legs[a]["rows"][id(req)],
                           legs["disabled"]["rows"][id(req)])
            for a in ("tiered", "device_only") for req in wl)
        for r in legs.values():
            del r["rows"]

        def trim(r):
            pc = r["prefix_cache"]
            out = {"ttft": r["ttft"], "latency": r["latency"],
                   "tokens_per_sec": r["tokens_per_sec"],
                   "jit_flat": r["jit_flat"],
                   "ledger_conserved": r["ledger_conserved"]}
            if pc.get("enabled"):
                out.update(
                    hit_rate=pc["hit_rate"], hits=pc["hits"],
                    misses=pc["misses"],
                    reused_tokens=pc["reused_tokens"],
                    capacity_bytes=pc["capacity_bytes"])
                if pc.get("host_rows"):
                    out.update(
                        host_hits=pc["host_hits"],
                        demotions=pc["demotions"],
                        promotions=pc["promotions"],
                        host_evictions=pc["host_evictions"],
                        host_capacity_bytes=pc["host_capacity_bytes"])
            return out

        points.append({
            "working_set": int(ws),
            "ws_to_budget": round(int(ws) / device_rows, 2),
            "requests": n_req,
            "token_parity": bool(parity),
            "tiered": trim(legs["tiered"]),
            "device_only": trim(legs["device_only"]),
            "disabled": trim(legs["disabled"]),
            # full blocks the headline promotes (cost classification,
            # goodput, steady-state gap) — per-point only the trims
            "_tiered_full": {k: legs["tiered"][k] for k in
                             ("cost", "loop", "goodput", "inter_token")},
        })
        log(f"[serving-bench] working-set {ws}: tiered hit-rate "
            f"{points[-1]['tiered'].get('hit_rate')} vs device-only "
            f"{points[-1]['device_only'].get('hit_rate')}")

    # headline = the deepest point past the budget (the cliff the host
    # tier exists to hold); falls back to the last point
    past = [p for p in points if p["ws_to_budget"] >= 4.0]
    head = (past or points)[-1]
    dev_hr = head["device_only"].get("hit_rate") or 0.0
    tier_hr = head["tiered"].get("hit_rate") or 0.0
    tiered_full = {**head["tiered"], **head.pop("_tiered_full")}
    for p in points:
        p.pop("_tiered_full", None)
    return {
        "points": points,
        # the headline point's legs at top level: perf_gate reads
        # detail.tiered.{ttft,inter_token,goodput} like any other
        # engine leg, detail.headline.tiered_hit_rate for the
        # higher-is-better gate
        "tiered": tiered_full,
        "device_only": head["device_only"],
        "headline": {
            "working_set": head["working_set"],
            "ws_to_budget": head["ws_to_budget"],
            "tiered_hit_rate": tier_hr,
            "device_only_hit_rate": dev_hr,
            "hit_rate_gain": (round(tier_hr / dev_hr, 2)
                              if dev_hr > 0 else None),
            "tiered_ttft_p50_s": head["tiered"]["ttft"]["p50"],
            "device_only_ttft_p50_s": head["device_only"]["ttft"]["p50"],
            "token_parity": head["token_parity"],
            "jit_flat": bool(head["tiered"]["jit_flat"]),
            "ledger_conserved": bool(
                head["tiered"]["ledger_conserved"]),
        },
        "workload": {"kind": "working_set_sweep",
                     "device_rows": device_rows,
                     "working_sets": [int(w) for w in working_sets],
                     # scalars for perf_gate's signature (it ignores
                     # the list): two sweeps compare only when they
                     # sweep the same depth
                     "max_working_set": int(max(working_sets)),
                     "n_points": len(list(working_sets)),
                     "requests_per_template": requests_per_template,
                     "rate_hz": rate_hz, "seed": seed,
                     "max_slots": max_slots,
                     "prefill_rows": prefill_rows,
                     "template_len": template_len}}


def run_tp_comparison(model, tp: int = 2, n_requests: int = 16,
                      rate_hz: float = 30.0, max_slots: int = 4,
                      prefill_chunk: int = 8, prefill_rows: int = 2,
                      eos_id: Optional[int] = None, seed: int = 0,
                      registry=None, log=None, mesh=None,
                      model_axis: str = "model") -> dict:
    """Replay ONE Poisson workload through the engine twice — SHARDED
    over a ``tp``-way model-axis device mesh (params Megatron-split,
    KV pools sharded on heads, SPMD dispatches) vs the plain
    single-device engine, everything else identical — and report
    TTFT / inter-token / latency percentiles for both, the sharded
    run's mesh block and jit-compile count, and whether the two paths
    produced token-identical greedy outputs (they must: a mesh changes
    WHERE the math runs, never the tokens). On a CPU host this is the
    hermetic host-device-mesh A/B ``bench.py --serving --tp`` emits;
    on real hardware the same call measures actual ICI scaling."""
    import jax

    from bigdl_tpu.parallel.engine import Engine

    log = log or (lambda *a, **k: None)
    if mesh is None:
        devices = jax.devices()
        if len(devices) < tp:
            try:
                devices = jax.devices("cpu")
            except RuntimeError:
                pass
        if len(devices) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices but only {len(devices)} "
                f"are visible; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={tp}")
        mesh = Engine.create_mesh([(model_axis, tp)],
                                  devices=devices[:tp])
    vocab = model.vocab_size
    wl = poisson_workload(n_requests, rate_hz, vocab,
                          decode_lens=(4, min(24, model.max_len // 2)),
                          seed=seed)
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(0, vocab, (12,)),
        np.int32)

    def run_path(name: str, **engine_kw) -> dict:
        return _engine_replay(
            model, wl, warm_prompt, 4, ("mesh", "jit_compiles"), log,
            "tensor-parallel",
            max_slots=max_slots, prefill_chunk=prefill_chunk,
            prefill_rows=prefill_rows, eos_id=eos_id,
            registry=registry, service_name=name, **engine_kw)

    sharded = run_path("bench_tp_sharded", mesh=mesh,
                       model_axis=model_axis)
    unsharded = run_path("bench_tp_unsharded")
    parity = all(
        np.array_equal(sharded["rows"][id(req)],
                       unsharded["rows"][id(req)])
        for req in wl)
    for r in (sharded, unsharded):
        del r["rows"]

    def ratio(block, key):
        a, b = unsharded[block][key], sharded[block][key]
        return round(a / b, 4) if a and b else None

    return {"sharded": sharded, "unsharded": unsharded,
            "ttft_p50_ratio": ratio("ttft", "p50"),
            "inter_token_p50_ratio": ratio("inter_token", "p50"),
            "inter_token_p99_ratio": ratio("inter_token", "p99"),
            "token_parity": bool(parity),
            "workload": {"kind": "tensor_parallel", "tp": int(tp),
                         "requests": n_requests, "rate_hz": rate_hz,
                         "seed": seed, "max_slots": max_slots,
                         "prefill_rows": prefill_rows}}


def run_poisson_comparison(model, n_requests: int = 16,
                           rate_hz: float = 20.0, max_slots: int = 4,
                           prefill_chunk: int = 8, max_batch: int = 4,
                           batch_timeout_ms: float = 10.0,
                           eos_id: Optional[int] = None, seed: int = 0,
                           registry=None, log=None) -> dict:
    """Run the same Poisson workload through the continuous-batching
    engine and through ``GenerationService``; return both result dicts
    plus the engine's TTFT percentiles and the p99 speedup ratio
    (> 1.0: the engine's tail is shorter)."""
    from bigdl_tpu.optim import GenerationService
    from bigdl_tpu.serving import ContinuousBatchingEngine

    log = log or (lambda *a, **k: None)
    vocab = model.vocab_size
    wl = poisson_workload(n_requests, rate_hz, vocab,
                          decode_lens=(4, min(24, model.max_len // 2)),
                          seed=seed)

    engine = ContinuousBatchingEngine(
        model, max_slots=max_slots, prefill_chunk=prefill_chunk,
        eos_id=eos_id, registry=registry, service_name="bench_engine")
    ttft: List[float] = []
    itl: List[float] = []
    tlock = threading.Lock()

    def collect_engine(handle, req):
        row = handle.result()
        with tlock:
            if handle.first_token_at is not None:
                ttft.append(handle.first_token_at - handle.submitted_at)
            _append_itl(itl, handle)
        return row.shape[0] - req["prompt"].shape[0]

    log("[serving-bench] engine replay...")
    with engine:
        eng = _replay(
            wl, lambda req: engine.submit(req["prompt"], req["n"],
                                          tenant=req.get("tenant")),
            collect_engine)
        stats = engine.stats()
        eng["alerts"] = stats["alerts"]
        eng.update(_usage_blocks(stats))
        eng["cost"] = stats.get("cost")
        eng["loop"] = stats.get("loop")
        # calm-storm incident gate: a healthy Poisson replay must
        # record ZERO incidents (perf_gate fails the build otherwise)
        inc = stats.get("incidents") or {}
        incidents = {"count": inc.get("count", 0),
                     "by_kind": inc.get("by_kind", {}), "calm": True}
    eng["ttft"] = _percentiles(ttft)
    eng["inter_token"] = _percentiles(itl)

    svc = GenerationService(model, max_batch=max_batch,
                            batch_timeout_ms=batch_timeout_ms,
                            bucket_tokens=8, prompt_bucket=8,
                            eos_id=eos_id, registry=registry,
                            service_name="bench_generation")
    log("[serving-bench] GenerationService replay...")
    gen = _replay(
        wl, lambda req: svc.generate(req["prompt"], req["n"]),
        lambda row, req: row.shape[0] - req["prompt"].shape[0])

    p99_ratio = (round(gen["latency"]["p99"] / eng["latency"]["p99"], 4)
                 if eng["latency"]["p99"] else None)
    return {"engine": eng, "generation_service": gen,
            "p99_speedup": p99_ratio,
            "incidents": incidents,
            "workload": {"requests": n_requests, "rate_hz": rate_hz,
                         "seed": seed, "max_slots": max_slots,
                         "max_batch": max_batch}}


# --------------------------------------------------------------- QoS storm

#: priority assignment cycle for the storm mix: half the traffic is
#: low-class batch work (long decodes that hold slots), a quarter
#: latency-sensitive high-class interactive traffic (long prompts,
#: short decodes), a quarter normal. The cycle leads with TWO lows so
#: the storm opens with every slot held by batch work — the first
#: high-class arrival then exercises the preemption path, not a free
#: slot
_QOS_MIX = ("low", "low", "high", "normal")

#: tenant names by class — the ledger's fair-share breakdown needs the
#: classes billed apart; the over-budget tenant is added on top
_QOS_TENANTS = {"high": "interactive", "normal": "standard",
                "low": "batch"}


def qos_storm_workload(n_requests: int, rate_hz: float, vocab: int,
                       n_greedy: int = 3, seed: int = 0) -> List[dict]:
    """Sample the MIXED-PRIORITY storm: Poisson arrivals cycling
    through ``_QOS_MIX`` — high-class requests get LONG prompts and
    short decodes (interactive: TTFT is the product), low/normal get
    short prompts and LONG decodes (batch: they hold slots, which is
    what makes them preemption victims) — plus ``n_greedy`` extra
    high-class requests under the ``greedy`` tenant spread across the
    storm span (the token-bucket's prey: even the top class cannot buy
    unmetered device time). Each request carries ``priority`` and
    ``tenant`` next to the usual arrival/prompt/n fields."""
    r = np.random.RandomState(seed)
    at = np.cumsum(r.exponential(1.0 / rate_hz, n_requests))
    out = []
    for i in range(n_requests):
        cls = _QOS_MIX[i % len(_QOS_MIX)]
        if cls == "high":
            # interactive prompts are LONG (12-14 prefill chunks):
            # TTFT is then dominated by real prefill work, so the
            # fixed few-ms cost of a preemption reads as the small
            # fraction it is, not as a 2x on a trivial baseline
            t0 = int(r.randint(96, 113))
            n = int(r.randint(4, 9))
        else:
            t0 = int(r.randint(8, 17))
            n = int(r.randint(56, 81))
        out.append({
            "arrival_s": float(at[i]),
            "prompt": r.randint(0, vocab, (t0,)).astype(np.int32),
            "n": n,
            "priority": cls,
            "tenant": _QOS_TENANTS[cls],
        })
    # pin the storm's opening: the second batch job lands 10ms behind
    # the first and the first interactive request 20ms behind that —
    # DETERMINISTICALLY, both slots are held by mid-decode batch work
    # when the first high-class request arrives, so the preemption
    # path runs on every seed, not just unlucky ones
    if n_requests > 2:
        out[1]["arrival_s"] = out[0]["arrival_s"] + 0.01
        out[2]["arrival_s"] = out[1]["arrival_s"] + 0.02
    span = float(at[-1])
    for k in range(n_greedy):
        out.append({
            # the first greedy request lands early enough to ADMIT and
            # drain the bucket (16 decode tokens bill well past the
            # bucket's burst); the rest arrive after it has finished
            # and been billed, so they meet an exhausted bucket
            "arrival_s": span * (0.2 + 0.65 * k / max(1, n_greedy - 1)),
            "prompt": r.randint(0, vocab, (24,)).astype(np.int32),
            "n": 16,
            "priority": "high",
            "tenant": "greedy",
        })
    out.sort(key=lambda q: q["arrival_s"])
    return out


def _qos_replay(engine, workload, timeout_s: float = 300.0) -> dict:
    """Open-loop replay with OUTCOME accounting: structured QoS
    rejections (shed / rate-limited) are expected results here, not
    errors — every submit is tallied into exactly one terminal outcome
    and the TTFT samples are kept PER CLASS (the storm's headline is
    the high class's tail, measured apart from the traffic being
    sacrificed for it)."""
    from bigdl_tpu.serving.streams import (
        RequestCancelled, RequestRateLimited, RequestShed,
        RequestTimedOut,
    )

    outcomes = {"finished": 0, "shed": 0, "rate_limited": 0,
                "cancelled": 0, "timed_out": 0}
    # the greedy tenant is high-CLASS but not the headline: its TTFTs
    # land in their own bucket so the interactive tail stays clean
    ttft_by_class = {"high": [], "normal": [], "low": [], "greedy": []}
    itl_high: List[float] = []
    lat: List[float] = []
    toks: List[int] = []
    retry_hints: List[float] = []
    errs: List[BaseException] = []
    lock = threading.Lock()
    t_start = time.monotonic()

    def one(req):
        try:
            t_sub = time.monotonic()
            try:
                h = engine.submit(req["prompt"], req["n"],
                                  tenant=req["tenant"],
                                  priority=req["priority"])
            except (RequestShed, RequestRateLimited) as e:
                kind = ("shed" if isinstance(e, RequestShed)
                        else "rate_limited")
                with lock:
                    outcomes[kind] += 1
                    retry_hints.append(float(e.retry_after_s))
                return
            try:
                row = h.result(timeout=timeout_s)
            except RequestTimedOut:
                with lock:
                    outcomes["timed_out"] += 1
                return
            except RequestCancelled:
                with lock:
                    outcomes["cancelled"] += 1
                return
            dt = time.monotonic() - t_sub
            cls = ("greedy" if req["tenant"] == "greedy"
                   else req["priority"])
            with lock:
                outcomes["finished"] += 1
                lat.append(dt)
                toks.append(row.shape[0] - req["prompt"].shape[0])
                if h.first_token_at is not None:
                    ttft_by_class[cls].append(
                        h.first_token_at - h.submitted_at)
                if cls == "high":
                    _append_itl(itl_high, h)
        except BaseException as e:
            with lock:
                errs.append(e)

    threads = []
    for req in workload:
        delay = t_start + req["arrival_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one, args=(req,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    if errs:
        raise errs[0]
    return {"latency": _percentiles(lat),
            "ttft_by_class": {c: _percentiles(v)
                              for c, v in ttft_by_class.items()},
            # the leg's headline percentile blocks are the HIGH class's
            # — the class the SLO is written for, and what perf_gate
            # reads as detail.qos.{ttft,inter_token}
            "ttft": _percentiles(ttft_by_class["high"]),
            "inter_token": _percentiles(itl_high),
            "tokens_per_sec": round(sum(toks) / max(wall, 1e-9), 2),
            "wall_s": round(wall, 3),
            "submitted": len(workload),
            "outcomes": outcomes,
            "retry_after_s_max": (round(max(retry_hints), 3)
                                  if retry_hints else None)}


def run_qos_storm(model, n_requests: int = 24, rate_hz: float = 20.0,
                  max_slots: int = 2, prefill_chunk: int = 8,
                  prefill_rows: int = 2, n_greedy: int = 3,
                  eos_id: Optional[int] = None, seed: int = 0,
                  registry=None, log=None) -> dict:
    """Replay ONE mixed-priority Poisson storm into a deliberately
    undersized engine (``max_slots`` far below the offered load) wired
    with the full QoS stack — a hair-trigger TTFT SLO objective so the
    burn-rate shedder fires on the real watchdog, zero preemption
    slack so waiting high-class requests evict batch slots through the
    KV-donation path, ``shed_classes=("low", "normal")`` so a severe
    burn widens the shed set, and a starved token bucket for the
    ``greedy`` tenant — then replay ONLY the high-class interactive
    requests through the SAME engine config as the uncontended
    baseline.

    The headline is ``high_ttft_p99_ratio`` (storm / uncontended high-
    class p99 TTFT; the acceptance bar is <= 1.25x — under a storm
    that sheds and preempts everything else, the top class's tail must
    stay within a quarter of its uncontended self). The row also
    carries the shed / preempted / rate-limited counts (all must be
    > 0: a storm that never fired the machinery measured nothing), the
    outcome-conservation verdict (client-side outcome tally == submits
    AND == the engine's own finished+shed+rate_limited accounting — no
    silent drops), and the per-tenant ledger breakdown."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    log = log or (lambda *a, **k: None)
    vocab = model.vocab_size
    wl = qos_storm_workload(n_requests, rate_hz, vocab,
                            n_greedy=n_greedy, seed=seed)
    # the uncontended baseline is the HIGH-PRIORITY traffic alone —
    # interactive AND greedy, at the same arrival offsets, under the
    # same rate limits — so any high-vs-high collision lands in both
    # legs identically and the ratio isolates what the STORM adds
    high_only = [q for q in wl if q["priority"] == "high"]
    warm_prompt = np.asarray(
        np.random.RandomState(seed + 1).randint(0, vocab, (12,)),
        np.int32)
    engine_kw = dict(
        max_slots=max_slots, prefill_chunk=prefill_chunk,
        prefill_rows=prefill_rows, eos_id=eos_id, registry=registry,
        # the burn objective is a tripwire, not a target: every real
        # TTFT lands over 0.1ms, so the storm's traffic itself drives
        # the watchdog into a SEVERE burn (burn 10 >= 2x threshold)
        # within min_count observations — shedding activates on the
        # same machinery production would use, just tuned to fire
        # min_count 3 = warm + the two leading lows: the slot-holding
        # batch work ADMITS before the burn trips, so the first high
        # arrival preempts a live victim; everything low/normal after
        # the trip sheds at submit
        slo_objectives=[{"name": "ttft_burn", "metric": "ttft",
                         "threshold_s": 1e-4, "target": 0.9,
                         "window_s": 30.0, "min_count": 3,
                         "burn_threshold": 2.0}],
        shed_classes=("low", "normal"),
        preempt_slack_s=0.0,
        tenant_rate_limits={"greedy": (0.01, 0.001)})

    def leg(name: str, work) -> dict:
        log(f"[serving-bench] qos {name} replay...")
        with ContinuousBatchingEngine(model, service_name=name,
                                      **engine_kw) as eng:
            eng.submit(warm_prompt, 4).result(timeout=300)
            res = _qos_replay(eng, work)
            stats = eng.stats()
        res["qos_state"] = stats["qos"]
        res.update(_usage_blocks(stats))
        res["cost"] = stats.get("cost")
        res["loop"] = stats.get("loop")
        res["alerts"] = stats["alerts"]
        # conservation against the ENGINE's own books, not just the
        # client's: every submit the engine saw must have landed in
        # exactly one terminal outcome counter
        qc = stats["qos"]
        eng_terminal = (stats["finished"] + qc["shed"]
                        + qc["rate_limited"] + stats["cancelled"]
                        + stats["timed_out"])
        client_terminal = sum(res["outcomes"].values())
        res["conservation_ok"] = bool(
            client_terminal == res["submitted"]
            # +1: the warm request finished outside the tally
            and eng_terminal == res["submitted"] + 1)
        return res

    storm = leg("bench_qos_storm", wl)
    uncont = leg("bench_qos_uncontended", high_only)

    def ratio(key):
        a = storm["ttft"][key]
        b = uncont["ttft"][key]
        return round(a / b, 4) if a and b else None

    qc = storm["qos_state"]
    return {
        "qos": storm, "uncontended": uncont,
        "high_ttft_p50_ratio": ratio("p50"),
        "high_ttft_p99_ratio": ratio("p99"),
        "shed": qc["shed"], "preempted": qc["preempted"],
        "rate_limited": qc["rate_limited"],
        "conservation_ok": bool(storm["conservation_ok"]
                                and uncont["conservation_ok"]),
        "workload": {"kind": "qos_storm", "requests": n_requests,
                     "n_greedy": n_greedy, "rate_hz": rate_hz,
                     "seed": seed, "max_slots": max_slots,
                     "prefill_rows": prefill_rows}}
