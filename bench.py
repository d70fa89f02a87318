"""Headline benchmark: ResNet-50 synthetic-ImageNet training throughput.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "imgs/sec/chip", "vs_baseline": N}

The reference publishes no imgs/sec table (BASELINE.md) — its north-star
target is ResNet-50 data-parallel at >70% of reference-JAX MFU. The
denominator is MEASURED in-process: bigdl_tpu/models/jax_resnet_ref.py is a
framework-free raw-JAX ResNet-50 step timed side-by-side on the same chip;
vs_baseline = ours_imgs_per_sec / (0.70 * ref_imgs_per_sec)  (>1.0 beats
the north star). If the ref measurement fails, the round-2 assumed constant
(50%-MFU reference -> 0.35 target MFU) stands in and ``detail.baseline_source``
says so.

detail also carries the LeNet-MNIST epoch wall-clock named by BASELINE.json.

One process, one device. The training headline needs a TPU and exits
non-zero without one; it never substitutes a model, re-runs elsewhere or
starts a child of itself. ``--serving`` modes are functional drives that run
on whatever device jax gives them, each row stamped with that device.
``--serving --fleet N`` opens no device in this process before its workers
are gone, and stamps the row with the device the workers report.

Run: python bench.py [--batch N] [--iters N] [--model resnet50]
"""

import argparse
import json
import sys

RESNET50_FWD_FLOPS_PER_IMG = 4.09e9  # 224x224, standard bottleneck count
TRAIN_FLOPS_MULT = 3.0               # fwd + bwd ≈ 3x fwd
TARGET_MFU = 0.35                    # 70% of an assumed 50%-MFU reference JAX impl


def _log(*a, **k):
    print(*a, file=sys.stderr, **k)


def _row_stamps(device_kind, mesh_shape=None):
    """Provenance fields every bench row carries: perf_gate refuses to
    compare rows across device kinds, and a jax upgrade explains a step
    change in the trend line."""
    import jax

    return {
        "device": device_kind,
        "device_kind": device_kind,
        "jax_version": jax.__version__,
        "mesh_shape": mesh_shape,
    }


def _cost_fields(leg):
    """mfu / membw_util / flops_source for one engine leg's detail row,
    from the cost-model block the engine replay attaches."""
    c = (leg or {}).get("cost") or {}
    overall = c.get("overall") or {}
    sources = {k.get("flops_source")
               for k in (c.get("kinds") or {}).values()
               if k.get("flops_source")}
    return {
        "mfu": overall.get("mfu"),
        "membw_util": overall.get("membw_util"),
        "flops_source": (sources.pop() if len(sources) == 1
                         else ("mixed" if sources else None)),
    }


def _emit(result):
    """Print the row and append it (+ UTC timestamp) to the trend file
    (``bench_history.jsonl`` beside this script; ``BIGDL_BENCH_HISTORY``
    overrides)."""
    import datetime
    import os

    print(json.dumps(result))
    rec = dict(result, ts=datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds"))
    path = (os.environ.get("BIGDL_BENCH_HISTORY")
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_history.jsonl"))
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as e:
        print(f"[bench] history append failed: {e}", file=sys.stderr)


def _lenet_epoch_wallclock(log):
    """LeNet-MNIST epoch wall-clock (BASELINE.json's second metric): one
    synthetic 60k-sample epoch, batch 512, through the standard train step."""
    import jax.numpy as jnp
    from bigdl_tpu.models.perf import run_perf

    batch, n_samples = 512, 60000
    iters = n_samples // batch  # 117
    s = run_perf("lenet5", batch_size=batch, iterations=iters, warmup=2,
                 dtype=jnp.float32, log=log)
    return round(s["time_s"], 3)


def main(argv=None):
    import os

    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--iters", type=int, default=None)
    p.add_argument("--model", default="resnet50")
    p.add_argument("--format", default=os.environ.get("BIGDL_BENCH_FORMAT", "NHWC"))
    p.add_argument("--serving", action="store_true",
                   help="Poisson-arrival serving benchmark: continuous-"
                        "batching engine vs GenerationService")
    p.add_argument("--shared-prefix", action="store_true",
                   help="with --serving: prefix-heavy workload (Poisson "
                        "arrivals over N shared prompt templates), "
                        "engine prefix-cache ON vs OFF — emits TTFT "
                        "speedup + hit rate into bench_history.jsonl")
    p.add_argument("--templates", type=int, default=4,
                   help="--shared-prefix: number of shared prompt "
                        "templates")
    p.add_argument("--working-set", type=int, default=0, metavar="N",
                   help="with --serving --shared-prefix: sweep the "
                        "shared-template working set up to N templates "
                        "round-robin against a 2-row device pool, host "
                        "tier sized to the working set vs device-only "
                        "vs cache-disabled — emits the hit-rate-cliff "
                        "A/B (per-point hit rate + TTFT, token parity, "
                        "jit-flat and ledger-conservation flags) into "
                        "bench_history.jsonl")
    p.add_argument("--speculative", action="store_true",
                   help="with --serving: repeated-text workload "
                        "replayed with an int8-draft speculative "
                        "engine vs the plain engine — emits the "
                        "inter-token p50/p99 A/B and the draft "
                        "acceptance rate into bench_history.jsonl")
    p.add_argument("--gamma", type=int, default=8,
                   help="--speculative: draft tokens proposed per "
                        "fused decode round (the int8 draft agrees "
                        "with its float source ~90%% of the time, so "
                        "a wide gamma amortizes dispatch overhead "
                        "hardest)")
    p.add_argument("--quantized", action="store_true",
                   help="with --serving: quantized A/B — the same "
                        "Poisson workload through the engine with "
                        "int8 KV pools + int8 target weights vs the "
                        "fp engine, plus both variants under the "
                        "int8-draft speculative path; emits the "
                        "inter-token p50/p99 speedups, membw_util "
                        "before/after, the logit-divergence quality "
                        "gate and the spec acceptance delta into "
                        "bench_history.jsonl")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="with --serving: multi-replica fleet A/B — one "
                        "shared-prefix Poisson storm through N spawn-"
                        "worker engine replicas routed by prefix "
                        "affinity vs round-robin, plus the mid-storm "
                        "drain drill; emits the affinity TTFT p50 "
                        "speedup, fleet hit-rate gain, zero-loss drain "
                        "verdict and token parity into "
                        "bench_history.jsonl")
    p.add_argument("--tp", type=int, default=0, metavar="N",
                   help="with --serving: tensor-parallel A/B — the "
                        "same Poisson workload through the engine "
                        "SHARDED over an N-way model-axis device mesh "
                        "(host-device mesh on CPU) vs the plain "
                        "single-device engine; emits both paths' TTFT "
                        "and inter-token percentiles + greedy token "
                        "parity into bench_history.jsonl")
    p.add_argument("--qos", action="store_true",
                   help="with --serving: SLO-aware QoS storm — one "
                        "mixed-priority Poisson storm (interactive "
                        "high, standard normal, batch low, plus an "
                        "over-budget greedy tenant) through a 2-slot "
                        "engine with burn-rate shedding, KV-donating "
                        "preemption and per-tenant token buckets, vs "
                        "the SAME high-class traffic uncontended; "
                        "emits the high-class TTFT p50/p99 ratios, "
                        "shed/preempted/rate-limited counts and the "
                        "outcome-conservation verdict into "
                        "bench_history.jsonl (the bar: p50 ratio "
                        "<= 1.25x, every QoS mechanism fired, no "
                        "silent drops)")
    p.add_argument("--trace", action="store_true",
                   help="also dump bench_trace.json — the run's span "
                        "trees + flight-recorder events as Chrome "
                        "trace JSON (open in Perfetto); path override: "
                        "BIGDL_BENCH_TRACE")
    p.add_argument("--profile", type=float, default=None,
                   metavar="SECONDS",
                   help="capture a jax.profiler trace of (up to) the "
                        "first SECONDS of the benchmark run — model/"
                        "engine build, compile, and warmup included "
                        "(observability.profiler); the artifact dir "
                        "lands in detail.profile_artifact")
    p.add_argument("--requests", type=int, default=24,
                   help="--serving: workload size")
    p.add_argument("--rate", type=float, default=20.0,
                   help="--serving: Poisson arrival rate (req/s)")
    args = p.parse_args(argv)

    if args.serving and args.tp and args.tp > 1:
        # the host-device mesh for --serving --tp: XLA reads this at
        # backend creation (first device use is below), so setting it
        # here still takes effect — on CPU it yields exactly tp
        # virtual devices, on real accelerators it is inert. Gated on
        # --serving: forcing virtual devices under a training bench
        # would divide its intra-op threads and poison the trend row.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.tp}")

    from bigdl_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # configuration only: opens no device

    if args.serving and args.fleet and args.fleet > 1:
        # before any device is opened here: a chip belongs to one
        # process, and the fleet's workers are processes of their own
        return _fleet_bench(args)

    import jax

    dev = jax.devices()[0]
    if args.serving:
        return _serving_bench(args, dev)

    if dev.platform != "tpu":
        print(f"[bench] the training headline needs a TPU; jax found "
              f"{dev.platform!r} ({dev.device_kind}). No row emitted.",
              file=sys.stderr)
        sys.exit(1)
    batch = args.batch or int(os.environ.get("BIGDL_BENCH_BATCH", "256"))
    iters = args.iters or 20
    model = args.model

    import jax.numpy as jnp

    from bigdl_tpu.models.perf import run_perf
    from bigdl_tpu.observability.costmodel import device_peaks
    from bigdl_tpu.version import __version__

    log = _log  # every bench line that is not THE row goes to stderr
    fmt = args.format if model.startswith("resnet") else "NCHW"
    # start as close to the profiled work as bench controls: run_perf
    # builds + compiles + warms + measures, all inside the capture
    prof = _start_profile(args.profile)
    s = run_perf(model, batch_size=batch, iterations=iters,
                 dtype=jnp.bfloat16 if model != "lenet5" else jnp.float32,
                 format=fmt,
                 master_f32=model != "lenet5",
                 log=log)

    imgs_per_sec = s["records_per_sec"]
    # per-image train FLOPs: XLA's own count from the step's executable
    # when run_perf extracted one, else the standard bottleneck constant
    # (said out loud: flops_source names which)
    if s.get("cost_source") == "xla":
        flops_per_img = s["flops_per_iter"] / batch
        flops_source = "xla"
    elif model == "resnet50":
        flops_per_img = RESNET50_FWD_FLOPS_PER_IMG * TRAIN_FLOPS_MULT
        flops_source = "analytic"
        print("[bench] XLA priced no FLOPs for the step; MFU uses the "
              "analytic ResNet-50 constant", file=sys.stderr)
    else:
        flops_per_img, flops_source = None, None
    peak = device_peaks(dev)["flops_per_s"]
    if model == "resnet50":
        mfu = imgs_per_sec * flops_per_img / peak
        # Until the measured denominator lands: assumed 50%-MFU reference.
        baseline_source = "assumed_0.50_mfu_ref"
        vs_baseline = mfu / TARGET_MFU
        metric = "resnet50_synthetic_imagenet_train_throughput"
    else:
        # No MFU north-star applies to the other models — vs_baseline is an
        # honest null, but a measured FLOP count still yields a real MFU
        # figure worth trending.
        mfu = (imgs_per_sec * flops_per_img / peak
               if flops_per_img else 0.0)
        baseline_source = None
        vs_baseline = None
        metric = f"{model}_synthetic_train_throughput"

    result = {
        "metric": metric,
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(vs_baseline, 4) if vs_baseline is not None else None,
        "detail": {
            "version": __version__,
            "batch": batch, "iters": iters,
            "dtype": "f32" if model == "lenet5" else "bf16",
            "format": fmt, "ms_per_iter": s["ms_per_iter"],
            "mfu": round(mfu, 4),
            "flops_source": flops_source,
            **_row_stamps(str(dev.device_kind)),
            "ref_jax_mfu": None,
            "baseline_source": baseline_source,
            "target_mfu": TARGET_MFU,
            "lenet_mnist_epoch_s": None,
        },
    }
    # Measured denominator: raw-JAX ResNet-50 on the same chip.
    if model == "resnet50" and not os.environ.get("BIGDL_BENCH_NOREF"):
        try:
            from bigdl_tpu.models.jax_resnet_ref import run_ref_perf
            r = run_ref_perf(batch_size=batch, iterations=max(5, iters // 2),
                             log=log)
            ref_achieved = (r["records_per_sec"] * RESNET50_FWD_FLOPS_PER_IMG
                            * TRAIN_FLOPS_MULT)
            result["detail"]["ref_jax_mfu"] = round(
                ref_achieved / peak, 4)
            result["vs_baseline"] = round(
                imgs_per_sec / (0.70 * r["records_per_sec"]), 4)
            result["detail"]["baseline_source"] = "measured_raw_jax_ref"
        except Exception as e:
            print(f"[bench] ref-jax denominator failed: {e}", file=sys.stderr)

    if not os.environ.get("BIGDL_BENCH_NOLENET"):
        try:
            result["detail"]["lenet_mnist_epoch_s"] = _lenet_epoch_wallclock(log)
        except Exception as e:
            print(f"[bench] lenet epoch metric failed: {e}", file=sys.stderr)

    art = _finish_profile(prof)
    if art is not None:
        result["detail"]["profile_artifact"] = art
    result["detail"]["memory"] = _memory_snapshot()
    _record_bench_metrics(result, model)
    _dump_prometheus_snapshot()
    if args.trace:
        _dump_chrome_trace()
    _emit(result)


def _serving_bench(args, dev):
    """`--serving`: replay ONE Poisson-arrival workload through the
    continuous-batching engine and through GenerationService; emit one
    JSON line (p50/p99 latency, TTFT, aggregate tokens/sec for both
    paths) into bench_history.jsonl + the Prometheus snapshot so the
    serving perf trajectory is tracked alongside the training headline.
    vs_baseline is the p99-latency speedup over GenerationService
    (> 1.0: the engine's tail is shorter). Engine rows also carry the
    usage ledger's goodput block (padding waste, utilization, tokens
    per device-second) and the per-tenant token/device-second
    breakdown; `scripts/perf_gate.py` additionally gates goodput
    between comparable rows, skipping rows that predate the field.

    `--serving --shared-prefix`: the prefix-heavy variant — Poisson
    arrivals over N shared prompt templates, replayed through the
    engine with its prefix cache ON vs OFF. vs_baseline is the p50
    TTFT speedup of the cached path (>1.0: the cache pays for itself;
    the acceptance bar is >=2x), and detail carries the hit rate,
    reused-token fraction, and greedy token-parity flag.
    `scripts/perf_gate.py` gates CI on the p99 TTFT of consecutive
    comparable rows.

    `--serving --shared-prefix --working-set N`: the tiered-cache
    sweep — round-robin template workloads at working sets from inside
    to N-templates past a 2-row device pool, each replayed through a
    host-tier engine (host rows = working set), a device-only engine,
    and a cache-disabled oracle. value is the headline tiered hit rate
    at the deepest point, vs_baseline the tiered/device-only hit-rate
    gain there (the device-only leg LRU-thrashes once the working set
    exceeds its rows; the bar is >=2x at >=4x the budget), and detail
    carries the per-point sweep plus token-parity / jit-flat /
    ledger-conservation flags. perf_gate gates the headline hit rate
    (higher-is-better) and the tiered leg's p50/p99 TTFT.

    `--serving --speculative`: the speculative A/B — one repeated-text
    Poisson workload replayed through the engine with an int8-clone
    draft (gamma proposals per fused round) vs the plain engine.
    vs_baseline is the inter-token p50 speedup of the speculative path
    (>1.0: the draft pays for itself), and detail carries both paths'
    inter-token p50/p99, the acceptance rate, and the greedy
    token-parity flag; perf_gate gates the speculative row's p99
    inter-token (and TTFT / goodput) between comparable runs.

    `--serving --fleet N`: the multi-replica fleet A/B — one shared-
    prefix Poisson storm replayed through N spawn-worker engine
    replicas (each its own process, model, engine, budget-bound prefix
    trie) routed by the PrefixAffinityRouter vs round-robin, plus the
    mid-storm drain drill (one replica drains and rejoins; zero lost
    requests is the bar). value/vs_baseline is the affinity-vs-round-
    robin client TTFT p50 speedup (>1.0: content-aware routing lands
    first tokens sooner), and detail carries both legs' percentiles,
    the fleet hit rates, the routing tallies, the drain block, the
    token-parity verdict against a single-replica reference, plus the
    affinity leg's capacity stamp (detail.capacity: fleet headroom,
    replicas-needed, per-role device-wall split) and SLO error-budget
    floor (detail.slo_budget.remaining_min). perf_gate gates the
    speedup, the fleet hit rate, the affinity leg's p99 TTFT, the
    capacity headroom band, and the calm-run budget floor between
    comparable rows.

    `--serving --tp N`: the tensor-parallel A/B — the same Poisson
    workload through the engine SHARDED over an N-way model-axis
    device mesh (a host-device mesh on CPU: the flag forces N virtual
    host devices) vs the plain single-device engine. vs_baseline is
    the inter-token p50 ratio unsharded/sharded (on CPU expect < 1.0
    — collectives cost and host compute doesn't shrink; the row
    tracks that overhead and pins greedy token parity + the sharded
    mesh/pool attribution block). perf_gate gates the sharded row's
    p99 TTFT / inter-token / goodput between comparable runs.

    `--serving --qos`: the QoS storm — one mixed-priority Poisson
    storm (interactive high-class, standard normal, batch low, plus a
    deliberately over-budget "greedy" tenant) through a 2-slot engine
    running the full QoS stack (burn-rate shedding of low/normal,
    KV-donating preemption, per-tenant token buckets), vs the SAME
    high-class traffic replayed uncontended. value is the storm leg's
    high-class TTFT p99; vs_baseline is the storm/uncontended
    high-class TTFT p50 ratio (~1.0: shedding + preemption hold the
    top class at its uncontended self; the bar is <= 1.25x). detail
    carries both legs' percentiles, per-class TTFT, the shed /
    preempted / rate-limited counts and the outcome-conservation
    verdict (every submission ended in exactly one terminal state).
    perf_gate gates the p50 ratio at the 1.25 ceiling, requires every
    QoS mechanism to have fired, conservation to hold, and bands the
    storm leg's high-class TTFT between comparable rows; the p99
    ratio rides along ungated (max-of-few-samples tail)."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving.benchmark import (
        run_poisson_comparison, run_qos_storm,
        run_quantized_comparison, run_shared_prefix_comparison,
        run_speculative_comparison, run_tp_comparison,
        run_working_set_sweep,
    )
    from bigdl_tpu.utils import random as rnd
    from bigdl_tpu.version import __version__

    log = _log  # every bench line that is not THE row goes to stderr
    kind = str(dev.device_kind)
    rnd.set_seed(7)
    model = TransformerLM(128, embed_dim=64, num_heads=4, num_kv_heads=2,
                          num_layers=2, max_len=128, use_rope=True)
    model.evaluate()
    prof = _start_profile(args.profile)
    if args.tp and args.tp > 1:
        res = run_tp_comparison(
            model, tp=args.tp, n_requests=args.requests,
            rate_hz=args.rate, max_slots=4, prefill_chunk=8,
            prefill_rows=2, log=log)
        result = {
            "metric": "serving_tp_tokens_per_sec",
            "value": res["sharded"]["tokens_per_sec"],
            "unit": "tokens/sec",
            # vs_baseline > 1.0: the sharded path's steady-state
            # decode gap is shorter than single-device (expect < 1.0
            # on a CPU host mesh, where collectives cost and compute
            # doesn't shrink — the row exists to track the overhead)
            "vs_baseline": res["inter_token_p50_ratio"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind, mesh_shape={"model": args.tp}),
                **_cost_fields(res["sharded"]),
                **res,
            },
        }
        _record_tp_metrics(res)
    elif args.qos:
        res = run_qos_storm(
            model, n_requests=args.requests, rate_hz=args.rate,
            max_slots=2, prefill_chunk=8, prefill_rows=2, log=log)
        result = {
            "metric": "serving_qos_high_ttft_p99",
            "value": res["qos"]["ttft"]["p99"],
            "unit": "seconds",
            # vs_baseline ~ 1.0: under a mixed-priority storm the
            # high class's MEDIAN first token lands where it would
            # uncontended — shedding + preemption absorbed the
            # contention (the acceptance bar is <= 1.25x)
            "vs_baseline": res["high_ttft_p50_ratio"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["qos"]),
                **res,
            },
        }
        _record_qos_metrics(res)
    elif args.quantized:
        res = run_quantized_comparison(
            model, n_requests=args.requests, rate_hz=args.rate,
            max_slots=4, prefill_chunk=8, prefill_rows=2,
            gamma=args.gamma, log=log)
        result = {
            "metric": "serving_quantized_tokens_per_sec",
            "value": res["quantized"]["tokens_per_sec"],
            "unit": "tokens/sec",
            # vs_baseline > 1.0: the int8 engine's steady-state decode
            # gap is shorter than fp's on the same workload (on CPU
            # expect ~1.0 — int8 matmuls aren't faster on host BLAS;
            # the row pins the quality gate and byte attribution, and
            # membw-bound accelerators collect the speedup)
            "vs_baseline": res["inter_token_p50_speedup"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["quantized"]),
                **res,
            },
        }
        _record_quantized_metrics(res)
    elif args.speculative:
        res = run_speculative_comparison(
            model, n_requests=args.requests, rate_hz=args.rate,
            max_slots=4, prefill_chunk=8, prefill_rows=2,
            gamma=args.gamma, log=log)
        result = {
            "metric": "serving_speculative_tokens_per_sec",
            "value": res["spec"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": res["inter_token_p50_speedup"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["spec"]),
                **res,
            },
        }
        _record_speculative_metrics(res)
    elif args.shared_prefix and args.working_set:
        res = run_working_set_sweep(
            model, working_sets=(2, max(4, args.working_set)),
            device_rows=2, rate_hz=args.rate, max_slots=4,
            prefill_chunk=8, prefill_rows=2, template_len=16, log=log)
        result = {
            "metric": "serving_tiered_prefix_hit_rate",
            "value": res["headline"]["tiered_hit_rate"],
            "unit": "fraction",
            # vs_baseline > 1.0: the host tier holds the hit rate the
            # device-only cache loses past its budget (the acceptance
            # bar is >=2x at a working set >=4x the device pool)
            "vs_baseline": res["headline"]["hit_rate_gain"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["tiered"]),
                **res,
            },
        }
        _record_working_set_metrics(res)
    elif args.shared_prefix:
        res = run_shared_prefix_comparison(
            model, n_requests=args.requests, rate_hz=args.rate,
            max_slots=4, prefill_chunk=8, prefill_rows=2,
            n_templates=args.templates, template_len=96, log=log)
        result = {
            "metric": "serving_shared_prefix_tokens_per_sec",
            "value": res["cached"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": res["ttft_p50_speedup"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["cached"]),
                **res,
            },
        }
        _record_shared_prefix_metrics(res)
    else:
        res = run_poisson_comparison(model, n_requests=args.requests,
                                     rate_hz=args.rate, max_slots=4,
                                     prefill_chunk=8, log=log)
        result = {
            "metric": "serving_poisson_tokens_per_sec",
            "value": res["engine"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": res["p99_speedup"],
            "detail": {
                "version": __version__,
                **_row_stamps(kind),
                **_cost_fields(res["engine"]),
                **res,
            },
        }
        _record_serving_metrics(res)
    art = _finish_profile(prof)
    if art is not None:
        result["detail"]["profile_artifact"] = art
    result["detail"]["memory"] = _memory_snapshot()
    _dump_prometheus_snapshot()
    if args.trace:
        _dump_chrome_trace()
    _emit(result)


def _fleet_bench(args):
    """``--serving --fleet N``. Runs before this process has opened any
    device: the workers are processes of their own and each needs the
    device to itself. The row is stamped with the device the workers
    report, never with this process's."""
    from bigdl_tpu.serving.fleet import run_fleet_comparison
    from bigdl_tpu.version import __version__

    if args.profile:
        sys.exit("[bench] --profile cannot go with --fleet: a capture "
                 "here would open the device before the workers do")
    log = _log  # every bench line that is not THE row goes to stderr
    res = run_fleet_comparison(
        n_replicas=args.fleet, n_requests=args.requests,
        rate_hz=args.rate, log=log)
    result = {
        "metric": "serving_fleet_ttft_p50_speedup",
        "value": res["ttft_p50_speedup"],
        "unit": "ratio",
        # vs_baseline > 1.0: the affinity leg's median first token
        # lands sooner than round-robin's on the same storm
        "vs_baseline": res["ttft_p50_speedup"],
        "detail": {
            "version": __version__,
            **_row_stamps(res["worker_device"]["kind"]),
            **res,
            # headline hop decomposition: the affinity leg's mean
            # seconds per fleet hop (route/rpc_submit/queue/
            # prefill/first_token/decode/stream)
            "hops": (res.get("affinity") or {}).get("hops"),
        },
    }
    _record_fleet_metrics(res)
    result["detail"]["memory"] = _memory_snapshot()
    _dump_prometheus_snapshot()
    if args.trace:
        _dump_chrome_trace()
    _emit(result)


def _start_profile(seconds):
    """``--profile``: begin a jax.profiler capture of the measured run
    plus a timer that stops it at the requested bound (whichever of
    run-end / timer comes first wins — stop_capture is idempotent).
    Returns an opaque handle for ``_finish_profile``, or None."""
    if not seconds or seconds <= 0:
        return None
    import threading

    from bigdl_tpu.observability import profiler

    try:
        path = profiler.start_capture()
    except Exception as e:
        print(f"[bench] profiler capture unavailable: {e}",
              file=sys.stderr)
        return None
    timer = threading.Timer(min(float(seconds), profiler.MAX_SECONDS),
                            profiler.stop_capture, kwargs={"strict": False})
    timer.daemon = True
    timer.start()
    print(f"[bench] profiling up to {seconds}s -> {path}",
          file=sys.stderr)
    return {"path": path, "timer": timer}


def _finish_profile(prof):
    """Stop the ``--profile`` capture (if the timer has not already)
    and return the artifact directory, or None when not profiling."""
    if prof is None:
        return None
    from bigdl_tpu.observability import profiler

    prof["timer"].cancel()
    try:
        profiler.stop_capture(strict=False)
    except Exception as e:
        print(f"[bench] profiler stop failed: {e}", file=sys.stderr)
    return prof["path"]


def _memory_snapshot():
    """One device-memory sample for the result's detail block: total
    bytes in use, per-device source, and the per-pool attribution the
    run registered (KV pools, params, optimizer slots). Never lets
    telemetry break the bench."""
    try:
        from bigdl_tpu.observability.memory import default_monitor

        s = default_monitor().sample()
        return {
            "bytes_in_use": s["bytes_in_use"],
            "devices": [{k: d[k] for k in
                         ("device", "bytes_in_use", "limit_bytes",
                          "source")}
                        for d in s["devices"]],
            "pools": s["pools"],
        }
    except Exception as e:
        print(f"[bench] memory snapshot failed: {e}", file=sys.stderr)
        return None


def _record_shared_prefix_metrics(res):
    """Mirror the shared-prefix comparison into the observability
    registry (``path`` label: cached / uncached) so live scrapes and
    bench snapshots share one schema. Never lets telemetry break the
    bench."""
    try:
        from bigdl_tpu import observability as obs

        # instruments resolve against the CURRENT default registry —
        # the same one the snapshot dump renders
        ins = obs.serving_bench_instruments()
        for path in ("cached", "uncached"):
            _record_path_metrics(ins, res[path], path)
        if res.get("ttft_p50_speedup") is not None:
            ins.prefix_ttft_p50_speedup().set(res["ttft_p50_speedup"])
        pc = res["cached"].get("prefix_cache", {})
        if pc.get("enabled"):
            ins.prefix_hit_rate().set(pc["hit_rate"])
            ins.prefix_reused_fraction().set(pc["reused_fraction"])
    except Exception as e:
        print(f"[bench] shared-prefix metrics registry update failed: "
              f"{e}", file=sys.stderr)


def _record_working_set_metrics(res):
    """Mirror the working-set sweep's HEADLINE point into the
    observability registry (``path`` label: tiered / device_only) so
    live scrapes and bench snapshots share one schema. Never lets
    telemetry break the bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path in ("tiered", "device_only"):
            _record_path_metrics(ins, res[path], path)
        head = res.get("headline") or {}
        if head.get("tiered_hit_rate") is not None:
            ins.tiered_hit_rate().set(head["tiered_hit_rate"])
        if head.get("hit_rate_gain") is not None:
            ins.tiered_hit_rate_gain().set(head["hit_rate_gain"])
    except Exception as e:
        print(f"[bench] working-set metrics registry update failed: "
              f"{e}", file=sys.stderr)


def _record_speculative_metrics(res):
    """Mirror the speculative A/B into the observability registry
    (``path`` label: spec_on / spec_off) so live scrapes and bench
    snapshots share one schema. Never lets telemetry break the
    bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("spec_on", "spec"), ("spec_off", "nospec")):
            _record_path_metrics(ins, res[key], path)
        if res.get("acceptance_rate") is not None:
            ins.spec_acceptance_rate().set(res["acceptance_rate"])
        if res.get("inter_token_p50_speedup") is not None:
            ins.spec_inter_token_p50_speedup().set(
                res["inter_token_p50_speedup"])
    except Exception as e:
        print(f"[bench] speculative metrics registry update failed: "
              f"{e}", file=sys.stderr)


def _record_quantized_metrics(res):
    """Mirror the quantized A/B into the observability registry
    (``path`` label: quant_on / quant_off / quant_spec_fp /
    quant_spec_int8) plus the unlabeled quality-gate scalars. Never
    lets telemetry break the bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("quant_on", "quantized"),
                          ("quant_off", "fp_baseline"),
                          ("quant_kv_only", "kv_only"),
                          ("quant_spec_fp", "spec_fp"),
                          ("quant_spec_int8", "spec_int8")):
            _record_path_metrics(ins, res[key], path)
        if res.get("inter_token_p50_speedup") is not None:
            ins.quant_inter_token_p50_speedup().set(
                res["inter_token_p50_speedup"])
        if res.get("inter_token_p99_speedup") is not None:
            ins.quant_inter_token_p99_speedup().set(
                res["inter_token_p99_speedup"])
        q = res.get("quality") or {}
        if q.get("logit_div_rel") is not None:
            ins.quant_logit_div_rel().set(q["logit_div_rel"])
        if q.get("acceptance_delta") is not None:
            ins.quant_acceptance_delta().set(q["acceptance_delta"])
        ratio = (res.get("capacity") or {}).get("row_bytes_ratio")
        if ratio is not None:
            ins.quant_row_bytes_ratio().set(ratio)
    except Exception as e:
        print(f"[bench] quantized metrics registry update failed: {e}",
              file=sys.stderr)


def _record_fleet_metrics(res):
    """Mirror the fleet A/B into the observability registry (``path``
    label: fleet_affinity / fleet_round_robin) so live scrapes and
    bench snapshots share one schema. Never lets telemetry break the
    bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("fleet_affinity", "affinity"),
                          ("fleet_round_robin", "round_robin")):
            _record_path_metrics(ins, res[key], path)
        if res.get("ttft_p50_speedup") is not None:
            ins.fleet_ttft_p50_speedup().set(res["ttft_p50_speedup"])
        hit = (res.get("affinity", {}).get("fleet") or {}).get("hit_rate")
        if hit is not None:
            ins.fleet_hit_rate().set(hit)
    except Exception as e:
        print(f"[bench] fleet metrics registry update failed: {e}",
              file=sys.stderr)


def _record_qos_metrics(res):
    """Mirror the QoS storm A/B into the observability registry
    (``path`` label: qos_storm / qos_uncontended) plus the unlabeled
    ratio / mechanism-count scalars. Never lets telemetry break the
    bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("qos_storm", "qos"),
                          ("qos_uncontended", "uncontended")):
            _record_path_metrics(ins, res[key], path)
        if res.get("high_ttft_p50_ratio") is not None:
            ins.qos_high_ttft_p50_ratio().set(
                res["high_ttft_p50_ratio"])
        if res.get("high_ttft_p99_ratio") is not None:
            ins.qos_high_ttft_p99_ratio().set(
                res["high_ttft_p99_ratio"])
        for key, gauge in (("preempted", ins.qos_preempted),
                           ("shed", ins.qos_shed),
                           ("rate_limited", ins.qos_rate_limited)):
            if res.get(key) is not None:
                gauge().set(res[key])
    except Exception as e:
        print(f"[bench] qos metrics registry update failed: {e}",
              file=sys.stderr)


def _record_goodput_metrics(ins, block, path):
    """Mirror one serving result's usage-ledger goodput block (emitted
    by the engine replays in ``bigdl_tpu.serving.benchmark``) into the
    ``path``-labelled bench gauges."""
    g = block.get("goodput") or {}
    if g.get("tokens_per_device_second") is not None:
        ins.goodput_tokens_per_device_second.labels(path).set(
            g["tokens_per_device_second"])
    if g.get("padding_waste_mean") is not None:
        ins.padding_waste_mean.labels(path).set(g["padding_waste_mean"])


def _record_path_metrics(ins, r, path):
    """Mirror ONE serving-comparison leg's standard result block
    (throughput, latency / TTFT / inter-token percentiles, goodput)
    into the ``path``-labelled bench gauges — the shared body of every
    per-variant recorder, so a gauge added here reaches all of them."""
    ins.tokens_per_sec.labels(path).set(r["tokens_per_sec"])
    if r.get("latency", {}).get("p50") is not None:
        ins.latency_p50.labels(path).set(r["latency"]["p50"])
        ins.latency_p99.labels(path).set(r["latency"]["p99"])
    if r.get("ttft", {}).get("p50") is not None:
        ins.ttft_p50.labels(path).set(r["ttft"]["p50"])
        ins.ttft_p99_by_path.labels(path).set(r["ttft"]["p99"])
    if r.get("inter_token", {}).get("p99") is not None:
        ins.inter_token_p99.labels(path).set(r["inter_token"]["p99"])
    _record_goodput_metrics(ins, r, path)


def _record_tp_metrics(res):
    """Mirror the tensor-parallel A/B into the observability registry
    under ``path`` labels (``tp_sharded`` / ``tp_unsharded``). Never
    lets telemetry break the bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("tp_sharded", "sharded"),
                          ("tp_unsharded", "unsharded")):
            _record_path_metrics(ins, res[key], path)
    except Exception as e:
        print(f"[bench] tp metrics registry update failed: {e}",
              file=sys.stderr)


def _record_serving_metrics(res):
    """Mirror the serving comparison into the observability registry
    under a ``path`` label, so live scrapes and bench snapshots share
    one schema. Never lets telemetry break the bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.serving_bench_instruments()
        for path, key in (("engine", "engine"),
                          ("generation_service", "generation_service")):
            r = res[key]
            ins.tokens_per_sec.labels(path).set(r["tokens_per_sec"])
            if r["latency"]["p50"] is not None:
                ins.latency_p50.labels(path).set(r["latency"]["p50"])
                ins.latency_p99.labels(path).set(r["latency"]["p99"])
        eng = res["engine"]
        if eng.get("ttft", {}).get("p99") is not None:
            ins.ttft_p99().set(eng["ttft"]["p99"])
        if eng.get("inter_token", {}).get("p99") is not None:
            ins.inter_token_p99.labels("engine").set(
                eng["inter_token"]["p99"])
        if res.get("p99_speedup") is not None:
            ins.p99_speedup().set(res["p99_speedup"])
        _record_goodput_metrics(ins, eng, "engine")
    except Exception as e:
        print(f"[bench] serving metrics registry update failed: {e}",
              file=sys.stderr)


def _record_bench_metrics(result, model):
    """Mirror the headline numbers into the observability registry —
    bench snapshots and live scrapes then share one metric schema
    (bigdl_* names, all minted in observability/instruments.py — the
    metrics lint holds that line), so the perf trajectory is diffable
    against production telemetry. Never lets telemetry break the
    bench."""
    try:
        from bigdl_tpu import observability as obs

        ins = obs.bench_instruments()
        d = result["detail"]
        ins.imgs_per_sec.labels(model).set(result["value"])
        ins.ms_per_iter.labels(model).set(d["ms_per_iter"])
        ins.mfu.labels(model).set(d["mfu"])
        if result.get("vs_baseline") is not None:
            ins.vs_baseline.labels(model).set(result["vs_baseline"])
        if d.get("lenet_mnist_epoch_s") is not None:
            ins.lenet_epoch_seconds().set(d["lenet_mnist_epoch_s"])
    except Exception as e:
        print(f"[bench] metrics registry update failed: {e}",
              file=sys.stderr)


def _dump_artifact(env_var, filename, writer_name, label):
    """Drop one observability artifact next to the BENCH_*.json trend
    files (path overridable via ``env_var``); ``writer_name`` is the
    ``bigdl_tpu.observability`` export that does the actual write.
    Never lets telemetry break the bench."""
    import os

    try:
        from bigdl_tpu import observability as obs

        path = (os.environ.get(env_var)
                or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                filename))
        getattr(obs, writer_name)(path)
        print(f"[bench] {label} -> {path}", file=sys.stderr)
    except Exception as e:
        print(f"[bench] {label} failed: {e}", file=sys.stderr)


def _dump_chrome_trace():
    """`--trace`: Chrome trace-event JSON of the run (span trees +
    flight-recorder request timelines) alongside bench_metrics.prom —
    one serving benchmark run becomes one Perfetto-loadable artifact."""
    _dump_artifact("BIGDL_BENCH_TRACE", "bench_trace.json",
                   "write_chrome_trace", "chrome trace")


def _dump_prometheus_snapshot():
    """Prometheus text snapshot alongside the BENCH_*.json trend files.
    Includes everything the run put in the default registry — bench
    gauges plus any bigdl_train_* series the perf loops populated."""
    _dump_artifact("BIGDL_BENCH_PROM", "bench_metrics.prom",
                   "write_prometheus", "prometheus snapshot")


if __name__ == "__main__":
    main()
