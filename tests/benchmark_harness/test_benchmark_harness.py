"""The benchmark's yardstick, checked on the CPU: the manifest resolves to
files, the traffic generator is deterministic, the window / trace / count
arithmetic is right on hand-made inputs, each driver runs end to end at a
tiny size (and writes no device metric), and ``correct`` comes out false for
a lower precision and for a broken timed path."""

import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import manifest_invariants  # noqa: E402  (beside this file)
from benchmark import compare, counts, harness, loadgen, reduce_trace  # noqa: E402



@pytest.fixture
def no_chip_needed(monkeypatch):
    """Skip the harness's look for a chip: the rest of a run on the CPU."""
    import jax

    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])


# ---------------------------------------------------------------- manifest
def test_manifest_names_units_and_files_resolve():
    manifest_invariants.names_units_and_files_resolve(harness.manifest(),
                                                      ROOT)


# ----------------------------------------------------------------- traffic
def test_traffic_is_seeded_clipped_and_one_schedule_for_every_seed():
    # (the mix's own lengths, clips and sharing; any seed up to 2**31 and over)
    mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    a = loadgen.open_loop_requests(mix, 2 ** 31 + 5, 40, 50257)
    b = loadgen.open_loop_requests(mix, 2 ** 31 + 5, 40, 50257)
    c = loadgen.open_loop_requests(mix, 7, 40, 50257)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["due_s"] == y["due_s"] for x, y in zip(a, b))
    sp = mix["shared_prefix"]
    for r in a:
        assert 16 <= len(r["prompt"]) <= 768 and 8 <= r["new_tokens"] <= 256
        assert len(r["prompt"]) + r["new_tokens"] <= 1024
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 50257
        if r["shared"] >= 0:
            assert len(r["prompt"]) >= sp["tokens"] + sp["min_own_tokens"]
    heads = {r["shared"]: tuple(r["prompt"][:sp["tokens"]])
             for r in a if r["shared"] >= 0}
    assert len(heads) == sp["count"] and all(
        tuple(r["prompt"][:sp["tokens"]]) == heads[r["shared"]]
        for r in a if r["shared"] >= 0)
    # another seed: other tokens, the mix's one schedule
    assert [(r["due_s"], r["new_tokens"], len(r["prompt"])) for r in a] == \
        [(r["due_s"], r["new_tokens"], len(r["prompt"])) for r in c]
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
    with pytest.raises(ValueError):
        loadgen.open_loop_requests(
            dict(mix, arrivals={"process": "uniform", "rate_per_s": 1.0}),
            7, 40, 50257)
    bursty = loadgen.open_loop_requests(
        dict(mix, arrivals={"process": "gamma", "cv": 3.0, "rate_per_s": 1.0}),
        7, 40, 50257)
    assert len(bursty) == len(a) and bursty[-1]["due_s"] < 40
    n = round(mix["arrivals"]["rate_per_s"] * (40 + mix["lead_in_s"]))
    assert len(a) == len(c) == n
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and -mix["lead_in_s"] <= due[0] and due[-1] < 40
    rec = loadgen.describe_requests(a, 40)
    assert rec["requests"] + rec["lead_in_requests"] == n


# ---------------------------------------------------- window and statistics
def test_percentile():
    assert harness.percentile([], 95) is None
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile([0, 10], 95) == pytest.approx(9.5)
    assert harness.median([5, 1, 3]) == 3


class _Done:
    def done(self):
        return True


def _finished(serve, req, stamps, t0):
    """``req`` as its client saw it served, made by hand: due ``due_s`` into
    the window that opens at ``t0`` (a lead-in request where that is
    negative), a token at each of ``stamps``."""
    c = serve.Client(req, t0 + req["due_s"])
    c.handle, c.stamps, c.tokens = _Done(), list(stamps), [0] * len(stamps)
    c.submitted = c.due + 0.002
    return c


def _client(serve, due_s, stamps, new=None, t0=100.0):
    return _finished(serve, {"due_s": due_s, "prompt": np.zeros(4, np.int32),
                             "new_tokens": new if new is not None
                             else len(stamps)}, stamps, t0)


def test_window_arithmetic_counts_from_the_due_time():
    serve = harness.load_module("drivers", "serve")
    t0, seconds = 100.0, 10.0
    clients = [
        _client(serve, 1.0, [101.5, 101.6, 101.8]),      # ttft 500 ms
        _client(serve, 2.0, [102.1, 109.9, 110.4]),      # last token outside
        _client(serve, 9.0, [], new=5),                  # never served
        _client(serve, -1.0, [100.5, 100.6]),           # lead-in: not measured
    ]
    measured, failed, e2e, extra = serve.window_numbers(
        clients, t0, seconds, cutoff=115.0)
    assert len(measured) == 3 and len(failed) == 1
    # the unserved request waited from its due time to the cut-off
    assert sorted(e2e["ttft_ms"]) == pytest.approx([100.0, 500.0, 6000.0])
    assert harness.load_module("metrics", "ttft_p95_ms").value(
        {"ttft_ms": e2e["ttft_ms"]}, None) == pytest.approx(
        harness.percentile([100.0, 500.0, 6000.0], 95))
    assert extra["gaps"] == 4
    assert e2e["itl_p95_ms"] == pytest.approx(harness.percentile(
        [100.0, 200.0, 7800.0, 500.0], 95), rel=1e-6)
    # tokens inside [t0, t0 + seconds] of the requests DUE in the window: the
    # lead-in request's two are in the log's other count only
    assert set(e2e) == {"ttft_ms", "itl_p95_ms", "serve_due_tok_per_s"}
    assert e2e["serve_due_tok_per_s"] == pytest.approx((3 + 2) / 10.0)
    assert extra["due_tokens_in_window"] == 5
    assert extra["tokens_in_window"] == 7
    late = [(c.submitted - c.due) * 1e3 for c in measured]
    assert max(late) == pytest.approx(2.0)


def _streams(serve, speedup, t0=100.0):
    """Four requests against a window [t0, t0 + 10]: two lead-in requests
    whose tails reach into it, two due inside it of which one runs past its
    end. ``speedup`` shortens every request's own time line (first token
    and every gap) by that factor: no stamp comes before its due time."""
    plan = [(-6.0, 1.0, 0.5, 30),      # (due_s, to first token, gap, tokens)
            (-0.5, 0.8, 0.4, 12),
            (1.0, 1.0, 0.5, 10),
            (6.0, 1.0, 0.5, 14)]
    return [_client(serve, due, [t0 + due + (first + k * gap) / speedup
                                 for k in range(n)])
            for due, first, gap, n in plan]


SPEEDUPS = [1.0, 1.25, 1.6, 2.0, 3.0, 5.0, 50.0]


@pytest.mark.parametrize("slower, faster", zip(SPEEDUPS, SPEEDUPS[1:]))
def test_a_faster_engine_never_lowers_the_due_count(slower, faster):
    """The property the count is there for, and the artefact it replaced,
    pinned: with every stamp moved earlier (never before its request's due
    time) the tokens of the requests due in the window can only enter it,
    while the count over all clients loses the lead-in requests' tails."""
    serve = harness.load_module("drivers", "serve")

    def counts(speedup):
        clients = _streams(serve, speedup)
        assert all(c.stamps[0] >= c.due for c in clients)
        _, failed, e2e, extra = serve.window_numbers(
            clients, 100.0, 10.0, cutoff=200.0)
        assert not failed
        assert e2e["serve_due_tok_per_s"] * 10.0 == pytest.approx(
            extra["due_tokens_in_window"])
        return extra["due_tokens_in_window"], extra["tokens_in_window"]

    assert counts(1.0) == (10 + 7, 10 + 7 + 20 + 12)
    assert counts(faster)[0] >= counts(slower)[0] >= counts(1.0)[0]
    if faster >= 2.0:
        # the artefact: the faster engine ended the lead-in tails before the
        # window opened and reads FEWER tokens by the old count
        assert counts(faster)[1] < counts(1.0)[1]
    if faster == 50.0:
        # all of the window's requests, whole: the offered load
        assert counts(faster) == (10 + 14, 10 + 14)


def _replayed(serve, step_ms, seconds=51.0, t0=1000.0):
    """PERF.md section 7 (f)'s replay of ``gpt2l-chat-steady`` over the mix's
    committed schedule, as hand-made clients: a turn is the decode step plus
    6 ms of host, 1.5 % over; a request's first token comes one turn a
    128-token chunk of its prompt (and half a turn) after its due time, the
    next every turn."""
    mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    reqs = loadgen.open_loop_requests(mix, 1, seconds, 50257)
    turn = 1.015 * (step_ms + 6.0) / 1e3

    def first(r):
        return r["due_s"] + (math.ceil(len(r["prompt"]) / 128) + 0.5) * turn

    clients = [_finished(serve, r, [t0 + first(r) + k * turn
                                    for k in range(r["new_tokens"])], t0)
               for r in sorted(reqs, key=lambda r: r["due_s"])]
    offered = sum(r["new_tokens"] for r in reqs if r["due_s"] >= 0) / seconds
    return clients, offered


def test_replay_of_chat_steady_reads_the_offered_load_at_every_speed():
    serve = harness.load_module("drivers", "serve")
    due, every = {}, {}
    for step_ms in (60.0, 36.6, 25.0, 14.9):
        clients, offered = _replayed(serve, step_ms)
        measured, failed, e2e, extra = serve.window_numbers(
            clients, 1000.0, 51.0, cutoff=2000.0)
        assert len(measured) == 53 and not failed
        due[step_ms] = e2e["serve_due_tok_per_s"]
        every[step_ms] = extra["tokens_in_window"] / 51.0
        assert due[step_ms] == extra["due_tokens_in_window"] / 51.0
    assert offered == pytest.approx(100.745, abs=1e-3)
    # PERF.md's columns, to the digit
    assert [round(every[s], 2) for s in (60.0, 36.6, 25.0, 14.9)] == [
        115.04, 113.12, 110.43, 107.25]
    assert [round(due[s], 2) for s in (60.0, 36.6, 25.0, 14.9)] == [
        96.2, 99.53, 100.1, 100.59]
    # the due count never falls as the engine gets faster, and reads the
    # offered load less the tail the window's end cuts off: within 1.5 % of
    # it from today's step on, 4.5 % under at a step of 60 ms
    assert due[60.0] <= due[36.6] <= due[25.0] <= due[14.9] <= offered
    assert all(due[s] >= 0.985 * offered for s in (36.6, 25.0, 14.9))
    assert due[60.0] >= 0.95 * offered
    # the old count: the `clip` step reads over 1 % (5.2 %) UNDER today's
    assert every[14.9] < 0.99 * every[36.6]


# ------------------------------------------------------------------- trace
def test_reduce_trace_on_a_hand_built_trace():
    ms = 1e6
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(11)", 0, 4 * ms),
                            ("jit_step(11)", 10 * ms, 6 * ms),
                            ("jit_step(11)", 20 * ms, 5 * ms),
                            ("jit_chunk(7)", 30 * ms, 2 * ms)],
            "XLA Ops": [("fusion.1", 0, 3 * ms), ("fusion.2", 2 * ms, 2 * ms),
                        ("all-reduce.3", 10 * ms, 6 * ms),
                        ("fusion.1", 20 * ms, 5 * ms),
                        ("copy.9", 30 * ms, 2 * ms)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 10 * ms)]},
        "/host:CPU": {"python": [("train/step", 3 * ms, 8 * ms),
                                 ("outer", 0, 40 * ms),
                                 ("bench/window", 0, 40 * ms),
                                 ("$ ignored", 4 * ms, 1 * ms)]},
    }
    # the profiler's own start and stop stall the host: only what lies under
    # the harness's marker span is read, cut at its ends
    marked = {k: {a: list(b) for a, b in v.items()} for k, v in planes.items()}
    marked["/host:CPU"]["python"][2] = ("bench/window", 2 * ms, 20 * ms)
    m = reduce_trace.summarize(marked)
    assert m["window_s"] == pytest.approx(0.020)
    # device 0 inside [2, 22] ms: 2..4, 10..16, 20..22
    assert m["busy_s_per_device"] == pytest.approx([0.010, 0.008])
    assert m["programs"] == {"jit_step": {
        "runs": 1, "median_ms": 6.0, "total_ms": 6.0}}
    # no host spans recorded: first to last start of the commonest program
    del marked["/host:CPU"]
    u = reduce_trace.summarize(marked)
    assert u["window_s"] == pytest.approx(0.020)           # 0 .. 20 ms
    assert u["busy_s_per_device"][0] == pytest.approx(0.010)
    assert u["programs"]["jit_step"]["runs"] == 2
    assert reduce_trace.short_name(
        "%fusion.9 = (f32[256]{0:T(256)}, f32[2,3]{1,0}) fusion(f32[4]{0} "
        "%all-reduce.3), kind=kLoop") == "fusion.9 fusion f32[256]"
    assert not reduce_trace.is_collective(
        "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %all-reduce.3), kind=kLoop")
    assert reduce_trace.is_collective(
        "%all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4]{0} %x)")
    assert reduce_trace.merged([(0, 3), (2, 4), (10, 16)]) == \
        [[0, 4], [10, 16]]
    s = reduce_trace.summarize(planes)
    assert s["window_s"] == pytest.approx(0.040)
    assert s["devices"] == 2
    # device 0: union 4 + 6 + 5 + 2 = 17 ms; device 1: 10 ms
    assert s["busy_s_per_device"] == pytest.approx([0.017, 0.010])
    assert s["busy_s"] == pytest.approx(0.0135)
    assert reduce_trace.device_idle_pct(s) == pytest.approx(
        100 * (1 - 0.0135 / 0.040))
    assert s["programs"]["jit_step"] == {
        "runs": 3, "median_ms": 5.0, "total_ms": 15.0}
    assert reduce_trace.program_median_ms(s, ["nope", "jit_chunk"]) == 2.0
    assert s["top_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    assert s["collective_ms"] == pytest.approx(6.0)
    gaps = dict(s["idle_gaps"])
    # gap 4..10 ms lies under train/step (the innermost span); the others
    # (16..20, 25..30) only under the outer one
    assert gaps["train/step"] == pytest.approx(0.006)
    assert gaps["outer"] == pytest.approx(0.009)
    assert reduce_trace.device_idle_pct({"devices": 0, "window_s": 1}) is None


# ------------------------------------------------------------------ counts
def test_counts_and_metric_readers_on_hand_worked_numbers():
    # first bottleneck of ResNet-50 at 56 x 56: 1x1 64->64, 3x3 64->64,
    # 1x1 64->256 and the 1x1 64->256 projection
    macs = dict(counts.resnet50_convs())
    px = 56 * 56
    assert macs["l0.b0.c1"] == px * 64 * 64
    assert macs["l0.b0.c2"] == px * 9 * 64 * 64
    assert macs["l0.b0.c3"] == macs["l0.b0.sc"] == px * 64 * 256
    assert macs["l1.b0.c1"] == px * 256 * 128            # before its stride
    assert macs["l1.b0.c2"] == 28 * 28 * 9 * 128 * 128
    assert macs["conv1"] == 112 * 112 * 49 * 3 * 64 and macs["fc"] == 2048000
    forward = sum(macs.values())
    assert 4.0e9 < forward < 4.2e9                       # the known ~4.1 GMACs
    assert counts.resnet50_train_step_flops(256) == \
        2 * (3 * forward - macs["conv1"]) * 256
    # one decode step of a 2-layer, width-8, vocabulary-10 model
    params = 2 * (8 * 24 + 8 * 8 + 2 * 4 * 8 * 8) + 10 * 8
    assert counts.gpt2_matmul_params(8, 2, 10) == params
    flops, data = counts.gpt2_decode_step(8, 2, 10, live_rows=3,
                                          live_tokens=50)
    assert flops == 2 * params * 3 + 4 * 8 * 2 * 50
    assert data == (params + 2 * (24 + 8 + 32 + 8)) * 2 + 2 * 2 * 8 * 2 * 50
    least, bound = counts.least_seconds(
        197e12, 819e9 * 2, {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == pytest.approx(2.0) and bound == "memory"
    # ... and the readers that use them, on a hand-made run
    peaks = harness.peaks_for("TPU v5 lite")
    with pytest.raises(harness.BenchmarkError):
        harness.peaks_for("some other chip")
    summary = {"devices": 4, "window_s": 2.0, "busy_s": 1.5,
               "collective_ms": 30.0,
               "programs": {"jit_step": {"runs": 10, "median_ms": 20.0,
                                         "total_ms": 200.0},
                            "jit__core": {"runs": 10, "median_ms": 200.0,
                                          "total_ms": 2000.0}}}
    run = {"programs": {"decode_step": ["jit_step"],
                        "train_step": ["jit_mapped", "jit__core"]},
           "sizes": {"n_embd": 1280, "n_layer": 36, "vocab_size": 50304,
                     "image": 224, "classes": 1000},
           "peaks": peaks, "batch_per_chip": 256,
           "live_in_trace": {"rows": 20.0, "tokens": 6000.0},
           "iteration_ms": [210.0, 212.0, 211.0],
           "late_ms": [1.0, 2.0], "queue_wait_ms": [5.0], "ttft_ms": [7.0],
           "prompt_tokens": 1000, "prefix_tokens": 250,
           "max_pages": 100, "pages_peak": 40,
           "loop_before": {"phases": {"sweep": 1.0, "decode_dispatch": 1.0}},
           "loop_after": {"phases": {"sweep": 2.0, "decode_dispatch": 4.0}}}
    read = lambda name: harness.load_module("metrics", name).value(run, summary)
    assert read("prefix_hit_pct") == 25.0 and read("kv_pages_peak_pct") == 40.0
    assert read("loop_host_pct") == 25.0
    assert read("decode_step_ms") == 20.0 and read("train_step_ms") == 200.0
    assert read("train_host_gap_ms") == 11.0
    assert read("device_idle_pct.serve") == read("device_idle_pct.train") == 25.0
    flops, data = counts.gpt2_decode_step(1280, 36, 50304, 20.0, 6000.0)
    assert read("decode_step_roofline") == pytest.approx(
        100 * (data / 819e9) / 0.020)
    assert 0 < read("decode_step_roofline") < 100
    assert read("train_step_mfu") == pytest.approx(
        100 * counts.resnet50_train_step_flops(256) / 197e12 / 0.200)
    assert 0 < read("train_step_mfu") < 100


# ------------------------------------------------------- serving rehearsal
def tiny_serving_cell():
    cell = harness.load_cell("gpt2l-chat-steady")
    cfg, mix = cell["config_json"], cell["traffic_json"]
    # a wider initialisation than the published 0.02: at two layers of
    # width 64 the token's own embedding would otherwise decide every logit
    cfg["sizes"].update(n_layer=2, n_embd=64, n_head=4, n_positions=128,
                        n_ctx=128, vocab_size=256, initializer_range=0.2)
    cfg["assumed"].update(vocab_real=250, weights_dtype="float32")
    cfg["engine"].update(max_slots=4, page_size=4, prefill_chunk=8,
                         prefill_rows=2)
    cfg["check"] = {"sample_requests": 12, "limits": {
        "served_token_gap_max_rel": 1e-3, "served_token_gap_mean_rel": 1e-4,
        "served_token_gap_under_own_logits_max_rel": 1e-3,
        "own_logits_error_rel_rms": 1e-4}}
    mix.update(
        arrivals={"process": "poisson", "rate_per_s": 6.0},
        prompt_tokens={"law": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 60},
        output_tokens={"law": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16},
        shared_prefix={"share": 0.5, "count": 2, "tokens": 16,
                       "min_own_tokens": 4},
        lead_in_s=0.3, drain_limit_s=20.0)
    return cell


@pytest.fixture(scope="module")
def served():
    import jax

    serve = harness.load_module("drivers", "serve")
    cell = tiny_serving_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        out = serve.run(cell, 2 ** 31 + 11, 2.0, False, time.perf_counter())
    return cell, out


def test_serving_rehearsal_is_correct_and_writes_no_device_metric(served):
    cell, out = served
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == round(6.0 * 2.0) or out["attempted"] > 8
    assert set(out["values"]) == {"itl_p95_ms", "serve_due_tok_per_s",
                                  "setup_s"}
    assert all(v > 0 for v in out["values"].values())
    assert out["device"]["platform"] == "cpu"
    line = harness.metric_entries(cell["end_to_end"], out["values"])
    assert set(line) == set(out["values"])
    # nothing traced on a CPU: every device_trace reader returns nothing
    for m in cell["per_layer"]:
        if m["source"] == "device_trace":
            assert harness.load_module("metrics", m["name"]).value(
                dict(out["record"], peaks={}), None) is None
    rec = out["record"]
    assert rec["prefix_tokens"] > 0 and rec["jit_compiles"] > 0
    assert rec["scopes"] == {}       # the configuration's file declares none
    assert harness.load_module("metrics", "loop_host_pct").value(
        rec, None) > 0


def test_serving_check_fails_int8(served):
    """The lower precision the engine offers: the model's own logits no
    longer lie as near the reference's, whether the reference's weights are
    rounded to int8 steps or the program's own int8 path (Quantizer weights,
    int8 page pool) makes them."""
    from benchmark import weights as bw
    from benchmark.models import gpt2
    from bigdl_tpu.nn.quantized import Quantizer

    serve = harness.load_module("drivers", "serve")
    cell, out = served
    cfg, got = cell["config_json"], out["compared"]
    sound = {r["name"]: r for r in out["checks"]}
    assert all(r["ok"] for r in out["checks"])
    assert sound["served_token_gap_under_own_logits_max_rel"]["value"] < 1e-5
    own = gpt2.paged_logits(Quantizer.quantize(gpt2.build(cfg, 2 ** 31 + 11)),
                            "int8", cfg, got["rows"])
    rounded = serve.reference_logits(cfg, 2 ** 31 + 11, got["rows"], bw.rounded)
    for low in (own, rounded):
        rows = {r["name"]: r for r in compare.serving_rows(
            got["reference_logits"], low, got["rows"], got["spans"], True, 0,
            cfg["check"]["limits"])}
        assert not rows["own_logits_error_rel_rms"]["ok"]
        assert rows["own_logits_error_rel_rms"]["value"] > \
            10 * sound["own_logits_error_rel_rms"]["value"]


def test_serving_run_with_a_token_altered_where_it_is_produced(
        monkeypatch, no_chip_needed):
    from bigdl_tpu.serving.streams import RequestHandle

    deliver = RequestHandle._deliver
    monkeypatch.setattr(
        RequestHandle, "_deliver",
        lambda self, token, now: deliver(self, (int(token) + 1) % 250, now))
    serve = harness.load_module("drivers", "serve")
    out = serve.run(tiny_serving_cell(), 5, 1.5, False, time.perf_counter())
    assert out["correct"] is False
    rows = {r["name"]: r for r in out["checks"]}
    assert not rows["served_token_gap_max_rel"]["ok"]
    assert not rows["served_token_gap_under_own_logits_max_rel"]["ok"]
    assert rows["own_logits_error_rel_rms"]["ok"]   # the model is sound


# ------------------------------------------------------ training rehearsal
class TinyNet:
    """conv3x3 -> BatchNorm -> ReLU -> global average -> linear, on the
    program's modules (adapter) and in plain jax.numpy (reference): what the
    training driver needs of a configuration, at a size a test can hold."""

    @staticmethod
    def weights(config, seed):
        import jax
        import jax.numpy as jnp

        k = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 2)
        return {"c.w": 0.3 * jax.random.normal(k[0], (3, 3, 3, 4)),
                "c.b": jnp.zeros((4,)), "n.g": jnp.ones((4,)),
                "n.b": jnp.zeros((4,)),
                "f.w": 0.3 * jax.random.normal(k[1], (4, 5)),
                "f.b": jnp.zeros((5,))}

    @staticmethod
    def build(config, seed):
        from bigdl_tpu import nn

        model = (nn.Sequential()
                 .add(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1,
                                            format="NHWC"))
                 .add(nn.SpatialBatchNormalization(4, 1e-3, format="NHWC"))
                 .add(nn.ReLU())
                 .add(nn.SpatialAveragePooling(8, 8, 1, 1, format="NHWC"))
                 .add(nn.View(4)).add(nn.Linear(4, 5)))
        w = TinyNet.weights(config, seed)
        tree = model.params_dict()
        tree["m0"]["~params"] = {"weight": w["c.w"].transpose(3, 2, 0, 1),
                                 "bias": w["c.b"]}
        tree["m1"]["~params"] = {"weight": w["n.g"], "bias": w["n.b"]}
        tree["m5"]["~params"] = {"weight": w["f.w"].T, "bias": w["f.b"]}
        model.load_params_dict(tree)
        return model

    @staticmethod
    def named(tree, config):
        p = {k: {a: np.asarray(b) for a, b in tree[k]["~params"].items()}
             for k in ("m0", "m1", "m5")}
        return {"c.w": p["m0"]["weight"].transpose(2, 3, 1, 0),
                "c.b": p["m0"]["bias"], "n.g": p["m1"]["weight"],
                "n.b": p["m1"]["bias"], "f.w": p["m5"]["weight"].T,
                "f.b": p["m5"]["bias"]}

    @staticmethod
    def follow(p0, batches, recipe, shards=1, cast=None):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from benchmark.reference.resnet50 import sgd_update

        def loss_fn(p, x, y):
            h = lax.conv_general_dilated(
                x, p["c.w"], (1, 1), [(1, 1), (1, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["c.b"]
            mean = jnp.mean(h, (0, 1, 2))
            var = jnp.mean(jnp.square(h - mean), (0, 1, 2))
            h = (h - mean) / jnp.sqrt(var + 1e-3) * p["n.g"] + p["n.b"]
            z = (jnp.mean(jax.nn.relu(h), (1, 2)) @ p["f.w"]
                 + p["f.b"]).astype(jnp.float32)
            lab = y.reshape(-1).astype(jnp.int32) - 1
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(z), lab[:, None], axis=1))

        p, v = p0, jax.tree.map(jnp.zeros_like, p0)
        losses, after = [], []
        for x, y in batches:
            with jax.default_matmul_precision("highest"):
                loss, g = jax.value_and_grad(loss_fn)(
                    p, jnp.asarray(x), jnp.asarray(y))
            p, v, _ = sgd_update(p, v, g, recipe["learning_rate"],
                                 recipe["momentum"], recipe["dampening"],
                                 recipe["weight_decay"])
            losses.append(float(loss))
            after.append(p)
        return losses, after


def tiny_training_cell(prebuilt=True):
    sys.modules["benchmark.models.tinynet"] = TinyNet
    sys.modules["benchmark.reference.tinynet"] = TinyNet
    cell = harness.load_cell("resnet50-local-b256")
    # the cell's mix feeds pre-built batches; the record path (one Sample a
    # record, stacked by the optimizer's producer thread) stays in the
    # generator for the input-pipeline cell of PERF.md section 7
    assert cell["traffic_json"]["prebuilt"] is True
    cell["traffic_json"]["prebuilt"] = prebuilt
    cell["config_json"].update(adapter="tinynet", reference="tinynet")
    cell["config_json"]["sizes"].update(classes=5, image=8)
    cell["config_json"]["optimizer"]["recipe"].update(
        learning_rate=0.1, dampening=0.0, l2=0.0)
    cell["config_json"]["check"]["limits"] = {
        "loss_rel_gap": 1e-4, "grad_norm_gap": 1e-2, "delta_norm_gap": 1e-2}
    cell["traffic_json"].update(samples=32, image=[8, 8, 3], classes=5,
                                batch_per_chip=4, warmup_iterations=4)
    return cell


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_prebuilt_mix_holds_the_seeded_records_in_their_own_order(seed):
    """What a ``"prebuilt": true`` mix feeds: the arrays ``synthetic_dataset``
    draws from the seed, cut into whole batches in their own order, each
    bit-equal to what the program's stacker makes of the same records."""
    from bigdl_tpu.dataset.minibatch import MiniBatch
    from bigdl_tpu.dataset.sample import Sample

    train = harness.load_module("drivers", "train")
    mix = dict(tiny_training_cell()["traffic_json"], samples=18)
    x, y = loadgen.synthetic_dataset(mix, seed)
    pairs = loadgen.whole_batches(x, y, 4)
    assert len(pairs) == 4                       # the remainder is left out
    assert np.array_equal(np.concatenate([p[0] for p in pairs]), x[:16])
    assert np.array_equal(np.concatenate([p[1] for p in pairs]), y[:16])
    assert all(np.shares_memory(p[0], x) for p in pairs)   # nothing copied
    items, keep, first_batches = train.feed(mix, x, y, 4)
    assert keep == train.CHECK_STEPS and len(items) == 4
    for k, item in enumerate(items):
        stacked = MiniBatch.from_samples(
            [Sample(x[i], y[i:i + 1]) for i in range(4 * k, 4 * k + 4)])
        assert item.size() == 4
        assert np.array_equal(item.get_input(), stacked.get_input())
        assert np.array_equal(item.get_target(), stacked.get_target())
        assert item.get_target().shape == stacked.get_target().shape
    got = first_batches([2, 0, 3])
    assert [np.array_equal(got[j][0], pairs[k][0])
            for j, k in enumerate((2, 0, 3))] == [True] * 3
    # the record path: one Sample a record, the first steps' rows by index
    items, keep, first_batches = train.feed(dict(mix, prebuilt=False), x, y, 4)
    assert keep == 3 * 4 and len(items) == 18
    idx = [5, 1, 17, 2, 0, 3, 4, 6, 9, 8, 7, 10]
    got = first_batches(idx)
    assert np.array_equal(got[1][0], x[[0, 3, 4, 6]])
    assert np.array_equal(got[2][1], y[[9, 8, 7, 10]])


def test_the_loop_is_fed_three_different_batches_of_the_cells_four():
    """The check's steps want rows that all differ. The program's dataset
    walks the four pre-built batches in an order of its own (a drawn offset,
    a reshuffle after every pass over its four items): pinned here, so that
    a change of that order which repeats a batch inside the first steps
    fails on the CPU and not as a weaker check on the chip."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.optim import LocalOptimizer, Trigger

    train = harness.load_module("drivers", "train")
    mix = dict(tiny_training_cell()["traffic_json"], samples=16)
    x, y = loadgen.synthetic_dataset(mix, 3)
    items, keep, _ = train.feed(mix, x, y, 4)
    fed = train.FeedLog(items, keep)
    opt = LocalOptimizer(model=nn.Sequential(nn.Linear(2, 2)),
                         dataset=DataSet.array(items).transform(fed),
                         criterion=nn.MSECriterion(), batch_size=4,
                         end_when=Trigger.max_iteration(1))
    stream = opt._batch_stream()
    seen = [next(stream) for _ in range(8)]
    assert len(fed.rows) == train.CHECK_STEPS == 3
    assert len(set(fed.rows)) == 3, fed.rows
    assert [s is items[i] for s, i in zip(seen, fed.rows)] == [True] * 3
    assert all(s.size() == 4 for s in seen)


@pytest.mark.parametrize("prebuilt", [True, False])
def test_training_rehearsal_is_correct_and_bfloat16_fails(no_chip_needed,
                                                          prebuilt):
    import jax.numpy as jnp

    train = harness.load_module("drivers", "train")
    cell = tiny_training_cell(prebuilt)
    if prebuilt:       # what the file declares rides on the run's record
        cell["config_json"]["scopes"] = {"aug/mix": "augment"}
    out = train.run(cell, 2 ** 31 + 3, 1.0, False, time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["record"]["scopes"] == ({"aug/mix": "augment"} if prebuilt
                                       else {})
    # the reference followed the batches the loop was fed: whole batches of
    # the seeded arrays in their own order, or rows of a shuffled draw
    x, y = loadgen.synthetic_dataset(cell["traffic_json"], 2 ** 31 + 3)
    whole = loadgen.whole_batches(x, y, 4)
    fed = out["compared"]["batches"]
    assert len(fed) == 3 and all(b[0].shape == (4, 8, 8, 3) for b in fed)
    assert all(any(np.array_equal(b[0], w[0]) and np.array_equal(b[1], w[1])
                   for w in whole) for b in fed) is prebuilt
    assert not any(np.array_equal(a[0], b[0])
                   for i, a in enumerate(fed) for b in fed[i + 1:])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["values"]) == {"train_samples_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["record"]["iteration_ms"]
    # parameters, inputs and activations in bfloat16: the update is lost
    control = train.control_rows(cell["config_json"], 2 ** 31 + 3, 1,
                                 out["compared"], jnp.bfloat16)
    assert any(not r["ok"] for r in control)


def test_training_run_with_a_step_that_leaves_its_state_unchanged(
        monkeypatch, no_chip_needed):
    from bigdl_tpu.optim import SGD

    train = harness.load_module("drivers", "train")
    build = train.build_optimizer

    def broken(*a, **k):
        opt = build(*a, **k)
        opt.set_optim_method(SGD(learning_rate=0.0))
        return opt

    monkeypatch.setattr(train, "build_optimizer", broken)
    out = train.run(tiny_training_cell(), 4, 0.5, False, time.perf_counter())
    assert out["correct"] is False
    assert not {r["name"]: r for r in out["checks"]}[
        "param_change_norm_worst_leaf_gap"]["ok"]


# ------------------------------------------------- references and no chip
def test_gpt2_reference_agrees_with_the_program_at_a_tiny_size():
    import jax.numpy as jnp

    from benchmark.models import gpt2
    from benchmark.reference import gpt2 as ref

    cfg = tiny_serving_cell()["config_json"]
    model = gpt2.build(cfg, 3)
    ids = np.random.RandomState(0).randint(0, 250, (2, 40))
    want = np.asarray(ref.forward(gpt2.weights(cfg, 3), ids, cfg))
    got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    gaps = compare.served_token_gaps(
        want[0], np.concatenate([ids[0, :30], want[0, 29:39].argmax(-1)]), 30)
    assert gaps[0] == 0.0 and (gaps >= 0).all()


def test_a_run_without_a_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2l-chat-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout and "TPU" in done.stderr
