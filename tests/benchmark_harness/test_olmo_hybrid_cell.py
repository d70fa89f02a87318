"""The hybrid decoder's cell (``olmoh-docqa-steady``): its own files go
through the unedited serving driver on the CPU at a test's sizes and come out
``correct``; the configuration is the source's but for the two stated cuts;
the counts and the pool budget match hand-worked numbers; the new readers
read hand-built spans and read nothing from nothing; the precision controls
read far above the sound run."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import compare, harness, olmo_hybrid_counts as counts  # noqa: E402
from hybrid_tiny import tiny_config  # noqa: E402

CELL = "olmoh-docqa-steady"
SEED = 2 ** 31 + 34
V5E_LIMIT = 16_909_336_064        # the chip's bytes_limit (PERF.md, PR 23)


def config_file():
    return harness.load_json(harness.HERE, "configs", "olmo-hybrid-7b.json")


def tiny_cell():
    """The cell's own files, its sizes and traffic brought to a test's."""
    cell = harness.load_cell(CELL)
    cfg, mix = cell["config_json"], cell["traffic_json"]
    tiny = tiny_config(positions=96)
    cfg["sizes"] = tiny["sizes"]
    cfg["assumed"].update(tiny["assumed"])
    cfg["engine"].update(tiny["engine"], max_slots=4)
    cfg["check"] = {"sample_requests": 10, "limits": {
        "served_token_gap_max_rel": 1e-3, "served_token_gap_mean_rel": 1e-4,
        "served_token_gap_under_own_logits_max_rel": 1e-3,
        "own_logits_error_rel_rms": 1e-4}}
    mix.update(
        arrivals={"process": "poisson", "rate_per_s": 5.0},
        prompt_tokens={"law": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 60},
        output_tokens={"law": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 16},
        shared_prefix={"share": 0.75, "count": 2, "tokens": 40,
                       "min_own_tokens": 4},
        lead_in_s=0.5, drain_limit_s=30.0)
    return cell


@pytest.fixture(scope="module")
def served():
    import jax

    serve = harness.load_module("drivers", "serve")
    cell = tiny_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        out = serve.run(cell, SEED, 3.0, False, time.perf_counter())
    return cell, out


def test_the_cells_files_are_served_and_correct_through_the_driver(served):
    cell, out = served
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8
    assert set(out["values"]) == {"itl_p95_ms", "serve_due_tok_per_s",
                                  "setup_s"}
    assert out["compared"]["rows"].shape == (10, 96)
    # three requests in four open with a shared document and resume from
    # its snapshot at the stride (32 of its 40 tokens)
    assert out["record"]["prefix_tokens"] >= 32 * 4
    assert out["record"]["jit_compiles"] == 6
    # the metrics the cell reports are the manifest's, and their readers run
    names = {m["name"] for m in cell["per_layer"]}
    assert {"olmoh_decode_step_roofline", "olmoh_prefill_chunk_roofline",
            "state_restore_ms", "state_snapshot_ms",
            "prefix_resume_shortfall_pct", "prefix_hit_pct",
            "decode_step_ms"} <= names
    assert "decode_step_roofline" not in names      # GPT-2's arithmetic
    hit = harness.load_module("metrics", "prefix_hit_pct").value(
        out["record"], None)
    assert 20.0 < hit < 75.0


def test_the_controls_read_far_above_the_sound_run_and_come_out_not_correct(served):
    """Rows in, numbers out (``benchmark/tools/olmo_controls.py``): the
    reference with a bfloat16 recurrent state, and with int8-rounded weights,
    in the program's place; the reference whose state stands still at one
    served token in 32, in the decode step's place. Each goes through the
    cell's limits and fails; the sound run's own numbers pass them."""
    controls = harness.load_module("tools", "olmo_controls")
    cell, out = served
    limits = cell["config_json"]["check"]["limits"]
    sound = {r["name"]: r["value"] for r in out["checks"]}
    found = controls.controls(cell["config_json"], SEED, out["compared"])
    assert set(found) == {"reference_state_bf16", "reference_int8",
                          "reference_stale_state"}
    for name in ("reference_state_bf16", "reference_int8"):
        numbers = found[name]
        assert numbers["own_logits_error_rel_rms"] > \
            10 * sound["own_logits_error_rel_rms"], name
        ok, fails = controls.verdict(numbers, limits)
        assert not ok and "own_logits_error_rel_rms" in fails, name
    # the fault is in the decode step alone: the prefill pass's logits are
    # the sound run's, and the served tokens part from both sets of logits
    stale = found["reference_stale_state"]
    assert stale["own_logits_error_rel_rms"] == \
        sound["own_logits_error_rel_rms"]
    ok, fails = controls.verdict(stale, limits)
    assert not ok and set(fails) >= {
        "served_token_gap_max_rel",
        "served_token_gap_under_own_logits_max_rel"}
    assert controls.verdict(
        {n: sound[n] for n in limits}, limits) == (True, [])
    rows = compare.serving_rows(
        out["compared"]["reference_logits"],
        out["compared"]["reference_logits"], out["compared"]["rows"],
        out["compared"]["spans"], True, 0, limits)
    assert all(r["ok"] for r in rows)


def test_the_references_stale_mark_leaves_the_state_as_it_was():
    """``stale`` at a token: the layers' recurrent state after it is the
    state before it, so logits before the mark are untouched, those from the
    mark on move, and no mark is the plain forward."""
    import jax.numpy as jnp
    from benchmark.models import olmo_hybrid as adapter
    from benchmark.reference import olmo_hybrid as ref

    cfg = tiny_config(positions=32)
    w = adapter.weights(cfg, 5)
    ids = np.random.RandomState(0).randint(0, 120, (1, 24)).astype(np.int32)
    plain = ref.forward(w, ids, cfg)
    mark = np.zeros(ids.shape, bool)
    np.testing.assert_array_equal(ref.forward(w, ids, cfg, stale=mark), plain)
    mark[0, 10] = True
    marked = ref.forward(w, ids, cfg, stale=mark)
    np.testing.assert_array_equal(marked[0, :10], plain[0, :10])
    assert np.abs(marked[0, 10] - plain[0, 10]).max() > 1e-3
    assert np.abs(marked[0, 23] - plain[0, 23]).max() > 0


def test_the_configuration_is_the_sources_but_for_the_two_stated_cuts():
    cfg = config_file()
    man = harness.manifest()
    entry = {c["name"]: c for c in man["configs"]}["olmo-hybrid-7b"]
    assert entry["source"] == cfg["source"] and entry["source"].startswith(
        "https://huggingface.co/allenai/Olmo-Hybrid-7B")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    # every published key sits at the top of the file under its own name,
    # and ``sizes`` (what the adapter and the reference read) says the same
    z = cfg["sizes"]
    assert all(cfg[k] == v for k, v in z.items())
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    differs = sorted(k for k, v in published.items() if z[k] != v)
    assert differs == sorted(cfg["reduced"])
    assert {k: published[k] for k in differs} == cfg["published"]
    assert z["num_hidden_layers"] == 16 and z["max_position_embeddings"] == 4096
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert z["layer_types"] == period * 8            # as published, whole
    assert counts.layer_kinds(z) == period * 4       # what is held here
    for key in ("deployment", "block", "qk_norm", "rope_theta", "linear_layer",
                "weights_dtype", "kv_dtype", "state_dtype", "weights",
                "decoding"):
        assert key in cfg["assumed"], key
    assert cfg["driver"] == "serve"
    assert cfg["adapter"] == cfg["reference"] == "olmo_hybrid"
    assert cfg["programs"] == {"decode_step": ["jit_step"],
                               "prefill_chunk": ["jit_chunk"]}


def test_the_counts_are_the_hand_worked_numbers():
    z = config_file()["sizes"]
    d, f, h, dk, dv = 3840, 11008, 30, 96, 192
    linear = d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d + 3 * d * f
    full = 4 * d * d + 3 * d * f
    assert counts.linear_mixer_params(z) + counts.mlp_params(z) == linear \
        == 215_516_160
    assert counts.full_mixer_params(z) + counts.mlp_params(z) == full \
        == 185_794_560
    assert counts.block_matmul_params(z) == 12 * linear + 4 * full
    assert counts.head_params(z) == 100352 * 3840 == 385_351_680
    # 16 layers + embedding + head: the 4.10 G parameters of the cut
    total = counts.block_matmul_params(z) + 2 * counts.head_params(z)
    assert abs(total - 4.10e9) < 0.01e9
    assert counts.kv_bytes_per_token(z) == 4 * 2 * 3840 * 2 == 61_440
    assert counts.lane_state_bytes(z) == 12 * (h * dk * dv * 4
                                               + 3 * 11520 * 2) == 27_371_520
    flops, data = counts.decode_step(z, 10, 20000)
    weights = 12 * linear + 4 * full + 385_351_680
    assert data == 2 * weights + 61_440 * 20000 + 2 * 27_371_520 * 10
    assert flops == 2 * weights * 10 + 4 * 3840 * 4 * 20000 \
        + 12 * 7 * h * dk * dv * 10
    flops, data = counts.prefill_chunk(z, 512, 2)
    assert data == 2 * weights
    assert flops == 2 * (12 * linear + 4 * full) * 512 + 2 * 385_351_680 * 2 \
        + 12 * 7 * h * dk * dv * 512 + 4 * 3840 * 4 * 512 * 256 / 2
    assert counts.gdn_step(z, 16) == (7 * 552_960 * 16, 8 * 552_960 * 16)
    assert counts.gdn_chunk(z, 512, 2) == (
        7 * 552_960 * 512, 8 * 552_960 * 2 + 4 * 11520 * 512)


def test_the_pool_budget_on_this_configurations_geometry():
    from benchmark.models import olmo_hybrid as adapter

    serve = harness.load_module("drivers", "serve")
    cfg = config_file()
    geometry = adapter.cache_geometry(cfg)
    lane = 27_371_520
    assert adapter.lane_state_bytes(cfg) == lane
    assert geometry == {
        "max_positions": 4096, "page_device_bytes": 16 * 61_440,
        # the lane and a sixteenth of the scratch lane at 4/3 (the padded
        # layout), two snapshots flat
        "fixed_device_bytes_per_lane": int(lane * (4 / 3 * (1 + 1 / 16) + 2))}
    weights = 2 * (counts.block_matmul_params(cfg["sizes"])
                   + 2 * counts.head_params(cfg["sizes"]))
    budget = (int(V5E_LIMIT * 0.9) - weights - cfg["engine"]["reserve_bytes"]
              - 16 * geometry["fixed_device_bytes_per_lane"])
    floor = 1 + 16 * 256
    # what is left after the step's scratch is under every lane at full
    # context: the floor holds (4.03 GB of pages), as ISSUE 34 reckoned
    assert budget // (16 * 61_440) < floor
    assert serve.pool_pages(cfg, geometry, V5E_LIMIT, weights) == floor == 4097
    assert serve.pool_pages(cfg, geometry, None, 0) == floor
    # a chip twice the size would give the budget's pages
    assert serve.pool_pages(cfg, geometry, 2 * V5E_LIMIT, weights) == (
        budget + int(V5E_LIMIT * 0.9 * 2) - int(V5E_LIMIT * 0.9)) // (16 * 61_440)


def test_the_traffic_file_holds_the_mix_the_issue_names():
    mix = harness.load_json(harness.HERE, "traffic", "docqa-steady.json")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["arrivals"]["rate_per_s"] > 0
    assert mix["prompt_tokens"] == {"law": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 64, "max": 3072}
    assert mix["output_tokens"] == {"law": "lognormal", "median": 128,
                                    "sigma": 0.6, "min": 16, "max": 512}
    assert mix["shared_prefix"] == {"share": 0.75, "count": 4, "tokens": 2048,
                                    "min_own_tokens": 32}
    assert (mix["lead_in_s"], mix["drain_limit_s"], mix["trace_seconds"],
            mix["trace_host_level"]) == (15.0, 90.0, 6.0, 1)
    # every key is one the generator already reads
    steady = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    assert set(mix) == set(steady)
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "docqa-steady"
    # no request outgrows the served context
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 4096


def fake_span(name, start, end, **attrs):
    return {"name": name, "start_ns": start, "end_ns": end, "span_id": start,
            "parent_id": None, "thread": "loop", "attrs": attrs}


@pytest.mark.parametrize("name", [
    "olmoh_decode_step_roofline", "olmoh_prefill_chunk_roofline",
    "state_restore_ms", "state_snapshot_ms", "prefix_resume_shortfall_pct"])
def test_new_metric_is_in_the_manifest_and_reads_nothing_from_nothing(name):
    entry = {m["name"]: m for m in harness.manifest()["per_layer"]}[name]
    assert CELL in entry["workloads"]
    assert entry["layer"] == ("kernels" if "roofline" in name
                              else "cache manager")
    reader = harness.load_module("metrics", name)
    run = {"programs": {"decode_step": ["jit_step"],
                        "prefill_chunk": ["jit_chunk"]},
           "sizes": config_file()["sizes"],
           "peaks": harness.peaks_for("TPU v5 lite"),
           # a program without spans (the parent): nothing to read
           "_program_spans": {"serving": None}}
    assert reader.value(run, {"programs": {}}) is None


def test_the_new_readers_read_hand_built_spans_and_programs():
    run = {"programs": {"decode_step": ["jit_step"],
                        "prefill_chunk": ["jit_chunk"]},
           "sizes": config_file()["sizes"],
           "peaks": harness.peaks_for("TPU v5 lite"),
           "live_in_trace": {"rows": 10.0, "tokens": 20000.0}}
    ms = 1e6
    inside = [
        fake_span("serving/state_restore", 0, 2 * ms, matched_tokens=2048,
                  resumed_tokens=2048, bytes=27_371_520),
        fake_span("serving/state_restore", 10, 10 + 4 * ms,
                  matched_tokens=2048, resumed_tokens=1536, bytes=27_371_520),
        fake_span("serving/state_restore", 20, 20 + 9 * ms,
                  matched_tokens=512, resumed_tokens=0, bytes=0),
        fake_span("serving/state_snapshot", 30, 30 + ms, position=512,
                  bytes=27_371_520),
        fake_span("serving/state_snapshot", 40, 40 + 3 * ms, position=1024,
                  bytes=27_371_520),
        fake_span("serving/prefill_dispatch", 50, 60, rows=2, tokens=512),
        fake_span("serving/prefill_dispatch", 70, 80, rows=2, tokens=512),
        fake_span("serving/prefill_dispatch", 90, 95, rows=1, tokens=100)]
    run["_program_spans"] = {"serving": {"inside": inside, "self_ns": {}}}
    trace = {"programs": {"jit_step": {"median_ms": 30.0},
                          "jit_chunk": {"median_ms": 40.0}}}
    read = lambda n: harness.load_module("metrics", n).value(run, trace)
    assert read("state_restore_ms") == 3.0       # the two that copied
    assert read("state_snapshot_ms") == 2.0
    assert read("prefix_resume_shortfall_pct") == pytest.approx(
        100.0 * (512 + 512) / 4608)
    flops, data = counts.decode_step(run["sizes"], 10.0, 20000.0)
    assert read("olmoh_decode_step_roofline") == pytest.approx(
        100.0 * (data / 819e9) / 0.030)          # memory binds a decode step
    flops, data = counts.prefill_chunk(run["sizes"], 512, 2)
    assert read("olmoh_prefill_chunk_roofline") == pytest.approx(
        100.0 * (flops / 197e12) / 0.040)        # compute binds a full chunk
    assert 0 < read("olmoh_decode_step_roofline") < 100
    assert 0 < read("olmoh_prefill_chunk_roofline") < 100
