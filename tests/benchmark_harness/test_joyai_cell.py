"""The JoyAI-LLM-Flash cell (``joyai-reask-steady``): its own files go through
the unedited serving driver on the CPU at a test's sizes and come out
``correct``; the configuration is the source's but for the five stated cuts;
the counts and the pool budget match hand-worked numbers; the traffic file
holds the mix the issue names; the new readers read a recorded run's spans
and read nothing from nothing; the controls come out not correct."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import compare, harness  # noqa: E402
from benchmark import joyai_counts as counts  # noqa: E402
from joyai_tiny import tiny_config  # noqa: E402

CELL = "joyai-reask-steady"
SEED = 2 ** 31 + 41
V5E_LIMIT = 16_909_336_064        # the chip's bytes_limit (PERF.md, PR 23)
SOURCE = "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/" \
    "config.json"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers"]
BY_SCOPE = {"decode_experts_ms": ("decode_step", "experts"),
            "chunk_experts_ms": ("prefill_chunk", "experts"),
            "decode_route_ms": ("decode_step", "route")}
BY_SPAN = ["experts_touched_pct", "expert_load_max_over_mean"]
ROOFLINES = ["joyai_decode_step_roofline", "joyai_prefill_chunk_roofline"]
NEW = list(BY_SCOPE) + BY_SPAN + ROOFLINES


def config_file():
    return harness.load_json(harness.HERE, "configs", "joyai-llm-flash.json")


def tiny_cell():
    """The cell's own files, its sizes and traffic brought to a test's: a
    dense layer and two routed ones (4 of 16 experts held, 4 a token),
    contexts of up to 96 tokens, shared documents of 48 tokens."""
    cell = harness.load_cell(CELL)
    cfg, mix = cell["config_json"], cell["traffic_json"]
    tiny = tiny_config(positions=96)
    cfg["sizes"], cfg["published"] = tiny["sizes"], tiny["published"]
    cfg["assumed"].update(tiny["assumed"])
    cfg["engine"].update(tiny["engine"], max_slots=4, prefill_chunk=8)
    cfg["check"] = {"sample_requests": 6, "limits": {
        "served_token_gap_max_rel": 1e-3, "served_token_gap_mean_rel": 1e-4,
        "served_token_gap_under_own_logits_max_rel": 1e-3,
        "own_logits_error_rel_rms": 1e-4}}
    mix.update(
        arrivals={"process": "poisson", "rate_per_s": 4.0},
        prompt_tokens={"law": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 70},
        output_tokens={"law": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 4, "max": 24},
        shared_prefix={"share": 0.8, "count": 2, "tokens": 48,
                       "min_own_tokens": 4},
        lead_in_s=1.0, drain_limit_s=60.0, law_seed=20261231)
    return cell


@pytest.fixture(scope="module")
def served():
    import jax
    from bigdl_tpu.observability import trace

    serve = harness.load_module("drivers", "serve")
    cell = tiny_cell()
    began = time.time_ns()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        out = serve.run(cell, SEED, 3.0, False, time.perf_counter())
    # the run's own spans (the process's tracer may hold an earlier test
    # file's), as a traced run's record would hold them
    spans = [r for r in trace.export()
             if r["start_ns"] >= began
             and r["name"] in ("serving/decode_dispatch",
                               "serving/prefill_dispatch")]
    return cell, out, spans


def test_the_cells_files_are_served_and_correct_through_the_driver(served):
    cell, out, _ = served
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8
    assert set(out["values"]) == {"itl_p95_ms", "serve_due_tok_per_s",
                                  "setup_s"}
    assert out["compared"]["rows"].shape == (6, 96)
    # four requests in five open with a shared document of 48 tokens
    assert out["record"]["prefix_tokens"] >= 48 * 4
    # pages alone: step, chunk, the first token's sampler, the page copy
    assert out["record"]["jit_compiles"] == 4
    assert out["record"]["scopes"] == config_file()["scopes"]
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"prefix_hit_pct", "decode_step_ms", "prefill_chunk_ms",
                       "kv_pages_peak_pct", "device_idle_pct.serve",
                       "loop_deliver_ms", "loop_observe_ms",
                       "decode_attend_ms", "chunk_kv_pages_ms"} <= names
    # other models' arithmetic and the lane state's readers stay out
    assert not names & {"decode_step_roofline", "olmoh_decode_step_roofline",
                        "sala_decode_step_roofline", "state_restore_ms",
                        "state_snapshot_ms", "decode_recurrent_ms",
                        "decode_select_ms", "sparse_kv_read_pct"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "itl_p95_ms", "serve_due_tok_per_s", "setup_s"}


def test_the_controls_come_out_not_correct(served):
    """Rows in, numbers out (``benchmark/tools/joyai_controls.py``): each
    planted departure from the layer equations and the int8-rounded weights,
    in the program's place, go through the cell's limits and fail; the sound
    run's own numbers pass them. (Here the program is float32, so every
    departure shows; which of them the bfloat16 program's own error hides on
    the chip is in PERF.md section 4.)"""
    controls = harness.load_module("tools", "joyai_controls")
    cell, out, _ = served
    limits = cell["config_json"]["check"]["limits"]
    sound = {r["name"]: r["value"] for r in out["checks"]}
    found = controls.controls(cell["config_json"], SEED, out["compared"])
    assert set(found) == {"bias_ignored", "gates_from_biased",
                          "sum_over_held", "no_scaling", "no_rope_score",
                          "latent_int8", "reference_int8"}
    assert set(controls.controls(cell["config_json"], SEED, out["compared"],
                                 only=["no_scaling"])) == {"no_scaling"}
    for name, numbers in found.items():
        assert numbers["own_logits_error_rel_rms"] > \
            10 * sound["own_logits_error_rel_rms"], name
        ok, fails = controls.verdict(numbers, limits)
        assert not ok and "own_logits_error_rel_rms" in fails, name
    assert controls.verdict(
        {n: sound[n] for n in limits}, limits) == (True, [])
    rows = compare.serving_rows(
        out["compared"]["reference_logits"],
        out["compared"]["reference_logits"], out["compared"]["rows"],
        out["compared"]["spans"], True, 0, limits)
    assert all(r["ok"] for r in rows)


def test_the_adapter_carries_the_donation_choice_and_refuses_a_parent(
        monkeypatch):
    from benchmark.models import joyai_llm_flash as adapter
    from bigdl_tpu.models import hybrid
    from bigdl_tpu.serving import ContinuousBatchingEngine

    config = tiny_config()
    config["engine"]["donate_at_prefill_end"] = True
    engine = ContinuousBatchingEngine(
        adapter.build(config, 1), max_slots=2, page_size=4, max_pages=80,
        prefill_chunk=int(config["engine"]["prefill_chunk"]))
    assert engine._donate_at_prefill_end is True
    # a program from before this configuration fails at once, cleanly
    monkeypatch.delattr(hybrid, "LATENT")
    with pytest.raises(harness.BenchmarkError, match="cannot run joyai"):
        adapter.build(config, 1)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(harness.HERE, "reference",
                            "joyai_llm_flash.py")).read()
    assert "bigdl_tpu" not in src.split('"""', 2)[2]
    assert "import benchmark" not in src and "from benchmark" not in src


def test_the_configuration_is_the_sources_but_for_the_five_stated_cuts():
    cfg = config_file()
    man = harness.manifest()
    entry = {c["name"]: c for c in man["configs"]}["joyai-llm-flash"]
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    z = cfg["sizes"]
    assert all(cfg[k] == v for k, v in z.items())
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        published = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
        assert published["source_url"] == cfg["source"]
        published = published["config"]
    else:       # the catalog's row, for a checkout without the guides
        published = {
            "hidden_size": 2048, "intermediate_size": 7168,
            "moe_intermediate_size": 768, "num_hidden_layers": 40,
            "num_attention_heads": 32, "q_lora_rank": 1536,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "head_dim": 64,
            "n_routed_experts": 256, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "routed_scaling_factor": 2.5, "rope_theta": 32000000,
            "vocab_size": 129280, "max_position_embeddings": 131072,
            "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6,
            "model_type": "joyai_llm_flash"}
    differs = sorted(k for k, v in published.items() if z[k] != v)
    assert differs == sorted(REDUCED)
    assert {k: published[k] for k in REDUCED} == cfg["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280, "max_position_embeddings": 131072,
        "num_nextn_predict_layers": 1}
    # the cut as the issue writes it
    assert (z["num_hidden_layers"], z["layers_held"]) == (12, [0, 12])
    assert (z["n_routed_experts"], z["experts_held"],
            z["router_experts"]) == (32, [0, 32], 256)
    assert (z["vocab_size"], z["max_position_embeddings"],
            z["num_nextn_predict_layers"]) == (16256, 16384, 0)
    assert cfg["assumed"]["vocab_real"] == 16160 == -(-129280 // 8)
    assert z["vocab_size"] % 128 == 0 and z["vocab_size"] - 16160 < 128
    # the floors: four layers after the dense one, 8 experts, an eighth
    assert z["num_hidden_layers"] - z["first_k_dense_replace"] >= 4
    assert z["n_routed_experts"] >= 8 and z["vocab_size"] * 8 >= 129280
    for key in ("deployment", "expert_load", "n_routed_experts",
                "num_hidden_layers", "vocab_size", "max_position_embeddings",
                "num_nextn_predict_layers", "mla", "rope_convention",
                "absorbed_decode", "router", "block", "weights",
                "latent_norm_gains", "router_scale", "selection_bias",
                "weights_dtype", "router_dtype", "kv_dtype", "activations",
                "decoding", "donate_at_prefill_end"):
        assert key in cfg["assumed"], key
    assert "8 v5e chips share each layer" in cfg["assumed"]["deployment"]
    assert cfg["driver"] == "serve"
    assert cfg["adapter"] == cfg["reference"] == "joyai_llm_flash"
    assert cfg["programs"] == {"decode_step": ["jit_step"],
                               "prefill_chunk": ["jit_chunk"]}
    assert cfg["engine"] == {
        "max_slots": 32, "page_size": 16, "prefill_chunk": 256,
        "prefill_rows": 2, "queue_capacity": 512,
        "reserve_bytes": 2 ** 31, "donate_at_prefill_end": True}
    assert cfg["scopes"] == {
        "moe/route": "route", "moe/experts": "experts",
        "moe/shared": "experts", "mla/expand": "attend",
        "mla/absorb": "attend"}
    assert set(cfg["check"]["limits"]) == {
        "served_token_gap_max_rel", "served_token_gap_mean_rel",
        "served_token_gap_under_own_logits_max_rel",
        "own_logits_error_rel_rms"}
    assert len(entry["why"]) <= 200


def test_the_counts_are_the_hand_worked_numbers():
    """The issue's arithmetic, from the adapter's own shapes."""
    import jax
    from benchmark.models import joyai_llm_flash as adapter

    cfg = config_file()
    z = cfg["sizes"]
    attention = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                 + 4096 * 2048)
    assert counts.attention_params(z) == attention == 26_345_472
    assert counts.expert_params(z) == 3 * 2048 * 768 == 4_718_592
    assert counts.router_params(z) == 2048 * 256
    assert counts.dense_mlp_params(z) == 3 * 2048 * 7168 == 44_040_192
    assert counts.head_params(z) == 16256 * 2048
    assert (counts.layers(z), counts.dense_layers(z),
            counts.routed_layers(z)) == (12, 1, 11)
    routed_layer = attention + 2048 * 256 + 33 * 4_718_592
    total = (attention + 44_040_192) + 11 * routed_layer + 2 * 16256 * 2048
    assert counts.weight_params(z) == total == 2_145_386_496   # 2.145 G
    # the adapter's tree holds exactly that, the gains and biases apart
    model = adapter.model_shapes(cfg)
    leaves = jax.tree.leaves(model.params_dict())
    held = sum(int(a.size) for a in leaves)
    vectors = sum(int(a.size) for a in leaves if a.ndim == 1)
    assert held - vectors == total
    # bfloat16 but the float32 routers: the issue's 4.29 GB
    assert 2 * total + 2 * 11 * 2048 * 256 == 4_302_307_328
    # the cache: 576 elements a token and layer, 640 on the device
    assert counts.row_elems(z) == 576 and counts.row_device_elems(z) == 640
    assert counts.kv_bytes_per_token(z) == 12 * 576 * 2 == 13_824
    assert model.kv_token_elems() == 12 * 640
    # a step of 30 rows holding 375000 tokens, 330 assignments on 220 of
    # the 352 expert slots
    every = 12 * attention + 44_040_192 + 11 * (2048 * 256 + 4_718_592)
    assert counts.every_token_params(z) == every
    flops, data = counts.decode_step(z, 30, 330, 220, 375_000)
    assert data == 2 * (every + 16256 * 2048) + 2 * 4_718_592 * 220 \
        + 13_824 * 375_000
    assert flops == 2 * (every + 16256 * 2048) * 30 + 2 * 4_718_592 * 330 \
        + 12 * (2 * 32 * (576 + 512) * 375_000 + 2 * 32 * 512 * 256 * 30)
    flops, data = counts.prefill_chunk(z, 512, 2)
    assert data == 2 * (every + 11 * 32 * 4_718_592 + 16256 * 2048)
    assert flops == 2 * every * 512 + 2 * 11 * 4_718_592 * 8 * 512 / 8 \
        + 2 * 16256 * 2048 * 2 + 2 * 32 * 320 * 12 * 512 * 256 / 2


def test_the_pool_budget_on_this_configurations_geometry():
    from benchmark.models import joyai_llm_flash as adapter

    serve = harness.load_module("drivers", "serve")
    cfg = config_file()
    geometry = adapter.cache_geometry(cfg)
    assert geometry == {"max_positions": 16384,
                        "page_device_bytes": 16 * 12 * 640 * 2,
                        "fixed_device_bytes_per_lane": 0}
    weights = 4_302_470_144          # the matrices and the gains beside them
    budget = int(V5E_LIMIT * 0.9) - weights - 2 ** 31
    floor = 1 + 32 * 1024
    pages = serve.pool_pages(cfg, geometry, V5E_LIMIT, weights)
    # every lane at full context fits (8.05 GB) with room for the eight hot
    # documents' own copies: the budget, not the floor, binds
    assert pages == budget // 245_760 > floor + 8 * 768 // 4
    assert serve.pool_pages(cfg, geometry, None, 0) == floor == 32_769


def test_the_traffic_file_holds_the_mix_the_issue_names():
    mix = harness.load_json(harness.HERE, "traffic", "reask-steady.json")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["arrivals"]["rate_per_s"] > 0
    assert mix["prompt_tokens"] == {"law": "lognormal", "median": 2048,
                                    "sigma": 0.8, "min": 256, "max": 15360}
    assert mix["output_tokens"] == {"law": "lognormal", "median": 128,
                                    "sigma": 0.6, "min": 16, "max": 512}
    assert mix["shared_prefix"] == {"share": 0.8, "count": 8,
                                    "tokens": 12288, "min_own_tokens": 64}
    assert (mix["lead_in_s"], mix["drain_limit_s"], mix["trace_seconds"],
            mix["trace_host_level"]) == (30.0, 90.0, 6.0, 1)
    steady = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    assert set(mix) == set(steady)      # every key the generator reads
    others = {harness.load_json(harness.HERE, "traffic", f)["law_seed"]
              for f in os.listdir(os.path.join(harness.HERE, "traffic"))
              if f.endswith("steady.json") and f != "reask-steady.json"}
    assert mix["law_seed"] not in others
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reask-steady"
    assert cell["config"] == "joyai-llm-flash" and len(cell["why"]) <= 200
    # the cell's why gives the rate the file holds
    assert f"{mix['arrivals']['rate_per_s']:g} req/s" in cell["why"]
    # no request outgrows the served context; a document is whole chunks
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 16384
    assert 12288 + 64 + 512 <= 16384 and 12288 % 256 == 0
    # every document is first used in the lead-in of the drawn schedule
    from benchmark import loadgen
    reqs = loadgen.open_loop_requests(mix, 1, 51.0, 16160)
    assert {r["shared"] for r in reqs if r["due_s"] < 0} >= set(range(8))
    assert max(int(r["prompt"].max()) for r in reqs[:20]) < 16160


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_in_the_manifest_and_reads_nothing_from_nothing(name):
    entry = {m["name"]: m for m in harness.manifest()["per_layer"]}[name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "itl_p95_ms"
    assert entry["layer"] == "kernels"
    assert entry["source"] == ("program_span" if name in BY_SPAN
                               else "device_trace")
    assert entry["unit"] == ("ms" if name in BY_SCOPE else
                             "ratio" if name.endswith("mean") else "%")
    reader = harness.load_module("metrics", name)
    run = {"programs": {"decode_step": ["jit_step"],
                        "prefill_chunk": ["jit_chunk"]},
           "sizes": config_file()["sizes"],
           "peaks": harness.peaks_for("TPU v5 lite"),
           "_program_spans": {"serving": None, "scopes": None}}
    assert reader.value(run, {"programs": {}}) is None
    # the parent's spans: a decode dispatch that says nothing of routing,
    # a program that opens none of the new scopes
    run["_program_spans"] = {"scopes": {
        "decode_step": {"by_group": {"dense": 3.0}},
        "prefill_chunk": {"by_group": {"dense": 9.0}}}, "serving": {
        "inside": [fake_span("serving/decode_dispatch", 0, 10, rows=4)],
        "self_ns": {}}}
    run["live_in_trace"] = {"rows": 4.0, "tokens": 40_000.0}
    trace = {"programs": {"jit_step": {"median_ms": 30.0}}}
    assert reader.value(run, trace) is None
    if name in BY_SCOPE:
        role, group = BY_SCOPE[name]
        src = open(os.path.join(harness.HERE, "metrics", name + ".py")).read()
        assert f'"{role}", "{group}"' in src
        run["_program_spans"]["scopes"][role]["by_group"][group] = 1.25
        assert reader.value(run, trace) == 1.25


def fake_span(name, start, end, **attrs):
    return {"name": name, "start_ns": start, "end_ns": end, "span_id": start,
            "parent_id": None, "thread": "loop", "attrs": attrs}


def test_the_new_readers_read_a_recorded_runs_spans(served):
    """The spans the tiny run's engine recorded (its decode steps carry the
    routing counts) go through the span readers and the rooflines as a
    traced run's would."""
    cell, out, spans = served
    steps = [r for r in spans if r["name"] == "serving/decode_dispatch"]
    assert len(steps) > 20
    assert all({"assignments_held", "experts_touched", "expert_load_max",
                "expert_slots", "rows", "kv_read_tokens"}
               <= set(r["attrs"]) for r in steps)
    z = cell["config_json"]["sizes"]
    run = {"programs": cell["config_json"]["programs"], "sizes": z,
           "peaks": harness.peaks_for("TPU v5 lite"),
           "live_in_trace": {"rows": 3.0, "tokens": 150.0},
           "_program_spans": {"serving": {"inside": spans, "self_ns": {}}}}
    trace = {"programs": {"jit_step": {"median_ms": 5.0},
                          "jit_chunk": {"median_ms": 9.0}}}
    read = lambda n: harness.load_module("metrics", n).value(run, trace)
    attrs = [r["attrs"] for r in steps]
    slots = sum(a["expert_slots"] for a in attrs)
    assert slots == 8 * len(steps)              # 2 routed layers x 4 held
    assert read("experts_touched_pct") == pytest.approx(
        100.0 * sum(a["experts_touched"] for a in attrs) / slots)
    assert 0 < read("experts_touched_pct") <= 100
    fullest = sum(a["expert_load_max"] for a in attrs) / (2 * len(steps))
    mean = sum(a["assignments_held"] for a in attrs) / slots
    assert read("expert_load_max_over_mean") == pytest.approx(fullest / mean)
    assert read("expert_load_max_over_mean") >= 1.0
    # the rooflines at the PUBLISHED sizes over hand-set medians
    big = config_file()["sizes"]
    step = lambda at: fake_span(
        "serving/decode_dispatch", at, at + 5, rows=30, assignments_held=330,
        experts_touched=220, expert_load_max=40, expert_slots=352)
    run = {"programs": run["programs"], "sizes": big, "peaks": run["peaks"],
           "live_in_trace": {"rows": 30.0, "tokens": 375_000.0},
           "_program_spans": {"serving": {"inside": [
               step(0), step(10), step(20),
               fake_span("serving/prefill_dispatch", 50, 60, rows=2,
                         tokens=512),
               fake_span("serving/prefill_dispatch", 70, 80, rows=1,
                         tokens=70)], "self_ns": {}}}}
    trace = {"programs": {"jit_step": {"median_ms": 40.0},
                          "jit_chunk": {"median_ms": 30.0}}}
    flops, data = counts.decode_step(big, 30, 330, 220, 375_000)
    assert read("joyai_decode_step_roofline") == pytest.approx(
        100.0 * (data / 819e9) / 0.040)          # memory binds a decode step
    flops, data = counts.prefill_chunk(big, 291, 1.5)
    assert read("joyai_prefill_chunk_roofline") == pytest.approx(
        100.0 * max(flops / 197e12, data / 819e9) / 0.030)
    assert 0 < read("joyai_decode_step_roofline") < 100
    assert 0 < read("joyai_prefill_chunk_roofline") < 100
