"""The MiniCPM-SALA cell (``sala-longdoc-steady``): its own files go through
the unedited serving driver on the CPU at a test's sizes and come out
``correct``; the configuration is the source's but for the three stated cuts;
the counts and the pool budget match hand-worked numbers; the traffic file
holds the mix the issue names; the new readers read hand-built spans and read
nothing from nothing; the controls come out not correct."""

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
from benchmark import minicpm_sala_counts as counts  # noqa: E402
from sala_tiny import tiny_config  # noqa: E402

CELL = "sala-longdoc-steady"
SEED = 2 ** 31 + 37
V5E_LIMIT = 16_909_336_064        # the chip's bytes_limit (PERF.md, PR 23)
NEW = ["sala_decode_step_roofline", "sala_prefill_chunk_roofline",
       "sparse_kv_read_pct", "sparse_decode_rows_pct"]


def config_file():
    return harness.load_json(harness.HERE, "configs", "minicpm-sala.json")


def tiny_cell():
    """The cell's own files, its sizes and traffic brought to a test's: three
    lightning layers and two sparse ones, contexts of up to 192 tokens that
    cross ``dense_len`` (64) and ``topk`` (96 tokens), shared documents of 84
    tokens (the snapshot stride is 12)."""
    cell = harness.load_cell(CELL)
    cfg, mix = cell["config_json"], cell["traffic_json"]
    tiny = tiny_config(positions=192, layers=5)
    cfg["sizes"], cfg["published"] = tiny["sizes"], tiny["published"]
    cfg["assumed"].update(tiny["assumed"])
    cfg["engine"].update(tiny["engine"], max_slots=4, prefill_chunk=4)
    cfg["check"] = {"sample_requests": 6, "limits": {
        "served_token_gap_max_rel": 1e-3, "served_token_gap_mean_rel": 1e-4,
        "served_token_gap_under_own_logits_max_rel": 1e-3,
        "own_logits_error_rel_rms": 1e-4}}
    mix.update(
        arrivals={"process": "poisson", "rate_per_s": 4.0},
        prompt_tokens={"law": "lognormal", "median": 40, "sigma": 0.5,
                       "min": 8, "max": 140},
        output_tokens={"law": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 4, "max": 40},
        shared_prefix={"share": 0.75, "count": 2, "tokens": 84,
                       "min_own_tokens": 4},
        lead_in_s=1.0, drain_limit_s=60.0, law_seed=20261231)
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
    assert out["compared"]["rows"].shape == (6, 192)
    # three requests in four open with a shared document of 84 tokens and
    # resume from its snapshot at the boundary
    assert out["record"]["prefix_tokens"] >= 84 * 4
    assert out["record"]["jit_compiles"] == 6
    # the longest compared request stood past dense_len and topk
    assert max(b for _, b in out["compared"]["spans"]) > 96
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"prefix_hit_pct", "decode_step_ms", "prefill_chunk_ms",
                       "kv_pages_peak_pct", "device_idle_pct.serve"} <= names
    # the loop's and the lane state's readers read here too (PR 40)
    assert {"loop_deliver_ms", "loop_observe_ms", "state_restore_ms",
            "prefix_resume_shortfall_pct"} <= names
    # other models' arithmetic stays out, and the reader that finds nothing
    # in this cell's traced 6 s: no cold prefill there reaches a stride
    assert not names & {"decode_step_roofline", "olmoh_decode_step_roofline",
                        "olmoh_prefill_chunk_roofline", "state_snapshot_ms"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "itl_p95_ms", "serve_due_tok_per_s", "setup_s"}


def test_the_controls_come_out_not_correct(served):
    """Rows in, numbers out (``benchmark/tools/sala_controls.py``): the
    reference that attends densely everywhere, the one that takes the forced
    blocks only, the one with a bfloat16 lightning state and the one with
    int8-rounded weights, each in the program's place, go through the cell's
    limits and fail; the sound run's own numbers pass them. (Here the program
    is float32, so every rounding shows; on the chip the bfloat16 state reads
    under the bfloat16 program's own error and cannot be told from it:
    PERF.md section 4.)"""
    controls = harness.load_module("tools", "sala_controls")
    cell, out = served
    limits = cell["config_json"]["check"]["limits"]
    sound = {r["name"]: r["value"] for r in out["checks"]}
    found = controls.controls(cell["config_json"], SEED, out["compared"])
    assert set(found) == {"reference_dense", "reference_forced_only",
                          "reference_state_bf16", "reference_int8",
                          "selection_agreement"}
    assert set(controls.controls(cell["config_json"], SEED, out["compared"],
                                 only=["reference_int8"])) == {
        "reference_int8"}
    for name in ("reference_dense", "reference_forced_only",
                 "reference_state_bf16", "reference_int8"):
        numbers = found[name]
        # ON THE CPU ONLY for reference_state_bf16: against this float32
        # program it fails; on the chip it reads 0.0089 under the sound
        # runs' 0.019-0.020 and passes (my chip run, PR 37)
        assert numbers["own_logits_error_rel_rms"] > \
            10 * sound["own_logits_error_rel_rms"], name
        ok, fails = controls.verdict(numbers, limits)
        assert not ok and "own_logits_error_rel_rms" in fails, name
    assert 0.5 < found["selection_agreement"]["share"] <= 1.0
    assert controls.verdict(
        {n: sound[n] for n in limits}, limits) == (True, [])
    rows = compare.serving_rows(
        out["compared"]["reference_logits"],
        out["compared"]["reference_logits"], out["compared"]["rows"],
        out["compared"]["spans"], True, 0, limits)
    assert all(r["ok"] for r in rows)


def test_the_adapter_refuses_a_program_that_keeps_the_state_below_float32(
        monkeypatch):
    """No limit of the check sees the lightning state's precision on the
    chip, so the stated float32 is held to the program's leaves."""
    import jax.numpy as jnp
    from bigdl_tpu.nn.lightning_attention import LightningAttention

    import sala_tiny
    from benchmark.models import minicpm_sala as adapter

    config = sala_tiny.tiny_config()
    adapter.held_state_dtype(adapter.model_shapes(config), config)
    whole = LightningAttention.init_state
    monkeypatch.setattr(
        LightningAttention, "init_state",
        lambda self, batch, dtype=jnp.float32: tuple(
            s.astype(jnp.bfloat16) for s in whole(self, batch, dtype)))
    with pytest.raises(ValueError, match="float32 state"):
        adapter.build(config, 1)


@pytest.mark.parametrize("stated", [True, None])
def test_the_adapter_carries_the_donation_choice_to_the_engine(stated):
    """The driver hands the engine a fixed list of arguments; the
    configuration's ``engine.donate_at_prefill_end`` reaches it on the
    model, and an engine nobody told donates at a request's end."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    import sala_tiny
    from benchmark.models import minicpm_sala as adapter

    config = sala_tiny.tiny_config()
    if stated:
        config["engine"]["donate_at_prefill_end"] = True
    engine = ContinuousBatchingEngine(
        adapter.build(config, 1), max_slots=2, page_size=4, max_pages=160,
        prefill_chunk=int(config["engine"]["prefill_chunk"]))
    assert engine._donate_at_prefill_end is bool(stated)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(harness.HERE, "reference",
                            "minicpm_sala.py")).read()
    assert "bigdl_tpu" not in src.split('"""', 2)[2]
    assert "import benchmark" not in src and "from benchmark" not in src


def test_the_configuration_is_the_sources_but_for_the_three_stated_cuts():
    cfg = config_file()
    man = harness.manifest()
    entry = {c["name"]: c for c in man["configs"]}["minicpm-sala"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "max_position_embeddings", "vocab_size"]
    z = cfg["sizes"]
    assert all(cfg[k] == v for k, v in z.items())
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        published = next(r for r in rows if r["name"] == "MiniCPM-SALA")
        assert published["source_url"] == cfg["source"]
        published = published["config"]
    else:       # the catalog's row, for a checkout without the guides
        published = {
            "hidden_size": 4096, "intermediate_size": 16384, "head_dim": 128,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "num_hidden_layers": 32, "max_position_embeddings": 524288,
            "vocab_size": 73448, "lightning_nh": 32, "lightning_nkv": 32,
            "lightning_head_dim": 128, "scale_emb": 12, "scale_depth": 1.4,
            "dim_model_base": 256, "mup_denominator": 32, "rope_theta": 10000,
            "rms_norm_eps": 1e-6, "model_type": "minicpm_sala"}
    differs = sorted(k for k, v in published.items() if z[k] != v)
    assert differs == sorted(cfg["reduced"])
    assert {k: published[k] for k in differs} == cfg["published"] == {
        "num_hidden_layers": 32, "max_position_embeddings": 524288,
        "vocab_size": 73448}
    assert (z["num_hidden_layers"], z["max_position_embeddings"],
            z["vocab_size"]) == (16, 32768, 9216)
    assert len(z["mixer_types"]) == 32              # as published, whole
    kinds = counts.layer_kinds(z)                   # what is held here
    assert z["layers_held"] == [9, 25] and len(kinds) == 16
    assert [i + 9 for i, k in enumerate(kinds) if k == "minicpm4"] == [
        9, 16, 17, 22]
    assert z["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    for key in ("deployment", "layers_held", "max_position_embeddings",
                "vocab_size", "sparse_config", "dense_len_by_position",
                "lightning_decay", "lightning_layer", "sparse_layer", "block",
                "qk_norm_gains", "weights_dtype", "kv_dtype", "state_dtype",
                "activations", "weights", "decoding"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["vocab_real"] == 9216 and 9216 % 128 == 0
    assert cfg["driver"] == "serve"
    assert cfg["adapter"] == cfg["reference"] == "minicpm_sala"
    assert cfg["programs"] == {"decode_step": ["jit_step"],
                               "prefill_chunk": ["jit_chunk"]}
    assert cfg["engine"]["page_size"] == z["sparse_config"]["kernel_stride"]
    assert cfg["engine"]["donate_at_prefill_end"] is True
    assert "donate_at_prefill_end" in cfg["assumed"]
    assert len(entry["why"]) <= 200


def test_the_counts_are_the_hand_worked_numbers():
    z = config_file()["sizes"]
    d, f = 4096, 16384
    lightning = 5 * d * d + 3 * d * f
    sparse = 3 * d * d + 2 * d * 256 + 3 * d * f
    assert counts.lightning_mixer_params(z) + counts.mlp_params(z) \
        == lightning == 285_212_672
    assert counts.sparse_mixer_params(z) + counts.mlp_params(z) \
        == sparse == 253_755_392
    assert counts.block_matmul_params(z) == 12 * lightning + 4 * sparse
    assert counts.head_params(z) == 9216 * 4096 == 37_748_736
    total = counts.block_matmul_params(z) + 2 * counts.head_params(z)
    assert total == 4_513_071_104                   # 9.03 GB in bfloat16
    assert counts.kv_bytes_per_token(z) == 4 * 2 * 2 * 128 * 2 == 4096
    assert counts.compressed_bytes_per_token(z) == 4 * 256 * 2 / 16 == 128
    assert counts.lane_state_bytes(z) == 12 * 32 * 128 * 128 * 4 == 25_165_824
    # a query at 25000: 64 blocks less the 23 tokens ahead of it in its own
    assert counts.attended_tokens(z, 25000) == 64 * 64 - (63 - 25000 % 64)
    assert counts.attended_tokens(z, 8191) == 8192
    assert counts.attended_tokens(z, 8192) == 64 * 64 - 63
    # ten rows at 25000: the selected tokens' K and V, every compressed key
    att, held = 10 * 4073, 10 * 25001
    flops, data = counts.decode_step(z, 10, att, held)
    weights = 12 * lightning + 4 * sparse + 37_748_736
    assert data == 2 * weights + 4 * 256 * 2 * (2 * att + held / 16) \
        + 2 * 25_165_824 * 10
    assert flops == 2 * weights * 10 \
        + 4 * (4 * 4096 * att + 2 * 4096 * held / 16) \
        + 12 * 5 * 32 * 128 * 128 * 10
    flops, data = counts.prefill_chunk(z, 512, 2)
    assert data == 2 * weights + 2 * 25_165_824 * 2
    assert flops == 2 * (12 * lightning + 4 * sparse) * 512 \
        + 2 * 37_748_736 * 2 + 12 * 5 * 524_288 * 512 \
        + 4 * 4096 * 4 * 512 * 256 / 2
    assert counts.lightning_step(z, 16) == (5 * 524_288 * 16,
                                            8 * 524_288 * 16)


def test_the_pool_budget_on_this_configurations_geometry():
    from benchmark.models import minicpm_sala as adapter

    serve = harness.load_module("drivers", "serve")
    cfg = config_file()
    geometry = adapter.cache_geometry(cfg)
    lane = 25_165_824
    assert geometry == {
        "max_positions": 32768, "page_device_bytes": 16 * (4096 + 128),
        # the lane and a sixteenth of the scratch lane (no padding: the
        # rehearsal's factor 1), two snapshots flat
        "fixed_device_bytes_per_lane": int(lane * (1 + 1 / 16 + 2))}
    weights = 2 * (counts.block_matmul_params(cfg["sizes"])
                   + 2 * counts.head_params(cfg["sizes"]))
    budget = (int(V5E_LIMIT * 0.9) - weights - cfg["engine"]["reserve_bytes"]
              - 16 * geometry["fixed_device_bytes_per_lane"])
    floor = 1 + 16 * 2048
    pages = serve.pool_pages(cfg, geometry, V5E_LIMIT, weights)
    # every lane at full context fits, and the four hot documents beside
    # them (4 x 1536 pages) with room: the budget, not the floor, binds
    assert pages == budget // 67_584 >= floor + 4 * 1536
    assert serve.pool_pages(cfg, geometry, None, 0) == floor == 32_769


def test_the_traffic_file_holds_the_mix_the_issue_names():
    mix = harness.load_json(harness.HERE, "traffic", "longdoc-steady.json")
    assert mix["kind"] == "open_loop"
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["arrivals"]["rate_per_s"] > 0
    assert mix["prompt_tokens"] == {"law": "lognormal", "median": 4096,
                                    "sigma": 0.8, "min": 256, "max": 30720}
    assert mix["output_tokens"] == {"law": "lognormal", "median": 192,
                                    "sigma": 0.6, "min": 32, "max": 768}
    assert mix["shared_prefix"] == {"share": 0.75, "count": 4,
                                    "tokens": 24576, "min_own_tokens": 64}
    assert mix["drain_limit_s"] == 90.0 and mix["lead_in_s"] >= 25.0
    steady = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    assert set(mix) == set(steady)      # every key the generator reads
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-steady"
    assert cell["config"] == "minicpm-sala" and len(cell["why"]) <= 200
    # the cell's why gives the rate the file holds
    assert f"{mix['arrivals']['rate_per_s']:g} req/s" in cell["why"]
    # no request outgrows the served context; a shared document is whole
    # snapshot strides (25,165,824 B a lane over 4224 B a token, in chunks
    # of 256: 6144 tokens), so a hit resumes at its end
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 32768
    assert 24576 + 64 + 768 <= 32768 and 24576 % 6144 == 0
    # every document is first used in the lead-in of the drawn schedule
    from benchmark import loadgen
    reqs = loadgen.open_loop_requests(mix, 1, 51.0, 9216)
    lead = {r["shared"] for r in reqs if r["due_s"] < 0}
    assert lead >= {0, 1, 2, 3}


def fake_span(name, start, end, **attrs):
    return {"name": name, "start_ns": start, "end_ns": end, "span_id": start,
            "parent_id": None, "thread": "loop", "attrs": attrs}


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_in_the_manifest_and_reads_nothing_from_nothing(name):
    entry = {m["name"]: m for m in harness.manifest()["per_layer"]}[name]
    assert CELL in entry["workloads"] and entry["moves"] == "itl_p95_ms"
    assert entry["source"] == ("device_trace" if "roofline" in name
                               else "program_span")
    assert entry["layer"] == ("kernels" if "roofline" in name
                              else "cache manager")
    assert entry["unit"] == "%"
    reader = harness.load_module("metrics", name)
    run = {"programs": {"decode_step": ["jit_step"],
                        "prefill_chunk": ["jit_chunk"]},
           "sizes": config_file()["sizes"],
           "peaks": harness.peaks_for("TPU v5 lite"),
           "_program_spans": {"serving": None}}
    assert reader.value(run, {"programs": {}}) is None
    # the parent's spans: a decode dispatch that says nothing of selection
    run["_program_spans"] = {"serving": {"inside": [
        fake_span("serving/decode_dispatch", 0, 10, rows=4)], "self_ns": {}}}
    trace = {"programs": {"jit_step": {"median_ms": 30.0}}}
    assert reader.value(run, trace) is None


def test_the_new_readers_read_hand_built_spans_and_programs():
    z = config_file()["sizes"]
    run = {"programs": {"decode_step": ["jit_step"],
                        "prefill_chunk": ["jit_chunk"]},
           "sizes": z, "peaks": harness.peaks_for("TPU v5 lite")}
    # a row that selects gathers 64 blocks of 64 tokens, one under
    # dense_len 128: the rule's count is under the gather's
    step = lambda at, rows, att, held, sel: fake_span(
        "serving/decode_dispatch", at, at + 5, rows=rows, attended_tokens=att,
        gathered_tokens=4096 * sel + 8192 * (rows - sel),
        cached_tokens=held, selecting_rows=sel)
    inside = [
        step(0, 10, 40_000, 250_000, 10), step(10, 10, 40_000, 250_000, 10),
        step(20, 8, 36_000, 150_000, 6),
        fake_span("serving/prefill_dispatch", 50, 60, rows=2, tokens=512),
        fake_span("serving/prefill_dispatch", 70, 80, rows=2, tokens=512),
        fake_span("serving/prefill_dispatch", 90, 95, rows=1, tokens=100)]
    run["_program_spans"] = {"serving": {"inside": inside, "self_ns": {}}}
    trace = {"programs": {"jit_step": {"median_ms": 20.0},
                          "jit_chunk": {"median_ms": 50.0}}}
    read = lambda n: harness.load_module("metrics", n).value(run, trace)
    assert read("sparse_kv_read_pct") == pytest.approx(
        100.0 * (26 * 4096 + 2 * 8192) / 650_000)
    assert read("sparse_decode_rows_pct") == pytest.approx(100.0 * 26 / 28)
    flops, data = counts.decode_step(z, 10, 40_000, 250_000)
    assert read("sala_decode_step_roofline") == pytest.approx(
        100.0 * (data / 819e9) / 0.020)          # memory binds a decode step
    flops, data = counts.prefill_chunk(z, 512, 2)
    assert read("sala_prefill_chunk_roofline") == pytest.approx(
        100.0 * (flops / 197e12) / 0.050)        # compute binds a full chunk
    assert 0 < read("sala_decode_step_roofline") < 100
    assert 0 < read("sala_prefill_chunk_roofline") < 100
