"""A second serving architecture comes by new files alone: a stand-in that
lives wholly in this file (a rotary, grouped-KV ``TransformerLM`` under
Hugging-Face-style size keys, its own seeded weights and its own plain
float32 reference, registered as ``benchmark.models.rotarylm`` /
``benchmark.reference.rotarylm``) goes through the unedited serving driver
on the CPU and comes out ``correct``. Beside it: the driver's pool budget on
hand-worked geometries, GPT-2's among them at the chip's own numbers."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, harness  # noqa: E402

SEED = 2 ** 31 + 21


class RotaryLM:
    """The adapter: what ``benchmark/models/<adapter>.py`` holds for a
    serving configuration."""

    @staticmethod
    def weights(config, seed):
        """The stand-in's own seeded tree (float32, host arrays made into
        device arrays): gains and biases random too, so that a leaf mapped
        to the wrong place shows."""
        import jax.numpy as jnp

        z = config["sizes"]
        e, v = int(z["hidden_size"]), int(z["vocab_size"])
        heads, kv = (int(z[k]) for k in ("num_attention_heads",
                                         "num_key_value_heads"))
        kv_dim, ff = kv * (e // heads), int(z["intermediate_size"])
        std = float(z["initializer_range"])
        rng = np.random.default_rng(int(seed))
        mat = lambda *s: jnp.asarray(std * rng.standard_normal(s), jnp.float32)
        vec = lambda n: jnp.asarray(0.1 * rng.standard_normal(n), jnp.float32)
        gain = lambda n: 1.0 + vec(n)
        blocks = [{"ln1_g": gain(e), "ln1_b": vec(e),
                   "q_w": mat(e, e), "q_b": vec(e),
                   "k_w": mat(kv_dim, e), "k_b": vec(kv_dim),
                   "v_w": mat(kv_dim, e), "v_b": vec(kv_dim),
                   "o_w": mat(e, e), "o_b": vec(e),
                   "ln2_g": gain(e), "ln2_b": vec(e),
                   "up_w": mat(ff, e), "up_b": vec(ff),
                   "down_w": mat(e, ff), "down_b": vec(e)}
                  for _ in range(int(z["num_hidden_layers"]))]
        return {"embed": mat(v, e), "blocks": blocks,
                "norm_g": gain(e), "norm_b": vec(e)}

    @staticmethod
    def build(config, seed):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.models.transformer import TransformerLM

        z = config["sizes"]
        model = TransformerLM(
            int(z["vocab_size"]), embed_dim=int(z["hidden_size"]),
            num_heads=int(z["num_attention_heads"]),
            num_layers=int(z["num_hidden_layers"]),
            max_len=int(z["max_position_embeddings"]),
            mlp_ratio=int(z["intermediate_size"]) // int(z["hidden_size"]),
            num_kv_heads=int(z["num_key_value_heads"]), use_rope=True)
        model.evaluate()
        w = RotaryLM.weights(config, seed)
        leaf = lambda a, b: {"~params": {"weight": a, "bias": b}}
        tree = {"~params": {"tok_embed": w["embed"]},
                "ln_f": leaf(w["norm_g"], w["norm_b"])}
        for i, b in enumerate(w["blocks"]):
            tree[f"block{i}"] = {
                "ln1": leaf(b["ln1_g"], b["ln1_b"]),
                "attn": {"qkv": leaf(
                    jnp.concatenate([b["q_w"], b["k_w"], b["v_w"]]),
                    jnp.concatenate([b["q_b"], b["k_b"], b["v_b"]])),
                    "out_proj": leaf(b["o_w"], b["o_b"])},
                "ln2": leaf(b["ln2_g"], b["ln2_b"]),
                "fc1": leaf(b["up_w"], b["up_b"]),
                "fc2": leaf(b["down_w"], b["down_b"])}
        assert jax.tree.structure(tree) == jax.tree.structure(
            model.params_dict())
        model.load_params_dict(tree)
        return model

    @staticmethod
    def cache_geometry(config):
        z, e = config["sizes"], config["engine"]
        head = int(z["hidden_size"]) // int(z["num_attention_heads"])
        token = (int(z["num_hidden_layers"]) * 2
                 * int(z["num_key_value_heads"]) * head * 4)   # float32 K, V
        return {"max_positions": int(z["max_position_embeddings"]),
                "page_device_bytes": int(e["page_size"]) * token,
                "fixed_device_bytes_per_lane": 0}

    # the parameters' bytes and the paged prefill pass are TransformerLM's,
    # whatever the sizes' key names: what the GPT-2 adapter wrote serves here
    @staticmethod
    def weight_bytes(model):
        from benchmark.models import gpt2

        return gpt2.weight_bytes(model)

    @staticmethod
    def paged_logits(model, kv_dtype, config, rows):
        from benchmark.models import gpt2

        return gpt2.paged_logits(model, kv_dtype, config, rows)


class RotaryReference:
    """The plain reference: what ``benchmark/reference/<ref>.py`` holds.
    Float32 jax.numpy at precision "highest", no cache, nothing of the
    program: rotary positions on interleaved feature pairs, each key/value
    head shared by consecutive query heads, pre-norm blocks, tanh-GELU MLP,
    logits against the embedding."""

    @staticmethod
    def forward(weights, ids, config):
        import jax
        import jax.numpy as jnp

        z = config["sizes"]
        heads, kv = (int(z[k]) for k in ("num_attention_heads",
                                         "num_key_value_heads"))
        theta = float(z["rope_theta"])

        def ln(x, g, b):
            mean = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
            return (x - mean) / jnp.sqrt(var + 1e-5) * g + b

        def rope(x):                             # (b, t, h, d)
            d, t = x.shape[-1], x.shape[1]
            inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
            sin, cos = (f(ang)[None, :, None, :] for f in (jnp.sin, jnp.cos))
            a, b = x[..., 0::2], x[..., 1::2]
            return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                             -1).reshape(x.shape)

        with jax.default_matmul_precision("highest"):
            ids = jnp.asarray(ids, jnp.int32)
            f32 = lambda tree: jax.tree.map(
                lambda a: a.astype(jnp.float32), tree)
            embed = f32(weights["embed"])
            x = jnp.take(embed, ids, axis=0)
            b, t, e = x.shape
            d = e // heads
            causal = jnp.tril(jnp.ones((t, t), bool))
            for w in map(f32, weights["blocks"]):
                h = ln(x, w["ln1_g"], w["ln1_b"])
                q = rope((h @ w["q_w"].T + w["q_b"]).reshape(b, t, heads, d))
                k = rope((h @ w["k_w"].T + w["k_b"]).reshape(b, t, kv, d))
                v = (h @ w["v_w"].T + w["v_b"]).reshape(b, t, kv, d)
                k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                    jnp.float32(d))
                p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
                a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, e)
                x = x + a @ w["o_w"].T + w["o_b"]
                h = ln(x, w["ln2_g"], w["ln2_b"])
                h = jax.nn.gelu(h @ w["up_w"].T + w["up_b"], approximate=True)
                x = x + h @ w["down_w"].T + w["down_b"]
            return ln(x, f32(weights["norm_g"]), f32(weights["norm_b"])) \
                @ embed.T


def rotary_cell():
    """The serving cell's traffic and engine at a test's size, under a
    configuration that names the stand-in's two modules and holds no
    GPT-2 key."""
    sys.modules["benchmark.models.rotarylm"] = RotaryLM
    sys.modules["benchmark.reference.rotarylm"] = RotaryReference
    cell = harness.load_cell("gpt2l-chat-steady")
    cfg, mix = cell["config_json"], cell["traffic_json"]
    cfg.update(name="rotary-stand-in", adapter="rotarylm",
               reference="rotarylm", reduced=[],
               sizes={"hidden_size": 48, "intermediate_size": 192,
                      "num_hidden_layers": 2, "num_attention_heads": 6,
                      "num_key_value_heads": 2,
                      "max_position_embeddings": 96, "vocab_size": 200,
                      "rope_theta": 10000.0, "initializer_range": 0.2})
    cfg["assumed"].update(vocab_real=200, weights_dtype="float32")
    cfg["engine"].update(max_slots=4, page_size=4, prefill_chunk=8,
                         prefill_rows=2)
    cfg["check"] = {"sample_requests": 12, "limits": {
        "served_token_gap_max_rel": 1e-3, "served_token_gap_mean_rel": 1e-4,
        "served_token_gap_under_own_logits_max_rel": 1e-3,
        "own_logits_error_rel_rms": 1e-4}}
    mix.update(
        arrivals={"process": "poisson", "rate_per_s": 6.0},
        prompt_tokens={"law": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 48},
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
    cell = rotary_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        out = serve.run(cell, SEED, 2.0, False, time.perf_counter())
    return cell, out


def test_second_architecture_is_served_and_correct_by_new_files_alone(served):
    cell, out = served
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8
    sizes = out["record"]["sizes"]
    assert sizes is cell["config_json"]["sizes"]
    assert not [k for k in sizes if k.startswith("n_")]
    assert set(out["values"]) == {"itl_p95_ms", "serve_due_tok_per_s",
                                  "setup_s"}
    # the sampled rows are as wide as the geometry says, not as GPT-2 is
    assert out["compared"]["rows"].shape[1] == 96
    assert out["record"]["prefix_tokens"] > 0
    # every file of it is this one
    for mod in (RotaryLM, RotaryReference):
        assert sys.modules[mod.__module__].__file__ == os.path.abspath(
            __file__)
    # GPT-2's own counts find nothing of theirs to read and say so loudly:
    # a new architecture brings its own reader as a new file
    with pytest.raises(KeyError):
        harness.load_module("metrics", "decode_step_roofline").value(
            dict(out["record"], live_in_trace={"rows": 1.0, "tokens": 9.0},
                 peaks=harness.peaks_for("TPU v5 lite")),
            {"programs": {"jit_step": {"runs": 1, "median_ms": 1.0,
                                       "total_ms": 1.0}}})


def test_second_architecture_reference_agrees_with_the_program():
    import jax.numpy as jnp

    cfg = rotary_cell()["config_json"]
    model = RotaryLM.build(cfg, 5)
    ids = np.random.RandomState(1).randint(0, 200, (2, 40))
    want = np.asarray(RotaryReference.forward(
        RotaryLM.weights(cfg, 5), ids, cfg))
    got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    # positions matter (rotary is there) and so does the grouping
    rolled = np.asarray(RotaryReference.forward(
        RotaryLM.weights(cfg, 5), np.roll(ids, 1, axis=1), cfg))
    assert np.abs(rolled[:, 1:] - want[:, :-1]).max() > 1e-2 * np.abs(
        want).max()


def test_second_architecture_check_fails_weights_rounded_to_int8(served):
    from benchmark import weights as bw

    serve = harness.load_module("drivers", "serve")
    cell, out = served
    cfg, got = cell["config_json"], out["compared"]
    low = serve.reference_logits(cfg, SEED, got["rows"], bw.rounded)
    rows = {r["name"]: r for r in compare.serving_rows(
        got["reference_logits"], low, got["rows"], got["spans"], True, 0,
        cfg["check"]["limits"])}
    sound = {r["name"]: r for r in out["checks"]}
    assert not rows["own_logits_error_rel_rms"]["ok"]
    assert rows["own_logits_error_rel_rms"]["value"] > \
        10 * sound["own_logits_error_rel_rms"]["value"]


# the chip's bytes_limit (PERF.md, PR 23) and GPT-2 Large's bfloat16 weights
V5E_LIMIT = 16_909_336_064
GPT2L_WEIGHTS = 2 * (50304 * 1280 + 1024 * 1280 + 2 * 1280 + 36 * (
    4 * 1280 + 3 * 1280 * 1280 + 3 * 1280 + 1280 * 1280 + 1280
    + 2 * 4 * 1280 * 1280 + 5 * 1280))


@pytest.mark.parametrize("case", ["gpt2-large", "no-limit", "hybrid"])
def test_pool_pages_budget_on_hand_worked_geometries(case):
    serve = harness.load_module("drivers", "serve")
    if case == "hybrid":
        # pages for 4 of 16 layers at 15,360 bytes a token, 12 layers of
        # 2.2 MB fixed state a lane: what ISSUE 33's motivation sizes
        config = {"engine": {"max_slots": 32, "page_size": 16,
                             "reserve_bytes": 2 << 30}}
        geometry = {"max_positions": 4096, "page_device_bytes": 16 * 15360,
                    "fixed_device_bytes_per_lane": 12 * 2_200_000}
        weights = 4_870_000_000
        budget = (int(V5E_LIMIT * 0.9) - weights - (2 << 30)
                  - 32 * 12 * 2_200_000)
        assert serve.pool_pages(config, geometry, V5E_LIMIT, weights) == \
            budget // (16 * 15360) == 29932
        # state that leaves no room for pages: the floor, every lane whole
        geometry["fixed_device_bytes_per_lane"] = 1 << 30
        assert serve.pool_pages(config, geometry, V5E_LIMIT, weights) == \
            1 + 32 * 256
        return
    from benchmark.models import gpt2

    config = harness.load_json(harness.HERE, "configs", "gpt2-large.json")
    geometry = gpt2.cache_geometry(config)
    assert geometry == {"max_positions": 1024,
                        "page_device_bytes": 36 * 2 * 16 * 1280 * 2 * 9 / 8,
                        "fixed_device_bytes_per_lane": 0}
    if case == "no-limit":     # the CPU of a test: every lane at full context
        assert serve.pool_pages(config, geometry, None, 0) == 1 + 32 * 64
    else:                      # the pool every run of the cell has had
        assert serve.pool_pages(config, geometry, V5E_LIMIT,
                                GPT2L_WEIGHTS) == 2502
