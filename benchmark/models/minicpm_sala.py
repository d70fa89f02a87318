"""MiniCPM-SALA configurations on the program: ``HybridDecoderLM`` (the class
that serves Olmo-Hybrid) built as shapes from the configuration file's sizes,
with lightning-attention and block-sparse layers, the pre-norm block and the
three muP scales, and loaded with the benchmark's seeded weights in the
served dtype; and the served model's own logits over given rows (the
precision check: the hybrid decoder's paged prefill pass, shared with
``benchmark/models/olmo_hybrid.py``)."""

import functools

import jax
import jax.numpy as jnp

from benchmark import minicpm_sala_counts as counts
from benchmark.models.olmo_hybrid import (  # noqa: F401  (the adapter's contract)
    SNAPSHOTS_PER_LANE, paged_logits, weight_bytes)

REFERENCE = "minicpm_sala"
SPARSE, LIGHTNING = counts.SPARSE, counts.LIGHTNING
#: the source's names for the layer kinds -> the program's
KINDS = {SPARSE: "sparse_attention", LIGHTNING: "lightning_attention"}

# memory_analysis of the compile rehearsal for a v5e (PERF.md, PR 37): the
# lanes' S leaves (lanes, 32, 128, 128) float32 are whole (8, 128) tiles:
# a lane takes its logical bytes
LANE_DEVICE_FACTOR = 1.0

#: the sparse layers' q and k gains are drawn from this range (``assumed``'s
#: ``qk_norm_gains`` says why)
QK_GAIN = (1.4, 2.2)


def layer_kinds(z):
    """The layers held here, under the source's names."""
    return tuple(counts.layer_kinds(z))


def _dims(z):
    return {"d": int(z["hidden_size"]), "h": int(z["num_attention_heads"]),
            "kv": int(z["num_key_value_heads"]), "hd": int(z["head_dim"]),
            "mlp": int(z["intermediate_size"]),
            "lh": int(z["lightning_nh"]), "ld": int(z["lightning_head_dim"]),
            "vocab": int(z["vocab_size"])}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _weights(key, dims, kinds, dtype, std, wide_std):
    """The family's initialisation as ``assumed`` states it: hidden matrices
    N(0, std) (muP: the base range over sqrt(hidden / dim_model_base)),
    embedding and head N(0, wide_std), unit gains but the sparse layers' q
    and k gains, drawn from ``QK_GAIN`` so that attention is peaked."""
    m = dict(dims)
    d, inner, kv = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    lin = m["lh"] * m["ld"]

    def normal(k, shape, s=std):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    gain = lambda k, n: jax.random.uniform(
        k, (n,), jnp.float32, *QK_GAIN).astype(dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for i, kind in enumerate(kinds):
        k = jax.random.split(jax.random.fold_in(k_layers, i), 10)
        w = {"mixer_norm_g": ones(d), "mlp_norm_g": ones(d),
             "gate_w": normal(k[0], (m["mlp"], d)),
             "up_w": normal(k[1], (m["mlp"], d)),
             "down_w": normal(k[2], (d, m["mlp"]))}
        if kind == LIGHTNING:
            w.update({
                "q_w": normal(k[3], (lin, d)), "k_w": normal(k[4], (lin, d)),
                "v_w": normal(k[5], (lin, d)), "g_w": normal(k[6], (lin, d)),
                "o_w": normal(k[7], (d, lin)),
                "q_norm_g": ones(m["ld"]), "k_norm_g": ones(m["ld"]),
                "o_norm_g": ones(lin)})
        else:
            w.update({
                "q_w": normal(k[3], (inner, d)), "k_w": normal(k[4], (kv, d)),
                "v_w": normal(k[5], (kv, d)), "g_w": normal(k[6], (inner, d)),
                "o_w": normal(k[7], (d, inner)),
                "q_norm_g": gain(k[8], m["hd"]),
                "k_norm_g": gain(k[9], m["hd"])})
        layers.append(w)
    return {"embed": normal(k_embed, (m["vocab"], d), wide_std),
            "head_w": normal(k_head, (m["vocab"], d), wide_std),
            "norm_f_g": ones(d), "layers": layers}


def weights(config, seed):
    """The benchmark's seeded weights in its own layout: the tree ``build``
    loads into the program and the plain reference reads."""
    z = config["sizes"]
    base = float(z.get("initializer_range", 0.1))
    width = float(z["hidden_size"]) / float(z["dim_model_base"])
    return _weights(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                    tuple(sorted(_dims(z).items())), layer_kinds(z),
                    jnp.dtype(config["assumed"]["weights_dtype"]),
                    base / width ** 0.5, base)


def lane_state_bytes(config):
    """One lane's recurrent state over every lightning layer (float32)."""
    return counts.lane_state_bytes(config["sizes"])


def cache_geometry(config):
    """What the driver sizes the page pool from: only the sparse layers hold
    pages: K and V of a page's tokens (a KV group's leaf is (pages, 16, 128):
    whole tiles, a page takes its logical bytes) and ONE compressed key a
    page; every lane holds its recurrent state, its share of the scratch lane
    and its share of the engine's snapshot store, whatever its length."""
    z, e = config["sizes"], config["engine"]
    item = jnp.dtype(config["assumed"]["weights_dtype"]).itemsize
    page = int(e["page_size"])
    if page != int(z["sparse_config"]["kernel_stride"]):
        raise ValueError("a page holds one compressed key: page_size must "
                         "equal the sparse layers' kernel_stride")
    page_bytes = page * (counts.kv_bytes_per_token(z, item)
                         + counts.compressed_bytes_per_token(z, item))
    slots = int(e["max_slots"])
    per_lane = lane_state_bytes(config) * (
        LANE_DEVICE_FACTOR * (1 + 1.0 / slots) + SNAPSHOTS_PER_LANE)
    return {"max_positions": int(z["max_position_embeddings"]),
            "page_device_bytes": int(page_bytes),
            "fixed_device_bytes_per_lane": int(per_lane)}


def layer_tree(kind, b):
    """One layer, the benchmark's layout -> ``HybridBlock.params_dict()``'s:
    q, k, v fused; the lightning layers carry an output norm."""
    p = lambda a: {"~params": {"weight": a}}
    mixer = {"qkv": p(jnp.concatenate([b["q_w"], b["k_w"], b["v_w"]])),
             "gate": p(b["g_w"]), "out_proj": p(b["o_w"]),
             "q_norm": p(b["q_norm_g"]), "k_norm": p(b["k_norm_g"])}
    if kind == LIGHTNING:
        mixer["o_norm"] = p(b["o_norm_g"])
    return {"mixer": mixer,
            "mixer_norm": p(b["mixer_norm_g"]), "mlp_norm": p(b["mlp_norm_g"]),
            "mlp": {"gate": p(b["gate_w"]), "up": p(b["up_w"]),
                    "down": p(b["down_w"])}}


def program_tree(w, kinds):
    """The benchmark's layout -> ``HybridDecoderLM.params_dict()``'s. Takes
    the layers out of ``w`` one at a time, so the two layouts never both
    hold more than one layer's fused projections."""
    p = lambda a: {"~params": {"weight": a}}
    tree = {"~params": {"tok_embed": w["embed"]},
            "norm_f": p(w["norm_f_g"]), "head": p(w["head_w"])}
    layers = w["layers"]
    for i, kind in enumerate(kinds):
        tree[f"block{i}"] = layer_tree(kind, layers[i])
        layers[i] = None
    return tree


def model_shapes(config):
    """The program's model for this configuration, as shapes."""
    from benchmark.harness import BenchmarkError
    from bigdl_tpu.models import hybrid
    from bigdl_tpu.models.hybrid import HybridDecoderLM
    from bigdl_tpu.nn.module import abstract_init

    if not all(hasattr(hybrid, name) for name in ("SPARSE", "LIGHTNING")):
        raise BenchmarkError(
            "this program's HybridDecoderLM has no lightning-attention or "
            "block-sparse layers: it cannot run minicpm-sala")
    z, m = config["sizes"], _dims(config["sizes"])
    if z.get("attn_use_rope"):
        raise ValueError("attn_use_rope: the sparse layers do not rotate")
    if m["d"] != m["h"] * m["hd"]:
        raise ValueError("hidden_size is not heads x head_dim")
    if (m["lh"], m["ld"]) != (m["h"], m["hd"]):
        raise ValueError("the program's lightning layers take the "
                         "attention's heads and head size")
    depth = int(config.get("published", {}).get(
        "num_hidden_layers", z["num_hidden_layers"]))
    model = abstract_init(lambda: HybridDecoderLM(
        m["vocab"], m["d"], m["h"], [KINDS[k] for k in layer_kinds(z)],
        m["mlp"], int(z["max_position_embeddings"]), num_kv_heads=m["kv"],
        eps=float(z["rms_norm_eps"]), block_style="pre_norm",
        residual_scale=float(z["scale_depth"]) / depth ** 0.5,
        embed_scale=float(z["scale_emb"]),
        logit_scale=float(z["dim_model_base"]) / m["d"],
        rope_theta=(float(z["rope_theta"]) if z["lightning_use_rope"]
                    else None),
        sparse={k: int(v) for k, v in z["sparse_config"].items()}))
    model.evaluate()
    return model


def held_state_dtype(model, config):
    """The dtype of the lanes' leaves as the program would make them for
    the engine: ``assumed`` states float32, and no limit of the check sees
    the state's precision (a bfloat16 fixed-decay state reads UNDER the
    bfloat16 program's own error on the chip: PERF.md section 4), so the
    adapter refuses a program that keeps it in anything else."""
    pool = jax.eval_shape(lambda: model.init_page_pool(
        2, int(config["engine"]["page_size"]),
        dtype=jnp.dtype(config["assumed"]["weights_dtype"]), lanes=1))
    kinds = {jnp.dtype(leaf.dtype) for leaf in jax.tree.leaves(pool["lanes"])}
    if kinds != {jnp.dtype(jnp.float32)}:
        raise ValueError(
            f"the lanes' recurrent state is kept in {sorted(map(str, kinds))}"
            ": the configuration states a float32 state")


def build(config, seed):
    model = model_shapes(config)
    held_state_dtype(model, config)
    tree = program_tree(weights(config, seed), layer_kinds(config["sizes"]))
    have = model.params_dict()
    if jax.tree.structure(tree) != jax.tree.structure(have) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(tree),
                                               jax.tree.leaves(have))):
        raise ValueError("HybridDecoderLM's parameter tree is not the one "
                         "benchmark/models/minicpm_sala.py maps to")
    model.load_params_dict(tree)
    # the deployment's choice (the configuration's "engine" block): the
    # driver hands the engine a fixed list of arguments, so the model
    # carries this one to it
    model.donate_at_prefill_end = bool(
        config["engine"].get("donate_at_prefill_end", False))
    return model
