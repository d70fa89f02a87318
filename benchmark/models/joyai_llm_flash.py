"""JoyAI-LLM-Flash configurations on the program: ``HybridDecoderLM`` (the
class that serves Olmo-Hybrid and MiniCPM-SALA) built as shapes from the
configuration file's sizes, every layer a latent-attention one, the
feed-forward branch routed from ``first_k_dense_replace`` on with the experts
this chip holds, and loaded with the benchmark's seeded weights in the served
dtype; and the served model's own logits over given rows (the precision
check: the hybrid decoder's paged prefill pass, shared with
``benchmark/models/olmo_hybrid.py``)."""

import functools

import jax
import jax.numpy as jnp

from benchmark import joyai_counts as counts
from benchmark.models.olmo_hybrid import (  # noqa: F401  (the adapter's contract)
    paged_logits, weight_bytes)

REFERENCE = "joyai_llm_flash"

#: the gains of the q_a and kv_a norms are drawn from this range
#: (``assumed``'s ``latent_norm_gains`` says why)
LATENT_GAIN = (2.4, 3.6)
#: the selection bias is drawn N(0, this) (``assumed``'s ``selection_bias``)
BIAS_STD = 0.012


def _dims(z):
    a, b = z["layers_held"]
    first, count = z["experts_held"]
    if int(count) != int(z["n_routed_experts"]):
        raise ValueError("experts_held does not hold n_routed_experts")
    return {"d": int(z["hidden_size"]), "h": int(z["num_attention_heads"]),
            "nope": int(z["qk_nope_head_dim"]),
            "rope": int(z["qk_rope_head_dim"]), "v": int(z["v_head_dim"]),
            "q_rank": int(z["q_lora_rank"]), "kv_rank": int(z["kv_lora_rank"]),
            "mlp": int(z["intermediate_size"]),
            "expert": int(z["moe_intermediate_size"]),
            "held": int(count), "router": int(z["router_experts"]),
            "shared": int(z["n_shared_experts"]),
            "layers": int(b) - int(a), "dense": counts.dense_layers(z),
            "vocab": int(z["vocab_size"])}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _weights(key, dims, dtype, std):
    """The family's initialisation as ``assumed`` states it: every matrix
    N(0, std), the router among them (float32, as its selection bias, drawn
    N(0, BIAS_STD)); unit gains but the q_a and kv_a norms', drawn from
    ``LATENT_GAIN`` so that attention is peaked."""
    m = dict(dims)
    d, h = m["d"], m["h"]

    def normal(k, shape, to=dtype):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(to)

    ones = lambda n: jnp.ones((n,), dtype)
    gain = lambda k, n: jax.random.uniform(
        k, (n,), jnp.float32, *LATENT_GAIN).astype(dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for i in range(m["layers"]):
        k = jax.random.split(jax.random.fold_in(k_layers, i), 16)
        w = {"mixer_norm_g": ones(d), "mlp_norm_g": ones(d),
             "q_a_w": normal(k[0], (m["q_rank"], d)),
             "q_a_norm_g": gain(k[1], m["q_rank"]),
             "q_b_w": normal(k[2], (h * (m["nope"] + m["rope"]),
                                    m["q_rank"])),
             "kv_a_w": normal(k[3], (m["kv_rank"] + m["rope"], d)),
             "kv_a_norm_g": gain(k[4], m["kv_rank"]),
             "kv_b_w": normal(k[5], (h * (m["nope"] + m["v"]),
                                     m["kv_rank"])),
             "o_w": normal(k[6], (d, h * m["v"]))}
        if i < m["dense"]:
            w.update({"gate_w": normal(k[7], (m["mlp"], d)),
                      "up_w": normal(k[8], (m["mlp"], d)),
                      "down_w": normal(k[9], (d, m["mlp"]))})
        else:
            e, f, s = m["held"], m["expert"], m["shared"] * m["expert"]
            w.update({
                "router_w": normal(k[7], (m["router"], d), jnp.float32),
                "select_bias": BIAS_STD * jax.random.normal(
                    k[8], (m["router"],), jnp.float32),
                "experts_gate_w": normal(k[9], (e, f, d)),
                "experts_up_w": normal(k[10], (e, f, d)),
                "experts_down_w": normal(k[11], (e, d, f)),
                "shared_gate_w": normal(k[12], (s, d)),
                "shared_up_w": normal(k[13], (s, d)),
                "shared_down_w": normal(k[14], (d, s))})
        layers.append(w)
    return {"embed": normal(k_embed, (m["vocab"], d)),
            "head_w": normal(k_head, (m["vocab"], d)),
            "norm_f_g": ones(d), "layers": layers}


def weights(config, seed):
    """The benchmark's seeded weights in its own layout: the tree ``build``
    loads into the program and the plain reference reads. The router's
    columns and the selection bias cover ALL ``router_experts``; the expert
    stacks hold ``experts_held`` only (every share draws its own: nothing
    here stands for another chip's experts)."""
    z = config["sizes"]
    return _weights(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                    tuple(sorted(_dims(z).items())),
                    jnp.dtype(config["assumed"]["weights_dtype"]),
                    float(z.get("initializer_range", 0.02)))


def cache_geometry(config):
    """What the driver sizes the page pool from: every layer holds one row
    of ``kv_lora_rank + qk_rope_head_dim`` elements a token, which the
    program's leaf holds at whole 128-lane tiles (576 -> 640: what a TPU
    pads the minor dimension to whatever the leaf says; the compile
    rehearsal for a v5e, PERF.md, PR 48); no lane holds anything whatever
    its length."""
    z, e = config["sizes"], config["engine"]
    item = jnp.dtype(config["assumed"]["weights_dtype"]).itemsize
    return {"max_positions": int(z["max_position_embeddings"]),
            "page_device_bytes": int(e["page_size"]) * counts.layers(z)
            * counts.row_device_elems(z) * item,
            "fixed_device_bytes_per_lane": 0}


def layer_tree(b):
    """One layer, the benchmark's layout -> ``HybridBlock.params_dict()``'s
    (a relabelling: no matrix is fused, none is copied)."""
    p = lambda a: {"~params": {"weight": a}}
    blk = {"mixer_norm": p(b["mixer_norm_g"]), "mlp_norm": p(b["mlp_norm_g"]),
           "mixer": {"q_a": p(b["q_a_w"]), "q_a_norm": p(b["q_a_norm_g"]),
                     "q_b": p(b["q_b_w"]), "kv_a": p(b["kv_a_w"]),
                     "kv_a_norm": p(b["kv_a_norm_g"]),
                     "kv_b": p(b["kv_b_w"]), "out_proj": p(b["o_w"])}}
    if "router_w" in b:
        blk["mlp"] = {
            "~params": {"router": b["router_w"],
                        "select_bias": b["select_bias"],
                        "w_gate": b["experts_gate_w"],
                        "w_up": b["experts_up_w"],
                        "w_down": b["experts_down_w"]},
            "shared": {"gate": p(b["shared_gate_w"]),
                       "up": p(b["shared_up_w"]),
                       "down": p(b["shared_down_w"])}}
    else:
        blk["mlp"] = {"gate": p(b["gate_w"]), "up": p(b["up_w"]),
                      "down": p(b["down_w"])}
    return blk


def program_tree(w):
    """The benchmark's layout -> ``HybridDecoderLM.params_dict()``'s."""
    p = lambda a: {"~params": {"weight": a}}
    tree = {"~params": {"tok_embed": w["embed"]},
            "norm_f": p(w["norm_f_g"]), "head": p(w["head_w"])}
    for i, layer in enumerate(w["layers"]):
        tree[f"block{i}"] = layer_tree(layer)
    return tree


def model_shapes(config):
    """The program's model for this configuration, as shapes."""
    from benchmark.harness import BenchmarkError
    from bigdl_tpu.models import hybrid
    from bigdl_tpu.nn.module import abstract_init

    if not hasattr(hybrid, "LATENT"):
        raise BenchmarkError(
            "this program's HybridDecoderLM has no latent-attention layers "
            "and no routed experts: it cannot run joyai-llm-flash")
    z, m = config["sizes"], _dims(config["sizes"])
    if z.get("rope_scaling") is not None or not z.get("rope_interleave"):
        raise ValueError("the program rotates interleaved pairs, unscaled")
    if (int(z["n_group"]), int(z["topk_group"])) != (1, 1):
        raise ValueError("the program's router chooses over one group")
    if (z["scoring_func"], z["topk_method"], bool(z["norm_topk_prob"])) != (
            "sigmoid", "noaux_tc", True):
        raise ValueError("the program's router scores by sigmoid, chooses "
                         "with a selection bias and normalises the gates")
    first = int(z["layers_held"][0])
    model = abstract_init(lambda: hybrid.HybridDecoderLM(
        m["vocab"], m["d"], m["h"], [hybrid.LATENT] * m["layers"], m["mlp"],
        int(z["max_position_embeddings"]), eps=float(z["rms_norm_eps"]),
        rope_theta=float(z["rope_theta"]), block_style="pre_norm",
        latent={"q_lora_rank": m["q_rank"], "kv_lora_rank": m["kv_rank"],
                "qk_nope_head_dim": m["nope"], "qk_rope_head_dim": m["rope"],
                "v_head_dim": m["v"]},
        experts={"expert_dim": m["expert"], "n_routed": m["router"],
                 "top_k": int(z["num_experts_per_tok"]),
                 "held": tuple(int(a) for a in z["experts_held"]),
                 "n_shared": m["shared"],
                 "scaling": float(z["routed_scaling_factor"]),
                 "first_dense": max(
                     0, int(z["first_k_dense_replace"]) - first)}))
    model.evaluate()
    return model


def build(config, seed):
    model = model_shapes(config)
    tree = program_tree(weights(config, seed))
    have = model.params_dict()
    if jax.tree.structure(tree) != jax.tree.structure(have) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(tree),
                                               jax.tree.leaves(have))):
        raise ValueError("HybridDecoderLM's parameter tree is not the one "
                         "benchmark/models/joyai_llm_flash.py maps to")
    model.load_params_dict(tree)
    # the deployment's choice (the configuration's "engine" block): the
    # driver hands the engine a fixed list of arguments, so the model
    # carries this one to it
    model.donate_at_prefill_end = bool(
        config["engine"].get("donate_at_prefill_end", False))
    return model
