"""A JoyAI-LLM-Flash-shaped decoder at a test's size, shared by the tests of
the layers, of the model and of the benchmark cell: a configuration file's
shape under the source's keys (a leading dense layer, then routed layers
whose router is 16 experts wide with 4 of them held here, 4 a token), the
adapter's seeded weights with every gain random, and the program's model
loaded with them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from hybrid_tiny import random_gains  # noqa: E402


def tiny_config(positions=64, layers=3, held=(4, 4), router=16):
    sizes = {"vocab_size": 120, "hidden_size": 32, "intermediate_size": 48,
             "moe_intermediate_size": 16, "num_hidden_layers": layers,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "head_dim": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
             "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
             "v_head_dim": 8, "first_k_dense_replace": 1, "moe_layer_freq": 1,
             "n_routed_experts": held[1], "router_experts": router,
             "experts_held": list(held), "n_shared_experts": 1,
             "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "routed_scaling_factor": 2.5,
             "scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "rope_theta": 10000.0, "rope_interleave": True,
             "rope_scaling": None, "rms_norm_eps": 1e-6,
             "max_position_embeddings": positions,
             "num_nextn_predict_layers": 0, "initializer_range": 0.3,
             "layers_held": [0, layers]}
    return {"sizes": sizes, "published": {"num_hidden_layers": 8},
            "adapter": "joyai_llm_flash", "reference": "joyai_llm_flash",
            "assumed": {"weights_dtype": "float32", "vocab_real": 120},
            "engine": {"max_slots": 3, "page_size": 4, "prefill_chunk": 16,
                       "prefill_rows": 2, "queue_capacity": 64,
                       "reserve_bytes": 0}}


def wide_bias(w, scale=0.2):
    """The selection biases made wide enough that they change a tiny
    router's choices often."""
    for layer in w["layers"]:
        if "select_bias" in layer:
            layer["select_bias"] = layer["select_bias"] * (scale / 0.012)
    return w


def built(config, seed):
    """(model, weights): the adapter's ``build`` with random gains and a
    wide selection bias."""
    from benchmark.models import joyai_llm_flash as adapter

    w = wide_bias(random_gains(adapter.weights(config, seed), seed))
    model = adapter.build(config, seed)
    model.load_params_dict(adapter.program_tree(w))
    return model, w
