"""A hybrid decoder at a test's size, shared by the tests of the model, of
the engine's lane state and of the benchmark cell: a configuration file's
shape under the source's keys, the adapter's seeded weights with every gain
random, and the program's model loaded with them."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny_config(theta=None, layers=4, positions=64):
    """A configuration file's shape at a test's size: the source's keys,
    every gain random (``gains``)."""
    kinds = ["linear_attention"] * 3 + ["full_attention"]
    sizes = {"vocab_size": 120, "hidden_size": 32, "intermediate_size": 48,
             "num_hidden_layers": layers, "num_attention_heads": 4,
             "num_key_value_heads": 4, "max_position_embeddings": positions,
             "rms_norm_eps": 1e-6, "layer_types": kinds * 8,
             "linear_num_key_heads": 3, "linear_num_value_heads": 3,
             "linear_key_head_dim": 8, "linear_value_head_dim": 16,
             "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
             "rope_parameters": {"rope_theta": theta},
             "initializer_range": 0.2}
    return {"sizes": sizes, "adapter": "olmo_hybrid",
            "reference": "olmo_hybrid",
            "assumed": {"weights_dtype": "float32", "vocab_real": 120},
            "engine": {"max_slots": 3, "page_size": 4, "prefill_chunk": 8,
                       "prefill_rows": 2, "queue_capacity": 64,
                       "reserve_bytes": 0}}


def random_gains(w, seed):
    """Every unit gain of the adapter's tree made random, so that a gain
    mapped to the wrong norm shows."""
    rng = np.random.default_rng(seed)

    def shake(path, a):
        name = str(path[-1])
        if name.endswith("_g']"):
            return a * jnp.asarray(1 + 0.3 * rng.standard_normal(a.shape),
                                   a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, w)


def built(config, seed, monkeypatch=None):
    """(model, weights): the adapter's ``build`` with random gains."""
    from benchmark.models import olmo_hybrid as adapter

    w = random_gains(adapter.weights(config, seed), seed)
    model = adapter.build(config, seed)
    kinds = adapter.layer_kinds(config["sizes"])
    copy = dict(w, layers=list(w["layers"]))
    model.load_params_dict(adapter.program_tree(copy, kinds))
    return model, w
