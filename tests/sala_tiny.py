"""A MiniCPM-SALA-shaped hybrid decoder at a test's size, shared by the
tests of the model, of the engine's lane state and of the benchmark cell: a
configuration file's shape under the source's keys (sparse sizes small enough
that sequences of a few hundred tokens cross ``dense_len`` and ``topk``
binds), the adapter's seeded weights with every gain random, and the
program's model loaded with them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from hybrid_tiny import random_gains  # noqa: E402

#: pages of 4 tokens, spans of 8, blocks of 16 (4 pages); the first block
#: and a window of 32 tokens (3 blocks) are always taken, 6 blocks in all;
#: queries under position 64 attend to everything
SPARSE = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 6,
          "init_blocks": 1, "window_size": 32, "dense_len": 64}


def tiny_config(positions=256, layers=4, first=1):
    """Layers ``first .. first + layers`` of a published list of 8: by
    default lightning, sparse, lightning, lightning."""
    kinds = ["minicpm4", "lightning-attn", "minicpm4", "lightning-attn",
             "lightning-attn", "minicpm4", "lightning-attn", "minicpm4"]
    sizes = {"vocab_size": 120, "hidden_size": 32, "intermediate_size": 48,
             "num_hidden_layers": layers, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 8,
             "max_position_embeddings": positions, "rms_norm_eps": 1e-6,
             "mixer_types": kinds, "lightning_nh": 4, "lightning_nkv": 4,
             "lightning_head_dim": 8, "lightning_use_rope": True,
             "attn_use_rope": False, "qk_norm": True, "rope_theta": 10000,
             "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 8,
             "initializer_range": 0.3, "sparse_config": dict(SPARSE),
             "layers_held": [first, first + layers]}
    return {"sizes": sizes, "published": {"num_hidden_layers": 8},
            "adapter": "minicpm_sala", "reference": "minicpm_sala",
            "assumed": {"weights_dtype": "float32", "vocab_real": 120},
            "engine": {"max_slots": 3, "page_size": 4, "prefill_chunk": 16,
                       "prefill_rows": 2, "queue_capacity": 64,
                       "reserve_bytes": 0}}


def built(config, seed):
    """(model, weights): the adapter's ``build`` with random gains."""
    from benchmark.models import minicpm_sala as adapter

    w = random_gains(adapter.weights(config, seed), seed)
    model = adapter.build(config, seed)
    copy = dict(w, layers=list(w["layers"]))
    model.load_params_dict(adapter.program_tree(
        copy, adapter.layer_kinds(config["sizes"])))
    return model, w
