"""GPT-2 configurations on the program: ``TransformerLM`` built from the
configuration file's sizes and loaded with the benchmark's seeded weights,
and the served model's own logits over given rows (the precision check)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as bw

REFERENCE = "gpt2"

# memory_analysis of the compile rehearsal on a v5e (PERF.md, PR 23): the
# pool's (pages, heads, 16, 64) bf16 leaves take 9/8 of their logical bytes
POOL_DEVICE_FACTOR = 1.125


def weights(config, seed):
    """The benchmark's seeded weights in its own layout: the tree ``build``
    loads into the program and the plain reference reads."""
    return bw.gpt2_weights(seed, config["sizes"],
                           jnp.dtype(config["assumed"]["weights_dtype"]))


def cache_geometry(config):
    """What the driver sizes the page pool from: every layer holds K and V
    pages of the model's width in bfloat16, and a lane holds nothing but
    pages."""
    z, e = config["sizes"], config["engine"]
    page = int(z["n_layer"]) * 2 * int(e["page_size"]) * int(z["n_embd"]) * 2
    return {"max_positions": int(z["n_positions"]),
            "page_device_bytes": page * POOL_DEVICE_FACTOR,
            "fixed_device_bytes_per_lane": 0}


def program_tree(w):
    """The benchmark's layout -> ``TransformerLM.params_dict()``'s."""
    leaf = lambda weight, bias: {"~params": {"weight": weight, "bias": bias}}
    tree = {"~params": {"tok_embed": w["wte"], "pos_embed": w["wpe"]},
            "ln_f": leaf(w["lnf_g"], w["lnf_b"])}
    for i, b in enumerate(w["blocks"]):
        tree[f"block{i}"] = {
            "ln1": leaf(b["ln1_g"], b["ln1_b"]),
            "attn": {"qkv": leaf(b["qkv_w"], b["qkv_b"]),
                     "out_proj": leaf(b["proj_w"], b["proj_b"])},
            "ln2": leaf(b["ln2_g"], b["ln2_b"]),
            "fc1": leaf(b["fc_w"], b["fc_b"]),
            "fc2": leaf(b["fc2_w"], b["fc2_b"])}
    return tree


def build(config, seed):
    from bigdl_tpu.models.transformer import TransformerLM

    z = config["sizes"]
    model = TransformerLM(int(z["vocab_size"]), embed_dim=int(z["n_embd"]),
                          num_heads=int(z["n_head"]),
                          num_layers=int(z["n_layer"]),
                          max_len=int(z["n_positions"]))
    model.evaluate()
    tree = program_tree(weights(config, seed))
    have = jax.tree.structure(model.params_dict())
    if jax.tree.structure(tree) != have:
        raise ValueError("TransformerLM's parameter tree is not the one "
                         "benchmark/models/gpt2.py maps to")
    model.load_params_dict(tree)
    return model


def weight_bytes(model):
    return sum(int(a.nbytes) for a in jax.tree.leaves(model.params_dict()))


def chunk_logits_fn(model):
    """(params, buffers, ids, pool, tables, pos0) -> (logits at every
    position of the chunk, pool): the model's paged prefill pass."""
    from bigdl_tpu.nn.module import bind

    def chunk_logits(p, bufs, ids, pool, tables, pos0):
        with bind(model, p, bufs, False, None):
            return model.verify_chunk_paged(ids, pool, tables, pos0)

    return chunk_logits


def paged_logits(model, kv_dtype, config, rows):
    """Logits of the SERVED model over ``rows`` (n, time) at every position,
    float32 on the host: the rows go through the model's paged prefill pass
    (``verify_chunk_paged``, the engine's chunk program with the head at
    every position) in the engine's own dispatch shape, ``prefill_rows`` x
    ``prefill_chunk``, through block tables into a page pool of the engine's
    page size and ``kv_dtype``. ``model`` is the object the engine served
    (its int8 clone where the engine quantized it) with its weights as
    served: whatever precision the engine runs, this pass runs."""
    e = config["engine"]
    page, chunk, width = (int(e[k]) for k in
                          ("page_size", "prefill_chunk", "prefill_rows"))
    rows = np.asarray(rows, np.int32)
    n, t = rows.shape
    table_len = -(-t // page)
    # page 0 is the engine's scratch page; each dispatch row owns its pages
    tables = jnp.asarray(1 + np.arange(width * table_len, dtype=np.int32)
                         .reshape(width, table_len))
    pool = model.init_page_pool(1 + width * table_len, page,
                                dtype=model.tok_embed.dtype,
                                kv_dtype=kv_dtype)
    params = jax.tree.map(jnp.asarray, model.params_dict())
    buffers = jax.tree.map(jnp.asarray, model.buffers_dict())

    fn = jax.jit(chunk_logits_fn(model), donate_argnums=(3,))
    out = np.zeros((n, t, int(config["sizes"]["vocab_size"])), np.float32)
    for r in range(0, n, width):
        ids = np.zeros((width, t), np.int32)
        ids[:min(width, n - r)] = rows[r:r + width]
        for c in range(0, t, chunk):
            got, pool = fn(params, buffers, jnp.asarray(ids[:, c:c + chunk]),
                           pool, tables,
                           jnp.full((width,), c, jnp.int32))
            got = np.asarray(got.astype(jnp.float32))
            out[r:r + width, c:c + chunk] = got[:min(width, n - r)]
    return out
