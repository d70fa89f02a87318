"""Olmo-Hybrid configurations on the program: ``HybridDecoderLM`` built as
shapes from the configuration file's sizes (no float32 initialisation ever
reaches the device) and loaded with the benchmark's seeded weights in the
served dtype, and the served model's own logits over given rows (the
precision check)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import olmo_hybrid_counts as counts

REFERENCE = "olmo_hybrid"
LINEAR = "linear_attention"

# memory_analysis of the compile rehearsal for a v5e (PERF.md, PR 34): the
# lanes' S leaves (lanes, 30, 96, 192) float32 pad their minor 192 to 256
# lanes of the tile and the 3 convolution rows to 4: 620.4 MB for 465.3
LANE_DEVICE_FACTOR = 4.0 / 3.0

# snapshots of the lane state the engine keeps a lane (it derives the
# store as this multiple of its lanes; tests/test_lane_state.py holds
# the two together)
SNAPSHOTS_PER_LANE = 2


def layer_kinds(z):
    """The layers held here: the first ``num_hidden_layers`` of the
    published ``layer_types``."""
    return tuple(counts.layer_kinds(z))


def _dims(z):
    d, h = int(z["hidden_size"]), int(z["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(z["num_key_value_heads"]),
            "mlp": int(z["intermediate_size"]),
            "lh": int(z["linear_num_value_heads"]),
            "dk": int(z["linear_key_head_dim"]),
            "dv": int(z["linear_value_head_dim"]),
            "taps": int(z["linear_conv_kernel_dim"]),
            "vocab": int(z["vocab_size"]), "head_dim": d // h}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _weights(key, dims, layer_types, dtype, std):
    """The family's initialisation as ``assumed`` states it: N(0, std)
    matrices, unit gains, convolution taps uniform in +-1/sqrt(taps),
    ``A`` uniform in (0, 16) and the step log-uniform in (1e-3, 1e-1)
    through the inverse of softplus (the delta-rule reference
    implementation's), so that alpha spans (0, 1) across heads."""
    m = dict(dims)
    d, h, lh, dk, dv = m["d"], m["h"], m["lh"], m["dk"], m["dv"]

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for i, kind in enumerate(layer_types):
        k = jax.random.split(jax.random.fold_in(k_layers, i), 16)
        w = {"mixer_norm_g": ones(d), "mlp_norm_g": ones(d),
             "gate_w": normal(k[0], (m["mlp"], d)),
             "up_w": normal(k[1], (m["mlp"], d)),
             "down_w": normal(k[2], (d, m["mlp"]))}
        if kind == LINEAR:
            bound = m["taps"] ** -0.5
            taps = lambda kk, c: jax.random.uniform(
                kk, (c, m["taps"]), jnp.float32, -bound, bound).astype(dtype)
            dt = jnp.exp(jax.random.uniform(
                k[13], (lh,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            w.update({
                "q_w": normal(k[3], (lh * dk, d)),
                "k_w": normal(k[4], (lh * dk, d)),
                "v_w": normal(k[5], (lh * dv, d)),
                "a_w": normal(k[6], (lh, d)), "b_w": normal(k[7], (lh, d)),
                "g_w": normal(k[8], (lh * dv, d)),
                "o_w": normal(k[9], (d, lh * dv)),
                "conv_q": taps(k[10], lh * dk), "conv_k": taps(k[11], lh * dk),
                "conv_v": taps(k[12], lh * dv),
                "A_log": jnp.log(jax.random.uniform(
                    k[14], (lh,), jnp.float32, 1e-3, 16.0)).astype(dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "o_norm_g": ones(dv)})
        else:
            kv = m["kv"] * m["head_dim"]
            w.update({
                "q_w": normal(k[3], (d, d)), "k_w": normal(k[4], (kv, d)),
                "v_w": normal(k[5], (kv, d)), "o_w": normal(k[6], (d, d)),
                "q_norm_g": ones(d), "k_norm_g": ones(kv)})
        layers.append(w)
    return {"embed": normal(k_embed, (m["vocab"], d)),
            "head_w": normal(k_head, (m["vocab"], d)),
            "norm_f_g": ones(d), "layers": layers}


def weights(config, seed):
    """The benchmark's seeded weights in its own layout: the tree ``build``
    loads into the program and the plain reference reads."""
    z = config["sizes"]
    return _weights(jax.random.PRNGKey(int(seed) % (2 ** 31)),
                    tuple(sorted(_dims(z).items())), layer_kinds(z),
                    jnp.dtype(config["assumed"]["weights_dtype"]),
                    float(z.get("initializer_range", 0.02)))


def lane_state_bytes(config):
    """One lane's recurrent state over every linear layer: S in float32
    and the convolution's last inputs in the weights' dtype."""
    return counts.lane_state_bytes(
        config["sizes"],
        jnp.dtype(config["assumed"]["weights_dtype"]).itemsize)


def cache_geometry(config):
    """What the driver sizes the page pool from: only the full-attention
    layers hold K and V pages (their minor dimension, heads x head size,
    is whole 128-lane tiles: a page takes its logical bytes); every lane
    holds its recurrent state, its share of the scratch lane and its
    share of the engine's snapshot store, whatever its length."""
    z, e = config["sizes"], config["engine"]
    item = jnp.dtype(config["assumed"]["weights_dtype"]).itemsize
    page = int(e["page_size"]) * counts.kv_bytes_per_token(z, item)
    slots = int(e["max_slots"])
    # the lane and its share of the scratch lane in the lanes' padded
    # layout, the snapshots flat at their logical bytes
    per_lane = lane_state_bytes(config) * (
        LANE_DEVICE_FACTOR * (1 + 1.0 / slots) + SNAPSHOTS_PER_LANE)
    return {"max_positions": int(z["max_position_embeddings"]),
            "page_device_bytes": page,
            "fixed_device_bytes_per_lane": int(per_lane)}


def layer_tree(kind, b):
    """One layer, the benchmark's layout -> ``HybridBlock.params_dict()``'s:
    the linear layers' q, k, v projections and taps side by side (taps
    time-major), a then b rows; the full layers' q, k, v fused."""
    p = lambda a: {"~params": {"weight": a}}
    blk = {"mixer_norm": p(b["mixer_norm_g"]), "mlp_norm": p(b["mlp_norm_g"]),
           "mlp": {"gate": p(b["gate_w"]), "up": p(b["up_w"]),
                   "down": p(b["down_w"])}}
    qkv = p(jnp.concatenate([b["q_w"], b["k_w"], b["v_w"]]))
    if kind == LINEAR:
        blk["mixer"] = {
            "~params": {
                "conv_weight": jnp.concatenate(
                    [b["conv_q"], b["conv_k"], b["conv_v"]]).T,
                "A_log": b["A_log"], "dt_bias": b["dt_bias"]},
            "qkv": qkv, "gate": p(b["g_w"]),
            "ab": p(jnp.concatenate([b["a_w"], b["b_w"]])),
            "out_proj": p(b["o_w"]), "o_norm": p(b["o_norm_g"])}
    else:
        blk["mixer"] = {"qkv": qkv, "out_proj": p(b["o_w"]),
                        "q_norm": p(b["q_norm_g"]), "k_norm": p(b["k_norm_g"])}
    return blk


def program_tree(w, layer_types):
    """The benchmark's layout -> ``HybridDecoderLM.params_dict()``'s. Takes
    the layers out of ``w`` one at a time, so the two layouts never both
    hold more than one layer's fused projections."""
    p = lambda a: {"~params": {"weight": a}}
    tree = {"~params": {"tok_embed": w["embed"]},
            "norm_f": p(w["norm_f_g"]), "head": p(w["head_w"])}
    layers = w["layers"]
    for i, kind in enumerate(layer_types):
        tree[f"block{i}"] = layer_tree(kind, layers[i])
        layers[i] = None
    return tree


def build(config, seed):
    from bigdl_tpu.models.hybrid import HybridDecoderLM
    from bigdl_tpu.nn.module import abstract_init

    z, m = config["sizes"], _dims(config["sizes"])
    theta = z["rope_parameters"]["rope_theta"]
    model = abstract_init(lambda: HybridDecoderLM(
        m["vocab"], m["d"], m["h"], layer_kinds(z), m["mlp"],
        int(z["max_position_embeddings"]), num_kv_heads=m["kv"],
        linear_heads=m["lh"], linear_key_dim=m["dk"],
        linear_value_dim=m["dv"], conv_kernel=m["taps"],
        allow_neg_eigval=bool(z["linear_allow_neg_eigval"]),
        eps=float(z["rms_norm_eps"]),
        rope_theta=None if theta is None else float(theta)))
    model.evaluate()
    tree = program_tree(weights(config, seed), layer_kinds(z))
    have = model.params_dict()
    if jax.tree.structure(tree) != jax.tree.structure(have) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(tree),
                                               jax.tree.leaves(have))):
        raise ValueError("HybridDecoderLM's parameter tree is not the one "
                         "benchmark/models/olmo_hybrid.py maps to")
    model.load_params_dict(tree)
    return model


def weight_bytes(model):
    return sum(int(a.nbytes) for a in jax.tree.leaves(model.params_dict()))


def chunk_logits_fn(model):
    """(params, buffers, ids, pool, tables, pos0, lanes) -> (logits at
    every position of the chunk, pool): the model's paged prefill pass."""
    from bigdl_tpu.nn.module import bind

    def chunk_logits(p, bufs, ids, pool, tables, pos0, lanes):
        with bind(model, p, bufs, False, None):
            return model.verify_chunk_paged(ids, pool, tables, pos0,
                                            lanes=lanes)

    return chunk_logits


def paged_logits(model, kv_dtype, config, rows):
    """Logits of the SERVED model over ``rows`` (n, time) at every position,
    float32 on the host: the rows go through the model's paged prefill pass
    (``verify_chunk_paged``, the engine's chunk program with the head at
    every position) in the engine's own dispatch shape, ``prefill_rows`` x
    ``prefill_chunk``: K and V through block tables into a page pool of the
    engine's page size, the recurrent state through one lane a dispatch row,
    carried from chunk to chunk as the engine carries it."""
    e = config["engine"]
    page, chunk, width = (int(e[k]) for k in
                          ("page_size", "prefill_chunk", "prefill_rows"))
    rows = np.asarray(rows, np.int32)
    n, t = rows.shape
    table_len = -(-t // page)
    # page 0 is the engine's scratch page; each dispatch row owns its pages
    tables = jnp.asarray(1 + np.arange(width * table_len, dtype=np.int32)
                         .reshape(width, table_len))
    lanes = jnp.arange(width, dtype=jnp.int32)
    params = jax.tree.map(jnp.asarray, model.params_dict())
    buffers = jax.tree.map(jnp.asarray, model.buffers_dict())
    fn = jax.jit(chunk_logits_fn(model), donate_argnums=(3,))
    out = np.zeros((n, t, model.vocab_size), np.float32)
    pool = model.init_page_pool(1 + width * table_len, page,
                                dtype=model.tok_embed.dtype, kv_dtype=kv_dtype,
                                lanes=width)
    for r in range(0, n, width):
        ids = np.zeros((width, t), np.int32)
        ids[:min(width, n - r)] = rows[r:r + width]
        for c in range(0, t, chunk):      # pos0 0 starts each row afresh
            got, pool = fn(params, buffers, jnp.asarray(ids[:, c:c + chunk]),
                           pool, tables, jnp.full((width,), c, jnp.int32),
                           lanes)
            got = np.asarray(got.astype(jnp.float32))
            out[r:r + width, c:c + chunk] = got[:min(width, n - r)]
    return out
