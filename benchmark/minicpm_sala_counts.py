"""The least work the MiniCPM-SALA cut's programs need, from shapes alone
(``sizes`` is the configuration file's: the source's own keys, the sparse
layer's ``sparse_config`` and ``layers_held``): operations and bytes the
algorithm requires, not what a compiler emits. A sparse layer is charged the
tokens its rule SELECTS and the compressed keys it sees; gathers of padded
tables, masked products, the chunked form's extra products and rows of a
ragged dispatch that hold no token count for nothing here, so a share of a
peak computed from these can only read under 100 %."""

import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layer_kinds(z):
    a, b = z["layers_held"]
    return list(z["mixer_types"])[a:b]


def _count(z, kind):
    return sum(k == kind for k in layer_kinds(z))


def mlp_params(z):
    return 3 * int(z["hidden_size"]) * int(z["intermediate_size"])


def lightning_mixer_params(z):
    """q, k, v, gate and output projections (gains are thousands)."""
    return 5 * int(z["hidden_size"]) * int(z["lightning_nh"]) \
        * int(z["lightning_head_dim"])


def sparse_mixer_params(z):
    """q, gate and output projections over all heads, k and v over the KV
    heads."""
    d, hd = int(z["hidden_size"]), int(z["head_dim"])
    return d * hd * (3 * int(z["num_attention_heads"])
                     + 2 * int(z["num_key_value_heads"]))


def block_matmul_params(z):
    """Weights every token multiplies in the blocks (the head apart)."""
    return (_count(z, LIGHTNING) * lightning_mixer_params(z)
            + _count(z, SPARSE) * sparse_mixer_params(z)
            + len(layer_kinds(z)) * mlp_params(z))


def head_params(z):
    return int(z["vocab_size"]) * int(z["hidden_size"])


def kv_row_elems(z):
    """One token's K (or V, or one compressed key) of one sparse layer."""
    return int(z["num_key_value_heads"]) * int(z["head_dim"])


def kv_bytes_per_token(z, itemsize=2):
    """K and V of one cached token over the layers that hold pages."""
    return _count(z, SPARSE) * 2 * kv_row_elems(z) * itemsize


def compressed_bytes_per_token(z, itemsize=2):
    """A token's share of the compressed keys: one a stride and layer."""
    return _count(z, SPARSE) * kv_row_elems(z) * itemsize \
        / int(z["sparse_config"]["kernel_stride"])


def state_elems_per_layer(z):
    return int(z["lightning_nh"]) * int(z["lightning_head_dim"]) ** 2


def lane_state_bytes(z):
    """One lane's recurrent state over every lightning layer, float32."""
    return _count(z, LIGHTNING) * state_elems_per_layer(z) * 4


def attended_tokens(z, positions):
    """Tokens one sparse layer attends for a query at each position: all
    of them under ``dense_len``, then ``topk`` blocks less the part of the
    query's own block that lies ahead of it."""
    c = z["sparse_config"]
    t = np.asarray(positions, np.int64)
    block = int(c["block_size"])
    taken = np.minimum(int(c["topk"]), t // block + 1)
    return np.where(t < int(c["dense_len"]), t + 1,
                    taken * block - (block - 1 - t % block))


def lightning_step(z, rows):
    """The recurrence of ONE layer's decode step over ``rows`` lanes (scope
    ``lightning/step``): decay, the rank-one update and q S are about 5
    operations a state element; S is read and written once."""
    e = state_elems_per_layer(z) * rows
    return 5 * e, 2 * 4 * e


def decode_step(z, rows, attended, cached, itemsize=2):
    """One decode step over ``rows`` live requests whose sparse layers
    attend ``attended`` tokens of the ``cached`` they hold (sums over the
    rows, one layer's): (operations, bytes) at the least: every matmul
    weight read once, the SELECTED tokens' K and V once, the visible
    compressed keys once, each live lane's state read and written once."""
    weights = block_matmul_params(z) + head_params(z)
    sparse, lightning = _count(z, SPARSE), _count(z, LIGHTNING)
    wide = int(z["num_attention_heads"]) * int(z["head_dim"])
    spans = cached / int(z["sparse_config"]["kernel_stride"])
    flops = (2 * weights * rows
             + sparse * (4 * wide * attended + 2 * wide * spans)
             + lightning * lightning_step(z, rows)[0])
    data = (weights * itemsize
            + sparse * kv_row_elems(z) * itemsize * (2 * attended + spans)
            + 2 * lane_state_bytes(z) * rows)
    return flops, data


def prefill_chunk(z, tokens, rows, itemsize=2):
    """One prefill dispatch that advances ``rows`` prompts by ``tokens`` real
    tokens in all: projections and MLP for every token, the head for each
    row's last position, the recurrence, and causal attention INSIDE the
    chunk (the cached tokens before it are not counted: the record does not
    say where a traced chunk stood; a token attends at most ``topk`` blocks,
    4096 tokens, which is 3 % of its matmul operations, so the share reads
    that much low). Bytes: the weights once, each row's state read and
    written."""
    sparse, lightning = _count(z, SPARSE), _count(z, LIGHTNING)
    wide = int(z["num_attention_heads"]) * int(z["head_dim"])
    flops = (2 * block_matmul_params(z) * tokens
             + 2 * head_params(z) * rows
             + lightning * 5 * state_elems_per_layer(z) * tokens
             + 4 * wide * sparse * tokens * (tokens / max(rows, 1)) / 2)
    data = ((block_matmul_params(z) + head_params(z)) * itemsize
            + 2 * lane_state_bytes(z) * rows)
    return flops, data
