"""The least work the hybrid decoder's programs need, from shapes alone
(``sizes`` is the configuration file's, the source's own keys): operations
and bytes the algorithm requires, not what a compiler emits. Gathers of a
lane's whole block table, padded tiles, the chunked form's extra products
and rows of a ragged dispatch that hold no token count for nothing here, so
a share of a peak computed from these can only read under 100 %."""

LINEAR = "linear_attention"


def layer_kinds(z):
    return list(z["layer_types"])[:int(z["num_hidden_layers"])]


def mlp_params(z):
    return 3 * int(z["hidden_size"]) * int(z["intermediate_size"])


def linear_mixer_params(z):
    """q, k, v, gate, a, b and output projections of one delta-rule layer
    (convolution taps, gains, A_log and dt_bias are thousands, not counted)."""
    d, h = int(z["hidden_size"]), int(z["linear_num_value_heads"])
    dk, dv = int(z["linear_key_head_dim"]), int(z["linear_value_head_dim"])
    return d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d


def full_mixer_params(z):
    d = int(z["hidden_size"])
    kv = int(z["num_key_value_heads"]) * (d // int(z["num_attention_heads"]))
    return d * (d + 2 * kv) + d * d


def block_matmul_params(z):
    """Weights every token multiplies in the blocks (the head apart)."""
    kinds = layer_kinds(z)
    lin = sum(k == LINEAR for k in kinds)
    return (lin * linear_mixer_params(z)
            + (len(kinds) - lin) * full_mixer_params(z)
            + len(kinds) * mlp_params(z))


def head_params(z):
    return int(z["vocab_size"]) * int(z["hidden_size"])


def kv_bytes_per_token(z, itemsize=2):
    """K and V of one cached token over the layers that hold pages."""
    d = int(z["hidden_size"])
    kv = int(z["num_key_value_heads"]) * (d // int(z["num_attention_heads"]))
    full = sum(k != LINEAR for k in layer_kinds(z))
    return full * 2 * kv * itemsize


def state_elems_per_layer(z):
    return (int(z["linear_num_value_heads"]) * int(z["linear_key_head_dim"])
            * int(z["linear_value_head_dim"]))


def lane_state_bytes(z, itemsize=2):
    """One lane's recurrent state over every linear layer: S in float32 and
    the convolution's last inputs in the activations' dtype."""
    h = int(z["linear_num_value_heads"])
    conv = h * (2 * int(z["linear_key_head_dim"])
                + int(z["linear_value_head_dim"]))
    layer = state_elems_per_layer(z) * 4 \
        + (int(z["linear_conv_kernel_dim"]) - 1) * conv * itemsize
    return layer * sum(k == LINEAR for k in layer_kinds(z))


def gdn_step(z, rows):
    """The delta-rule core of ONE layer's decode step over ``rows`` lanes
    (scope ``gdn/step``): decay, S^T k, the rank-one update and S^T q are
    about 7 operations a state element; S is read and written once."""
    e = state_elems_per_layer(z) * rows
    return 7 * e, 2 * 4 * e


def gdn_chunk(z, tokens, rows):
    """The delta-rule core of ONE layer over ``tokens`` prefilled tokens in
    ``rows`` rows (scope ``gdn/chunk``), counted as the recurrence needs it
    (7 operations a state element a token; the chunked form spends more, on
    the MXU); each row's S read and written once, q, k, v read in float32."""
    h = int(z["linear_num_value_heads"])
    qkv = h * (2 * int(z["linear_key_head_dim"])
               + int(z["linear_value_head_dim"]))
    return (7 * state_elems_per_layer(z) * tokens,
            2 * 4 * state_elems_per_layer(z) * rows + 4 * qkv * tokens)


def decode_step(z, live_rows, live_tokens, itemsize=2):
    """One decode step over ``live_rows`` requests holding ``live_tokens``
    cached tokens in all: (operations, bytes) it needs at the least: every
    matmul weight read once, each live token's K and V once, each live
    lane's recurrent state read and written once."""
    lin = sum(k == LINEAR for k in layer_kinds(z))
    weights = block_matmul_params(z) + head_params(z)
    d = int(z["hidden_size"])
    full = len(layer_kinds(z)) - lin
    flops = (2 * weights * live_rows + 4 * d * full * live_tokens
             + lin * gdn_step(z, live_rows)[0])
    data = (weights * itemsize + kv_bytes_per_token(z, itemsize) * live_tokens
            + 2 * lane_state_bytes(z, itemsize) * live_rows)
    return flops, data


def prefill_chunk(z, tokens, rows, itemsize=2):
    """One prefill dispatch that advances ``rows`` prompts by ``tokens`` real
    tokens in all: projections and MLP for
    every token, the head for each row's last position, the delta rule, and
    causal attention INSIDE the chunk (the cached tokens before it are not
    counted: the record does not say where a traced chunk stood; at this
    configuration's lengths they are under 2 % of the chunk's operations,
    so the share reads that much low). Bytes: the weights once."""
    lin = sum(k == LINEAR for k in layer_kinds(z))
    full = len(layer_kinds(z)) - lin
    d = int(z["hidden_size"])
    flops = (2 * block_matmul_params(z) * tokens
             + 2 * head_params(z) * rows
             + lin * gdn_chunk(z, tokens, rows)[0]
             + 4 * d * full * tokens * (tokens / max(rows, 1)) / 2)
    data = (block_matmul_params(z) + head_params(z)) * itemsize
    return flops, data
