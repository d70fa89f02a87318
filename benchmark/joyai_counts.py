"""The least work the JoyAI-LLM-Flash cut's programs need, from shapes alone
(``sizes`` is the configuration file's: the source's own keys, with
``n_routed_experts`` the experts HELD here, ``router_experts`` the router's
published width, ``layers_held`` and ``experts_held``): operations and bytes
the algorithm requires, not what a compiler emits. The decode step is charged
the weights every token uses, the held experts some row TOUCHED, the latent
rows its lanes hold and the absorbed products over them; gathers of whole
tables, expert slots that hold no token, the chunk's expansion of keys and
values it has expanded before and rows of a ragged dispatch that hold no
token count for nothing here, so a share of a peak computed from these can
only read under 100 %."""


def layers(z):
    a, b = z["layers_held"]
    return int(b) - int(a)


def dense_layers(z):
    """Held layers whose feed-forward branch is the gated MLP."""
    a, b = z["layers_held"]
    return max(0, min(int(b), int(z["first_k_dense_replace"])) - int(a))


def routed_layers(z):
    return layers(z) - dense_layers(z)


def attention_params(z):
    """W_qa, W_qb, W_kva, W_kvb, W_o of one layer (gains are thousands)."""
    d, h = int(z["hidden_size"]), int(z["num_attention_heads"])
    nope, rope = int(z["qk_nope_head_dim"]), int(z["qk_rope_head_dim"])
    q_rank, kv_rank = int(z["q_lora_rank"]), int(z["kv_lora_rank"])
    return (d * q_rank + q_rank * h * (nope + rope) + d * (kv_rank + rope)
            + kv_rank * h * (nope + int(z["v_head_dim"]))
            + h * int(z["v_head_dim"]) * d)


def expert_params(z):
    return 3 * int(z["hidden_size"]) * int(z["moe_intermediate_size"])


def router_params(z):
    return int(z["hidden_size"]) * int(z["router_experts"])


def dense_mlp_params(z):
    return 3 * int(z["hidden_size"]) * int(z["intermediate_size"])


def head_params(z):
    return int(z["vocab_size"]) * int(z["hidden_size"])


def every_token_params(z):
    """Weights every token multiplies in the blocks (the head apart): the
    attention, the dense layers' MLP, each routed layer's router and shared
    experts."""
    return (layers(z) * attention_params(z)
            + dense_layers(z) * dense_mlp_params(z)
            + routed_layers(z) * (router_params(z) + int(z["n_shared_experts"])
                                  * expert_params(z)))


def held_expert_params(z):
    """The routed experts held over every routed layer."""
    return routed_layers(z) * int(z["n_routed_experts"]) * expert_params(z)


def weight_params(z):
    """Every matrix held: embedding and head among them."""
    return every_token_params(z) + held_expert_params(z) + 2 * head_params(z)


def row_elems(z):
    """One token's cache row in one layer: the latent and the shared key."""
    return int(z["kv_lora_rank"]) + int(z["qk_rope_head_dim"])


def row_device_elems(z):
    """... as the program's leaf and the device hold it: whole 128-lane
    tiles, zeros behind."""
    return -(-row_elems(z) // 128) * 128


def kv_bytes_per_token(z, itemsize=2):
    return layers(z) * row_elems(z) * itemsize


def absorbed_flops_per_key(z):
    """One decode query over one cached row of one layer: every head's score
    against the row and its values out of the latent."""
    return 2 * int(z["num_attention_heads"]) * (
        row_elems(z) + int(z["kv_lora_rank"]))


def absorb_flops_per_token(z):
    """The two absorbed products of one layer for one token."""
    return 2 * int(z["num_attention_heads"]) * int(z["kv_lora_rank"]) * (
        int(z["qk_nope_head_dim"]) + int(z["v_head_dim"]))


def decode_step(z, rows, assignments, touched, cached, itemsize=2):
    """One decode step over ``rows`` live requests that hold ``cached``
    tokens in all, whose ``assignments`` (summed over the routed layers)
    fell on held experts, ``touched`` of which (layers x experts) some row
    chose: (operations, bytes) at the least: every-token weights and the
    head read once, the TOUCHED experts' weights once, each cached row once
    a layer."""
    always = every_token_params(z) + head_params(z)
    flops = (2 * always * rows + 2 * expert_params(z) * assignments
             + layers(z) * (absorbed_flops_per_key(z) * cached
                            + absorb_flops_per_token(z) * rows))
    data = (always * itemsize + expert_params(z) * touched * itemsize
            + kv_bytes_per_token(z, itemsize) * cached)
    return flops, data


def prefill_chunk(z, tokens, rows, itemsize=2):
    """One prefill dispatch that advances ``rows`` prompts by ``tokens`` real
    tokens in all: every-token weights for each token, ``num_experts_per_tok``
    experts a token at the share held here, the head for each row's last
    position, and causal attention INSIDE the chunk in the expanded form
    (the cached tokens before it, and the expansion of their keys and values,
    are not counted: the record does not say where a traced chunk stood).
    Bytes: every weight held once (at 16 tokens an expert a full chunk
    touches every held expert)."""
    h = int(z["num_attention_heads"])
    wide = h * (int(z["qk_nope_head_dim"]) + int(z["qk_rope_head_dim"])
                + int(z["v_head_dim"]))
    share = int(z["n_routed_experts"]) / int(z["router_experts"])
    flops = (2 * every_token_params(z) * tokens
             + 2 * routed_layers(z) * expert_params(z)
             * int(z["num_experts_per_tok"]) * share * tokens
             + 2 * head_params(z) * rows
             + 2 * wide * layers(z) * tokens * (tokens / max(rows, 1)) / 2)
    data = (every_token_params(z) + held_expert_params(z)
            + head_params(z)) * itemsize
    return flops, data
