"""A decoder of multi-head latent attention (MLA) layers whose feed-forward
branch is a gated MLP in the leading dense layers and routed experts beside a
shared expert in the rest (the JoyAI-LLM-Flash family, ``model_type``
``joyai_llm_flash``: the DeepSeek-V3 layer equations, arXiv:2412.19437
sections 2.1.1 and 2.1.2, with ``topk_method`` ``noaux_tc`` at one group) in
plain float32 jax.numpy: the EXPANDED attention only (every head's keys and
values made from the latent), dense causal scores by query blocks, no cache,
no pages, no absorbed products, no sorting of tokens by expert; matmuls at
precision "highest". Imports nothing of the program; its weights come from
``benchmark/models/joyai_llm_flash.py`` in the benchmark's own layout (one
matrix a projection, one stack a kind of expert matrix).

Block (pre-norm, ``n``: RMSNorm)::

    h = x + MLA(n(x));   out = h + FF(n(h));   logits = W_head n(x_last)

MLA, a token ``x`` at position ``t`` (``R_t``: rotary over interleaved
feature pairs at ``rope_theta``, no scaling)::

    c_q = n(W_qa x);  [q_nope_h | q_rope_h] = W_qb^h c_q;  q_rope_h <- R_t(q_rope_h)
    [c | k_r] = W_kva x;  c_kv = n(c);  k_rope = R_t(k_r)          (one head, shared)
    [k_nope_h | v_h] = W_kvb^h c_kv
    score_h(t, s) = (q_nope_h . k_nope_h,s + q_rope_h . k_rope_s) / sqrt(nope + rope)
    o_h = sum_{s <= t} softmax_s(score_h) v_h,s;   y = W_o [o_1 .. o_H]

Routed layer (``E(x) = W_down (silu(W_gate x) * W_up x)``)::

    s = sigmoid(W_r x)                    over ALL the router's experts, float32
    chosen = top_k of (s + b)             b: the selection bias; ties to the lower index
    g_i = scaling * s_i / (sum_{j chosen} s_j + 1e-20)     (s, NOT s + b; ALL the chosen)
    FF(x) = sum_{i chosen and held} g_i E_i(x) + E_shared(x)

``experts_held = [first, count]`` names the experts whose weights are here:
this chip's share of an expert-parallel layer. What the absent experts would
add is left out, and that partial result goes on to the next layer.

One row at a time, weights cast a layer (an expert) at a time, queries, the
feed-forward and the head in blocks, so that a row of 16384 positions fits
beside the weights. ``fault`` plants one of ``FAULTS`` (the controls of
``benchmark/tools/joyai_controls.py``); None is the model.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256        # queries attended at once
ROW_BLOCK = 4096     # positions through a projection, the feed-forward or the head at once

#: what a control may plant, each a departure from the equations above
FAULTS = ("bias_ignored",        # the top-k is of s, not of s + b
          "gates_from_biased",   # g_i from s_i + b_i
          "sum_over_held",       # the normalising sum over the HELD chosen only
          "no_scaling",          # routed_scaling_factor dropped
          "no_rope_score",       # the q_rope . k_rope part of the score left out
          "latent_int8")         # a token's cache row rounded to 255 steps


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _by_rows(fn, x):
    """``fn`` over ``x`` (T, ...) in blocks of ROW_BLOCK positions."""
    return jnp.concatenate([fn(x[a:a + ROW_BLOCK])
                            for a in range(0, x.shape[0], ROW_BLOCK)])


def _rotate(x, positions, theta):
    """(T, H, D), interleaved feature pairs rotated (RoFormer)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _int8_rows(x):
    """Each row rounded to 255 steps of its own largest magnitude."""
    step = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
    return jnp.round(x / jnp.where(step > 0, step, 1.0)) * step


def _dims(z):
    return (int(z["num_attention_heads"]), int(z["qk_nope_head_dim"]),
            int(z["qk_rope_head_dim"]), int(z["v_head_dim"]),
            int(z["kv_lora_rank"]))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _projections(x, w, t0, dims, theta, eps, fault):
    """``x`` (T, D), tokens ``t0 ..`` of a row -> the queries (T, H, nope),
    (T, H, rope) and, expanded from the token's latent, every head's keys
    (T, H, nope), the shared rotated key (T, rope) and values (T, H, v)."""
    heads, nope, rope, v_dim, rank = dims
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        t = x.shape[0]
        pos = t0 + jnp.arange(t)
        n = _rms(x, w["mixer_norm_g"], eps)
        c_q = _rms(n @ w["q_a_w"].T, w["q_a_norm_g"], eps)
        q = (c_q @ w["q_b_w"].T).reshape(t, heads, nope + rope)
        q_rope = _rotate(q[..., nope:], pos, theta)
        y = n @ w["kv_a_w"].T
        c_kv = _rms(y[:, :rank], w["kv_a_norm_g"], eps)
        k_rope = _rotate(y[:, None, rank:], pos, theta)[:, 0]
        if fault == "latent_int8":
            row = _int8_rows(jnp.concatenate([c_kv, k_rope], -1))
            c_kv, k_rope = row[:, :rank], row[:, rank:]
        kv = (c_kv @ w["kv_b_w"].T).reshape(t, heads, nope + v_dim)
        return q[..., :nope], q_rope, kv[..., :nope], k_rope, kv[..., nope:]


@functools.partial(jax.jit, static_argnums=(6,))
def _attend(q_nope, q_rope, k_nope, k_rope, v, t0, fault):
    """Queries at ``t0 ..`` over the row's keys: (Qb, H * v)."""
    with jax.default_matmul_precision("highest"):
        qb, _, nope = q_nope.shape
        s = jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        if fault != "no_rope_score":
            s = s + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
        s = s / jnp.sqrt(jnp.float32(nope + q_rope.shape[-1]))
        causal = jnp.arange(k_nope.shape[0])[None] \
            <= (t0 + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(qb, -1)


@jax.jit
def _attention_out(x, o, o_w):
    with jax.default_matmul_precision("highest"):
        return x + o @ o_w.astype(jnp.float32).T


def attention(x, w, z, fault=None):
    """One row ``x`` (T, D) -> ``x + MLA(n(x))``."""
    dims, eps = _dims(z), float(z["rms_norm_eps"])
    theta = float(z["rope_theta"])
    parts = [_projections(x[a:a + ROW_BLOCK], w, a, dims, theta, eps, fault)
             for a in range(0, x.shape[0], ROW_BLOCK)]
    q_nope, q_rope, k_nope, k_rope, v = (jnp.concatenate(p) for p in
                                         zip(*parts))
    o = jnp.concatenate([
        _attend(q_nope[a:a + Q_BLOCK], q_rope[a:a + Q_BLOCK], k_nope, k_rope,
                v, a, fault) for a in range(0, x.shape[0], Q_BLOCK)])
    return _attention_out(x, o, w["o_w"])


def _gated(n, gate_w, up_w, down_w):
    return (jax.nn.silu(n @ gate_w.T) * (n @ up_w.T)) @ down_w.T


def gates(n, router_w, select_bias, top_k, scaling, held=None, fault=None):
    """``n`` (T, D) -> (T, router's experts) float32: ``g_i`` where the
    token chose expert ``i``, 0 elsewhere. ``held`` (first, count) is read
    only by the fault that sums over the held."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(n @ router_w.astype(jnp.float32).T)
    b = select_bias.astype(jnp.float32)
    key = s if fault == "bias_ignored" else s + b
    # a stable sort of the negated keys: ties go to the lower index
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    chosen = rank < top_k
    weight = jnp.where(chosen, s + b if fault == "gates_from_biased" else s,
                       0.0)
    total = weight
    if fault == "sum_over_held":
        first, count = held
        i = jnp.arange(s.shape[-1])
        total = jnp.where((i >= first) & (i < first + count), weight, 0.0)
    weight = weight / (jnp.sum(total, -1, keepdims=True) + 1e-20)
    return weight * (1.0 if fault == "no_scaling" else scaling)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _routed(h, w, eps, top_k, scaling, held, shared, fault):
    with jax.default_matmul_precision("highest"):
        first, count = held
        n = _rms(h, w["mlp_norm_g"].astype(jnp.float32), eps)
        g = gates(n, w["router_w"], w["select_bias"], top_k, scaling, held,
                  fault)

        def one_expert(out, e):
            gate_w, up_w, down_w, g_e = e
            return out + g_e[:, None] * _gated(
                n, gate_w.astype(jnp.float32), up_w.astype(jnp.float32),
                down_w.astype(jnp.float32)), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
            w["experts_gate_w"], w["experts_up_w"], w["experts_down_w"],
            g[:, first:first + count].T))
        if shared:
            out = out + _gated(n, *(w[k].astype(jnp.float32) for k in (
                "shared_gate_w", "shared_up_w", "shared_down_w")))
        return out


def routed_branch(h, w, z, held=None, shared=True, fault=None):
    """``FF(n(h))`` of a routed layer over ``h`` (T, D). The stacks in ``w``
    hold the experts ``held`` = (first, count) of the router's (row ``j`` is
    expert ``first + j``; ``experts_held`` of the sizes if None); the shared
    expert is added if ``shared``."""
    held = tuple(int(a) for a in (held or z["experts_held"]))
    return _routed(h, w, float(z["rms_norm_eps"]),
                   int(z["num_experts_per_tok"]),
                   float(z["routed_scaling_factor"]), held, bool(shared),
                   fault)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(h, w, eps):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        return _gated(_rms(h, w["mlp_norm_g"], eps), w["gate_w"], w["up_w"],
                      w["down_w"])


def feed_forward(h, w, z, fault=None):
    """``h + FF(n(h))``: routed where the layer holds a router."""
    if "router_w" in w:
        return _by_rows(lambda a: a + routed_branch(a, w, z, fault=fault), h)
    return _by_rows(lambda a: a + _dense(a, w, float(z["rms_norm_eps"])), h)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, g, head_w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) \
            @ head_w.astype(jnp.float32).T


def forward(weights, ids, config, fault=None):
    """(rows, time) int ids -> (rows, time, vocab) float32 logits on the
    host. ``weights`` in the layout of ``benchmark.models.joyai_llm_flash
    .weights``, any floating type: read as float32 values. Of ``config`` (the
    configuration file) ``sizes`` is read. ``fault``: one of ``FAULTS``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: expected one of {FAULTS}")
    z = config["sizes"]
    if z.get("rope_scaling") is not None:
        raise ValueError("rope_scaling: the family's published value is null")
    if (int(z.get("n_group", 1)), int(z.get("topk_group", 1))) != (1, 1):
        raise ValueError("n_group / topk_group: one group is written down")
    if not z.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false: the gates are written "
                         "normalised over the chosen")
    eps = float(z["rms_norm_eps"])
    ids = np.asarray(ids, np.int32)
    out = np.zeros(ids.shape + (int(z["vocab_size"]),), np.float32)
    for r in range(ids.shape[0]):
        x = jnp.take(weights["embed"], jnp.asarray(ids[r]),
                     axis=0).astype(jnp.float32)
        for w in weights["layers"]:
            x = feed_forward(attention(x, w, z, fault), w, z, fault)
        for a in range(0, ids.shape[1], ROW_BLOCK):
            out[r, a:a + ROW_BLOCK] = np.asarray(_head(
                x[a:a + ROW_BLOCK], weights["norm_f_g"], weights["head_w"],
                eps))
    return out
