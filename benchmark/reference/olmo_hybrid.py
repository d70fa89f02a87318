"""A hybrid decoder of gated delta-rule layers and full-attention layers
(the Olmo-Hybrid family; the delta rule as in Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464) in plain float32 jax.numpy: the recurrence
TOKEN BY TOKEN (a ``lax.scan`` over time), softmax attention over the
whole row, no cache, no chunks, no kernels; matmuls at precision
"highest". Imports nothing of the program; its weights come from
``benchmark/models/olmo_hybrid.py`` in the benchmark's own layout (one
matrix a projection, convolution taps as (channels, taps)).

Per token x and head, linear layer::

    q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
    q <- q / |q| / sqrt(dk);  k <- k / |k|
    beta = 2 sigmoid(w_b . x);  alpha = exp(-exp(A_log) softplus(w_a . x + dt_bias))
    S <- alpha S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
    y = W_o (rmsnorm(o) * g_norm * silu(W_g x))

Full layer: RMSNorm over the whole q and the whole k projection, heads
split after, causal softmax attention, no rotation unless the
configuration gives ``rope_theta``. Block: ``h = x + rmsnorm(mixer(x))``,
``out = h + rmsnorm(W_down(silu(W_gate h) * W_up h))``. Final RMSNorm,
untied head. One row at a time, queries and the head in blocks, so that a
row of 4096 positions fits beside the weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512        # queries attended at once
HEAD_BLOCK = 1024    # positions whose logits are computed at once


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _conv(x, taps):
    """Causal depthwise convolution over time: ``x`` (T, C), ``taps``
    (C, K); tap K-1 multiplies the present input."""
    k = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + x.shape[0]] * taps[:, j] for j in range(k))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _mlp(h, w, eps):
    y = (jax.nn.silu(h @ w["gate_w"].T) * (h @ w["up_w"].T)) @ w["down_w"].T
    return h + _rms(y, w["mlp_norm_g"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _linear_layer(x, w, heads, dk, dv, neg_eigval, eps, state_dtype,
                  stale=None):
    """``x`` (T, D) one row. ``state_dtype`` is float32; the precision
    control passes bfloat16 (the state rounded after every token).
    ``stale`` (T,) bool, the fault control's alone: the tokens whose step
    reads S and leaves it as it was."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        t = x.shape[0]
        if stale is None:
            stale = jnp.zeros((t,), bool)
        q = jax.nn.silu(_conv(x @ w["q_w"].T, w["conv_q"])).reshape(t, heads, dk)
        k = jax.nn.silu(_conv(x @ w["k_w"].T, w["conv_k"])).reshape(t, heads, dk)
        v = jax.nn.silu(_conv(x @ w["v_w"].T, w["conv_v"])).reshape(t, heads, dv)
        q, k = _unit(q) / jnp.sqrt(jnp.float32(dk)), _unit(k)
        beta = jax.nn.sigmoid(x @ w["b_w"].T) * (2.0 if neg_eigval else 1.0)
        alpha = jnp.exp(-jnp.exp(w["A_log"])
                        * jax.nn.softplus(x @ w["a_w"].T + w["dt_bias"]))

        def token(s, xs):
            q_t, k_t, v_t, a_t, b_t, stale_t = xs
            old = s.astype(jnp.float32)
            s = old * a_t[:, None, None]
            u = jnp.einsum("hkv,hk->hv", s, k_t)
            s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - u))
            s = jnp.where(stale_t, old, s)
            return s.astype(state_dtype), jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), state_dtype),
                            (q, k, v, alpha, beta, stale))
        gate = jax.nn.silu(x @ w["g_w"].T).reshape(t, heads, dv)
        y = (_rms(o, w["o_norm_g"], eps) * gate).reshape(t, heads * dv)
        h = x + _rms(y @ w["o_w"].T, w["mixer_norm_g"], eps)
        return _mlp(h, w, eps)


def _rotate(x, positions, theta):
    """(T, H, D), interleaved feature pairs rotated (RoFormer)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _full_layer(x, w, heads, kv_heads, eps, theta):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        t, d_model = x.shape
        d = d_model // heads
        q = _rms(x @ w["q_w"].T, w["q_norm_g"], eps).reshape(t, heads, d)
        k = _rms(x @ w["k_w"].T, w["k_norm_g"], eps).reshape(t, kv_heads, d)
        v = (x @ w["v_w"].T).reshape(t, kv_heads, d)
        if theta is not None:
            pos = jnp.arange(t)
            q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        out = []
        for a in range(0, t, Q_BLOCK):
            s = jnp.einsum("qhd,khd->hqk", q[a:a + Q_BLOCK], k) \
                / jnp.sqrt(jnp.float32(d))
            causal = (jnp.arange(t)[None, :]
                      <= jnp.arange(a, min(a + Q_BLOCK, t))[:, None])
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            out.append(jnp.einsum("hqk,khd->qhd", p, v))
        y = jnp.concatenate(out).reshape(t, d_model)
        h = x + _rms(y @ w["o_w"].T, w["mixer_norm_g"], eps)
        return _mlp(h, w, eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, g, head_w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) \
            @ head_w.astype(jnp.float32).T


def forward(weights, ids, config, state_dtype=jnp.float32, stale=None):
    """(rows, time) int ids -> (rows, time, vocab) float32 logits on the
    host. ``weights`` in the layout of ``benchmark.models.olmo_hybrid
    .weights``, any floating type: read as float32 values. Of ``config``
    (the configuration file) the ``sizes`` are read. ``stale`` (rows, time)
    bool, for the fault control alone: the tokens at which every linear
    layer leaves its recurrent state as it was."""
    z = config["sizes"]
    heads, kv_heads = int(z["num_attention_heads"]), int(z["num_key_value_heads"])
    eps = float(z["rms_norm_eps"])
    theta = z["rope_parameters"]["rope_theta"]
    theta = None if theta is None else float(theta)
    lin = (int(z["linear_num_value_heads"]), int(z["linear_key_head_dim"]),
           int(z["linear_value_head_dim"]), bool(z["linear_allow_neg_eigval"]))
    ids = np.asarray(ids, np.int32)
    out = np.zeros(ids.shape + (int(z["vocab_size"]),), np.float32)
    for r in range(ids.shape[0]):
        x = jnp.take(weights["embed"], jnp.asarray(ids[r]), axis=0) \
            .astype(jnp.float32)
        kinds = z["layer_types"][:int(z["num_hidden_layers"])]
        for kind, w in zip(kinds, weights["layers"]):
            if kind == "linear_attention":
                x = _linear_layer(x, w, *lin, eps, jnp.dtype(state_dtype),
                                  None if stale is None
                                  else jnp.asarray(stale[r]))
            else:
                x = _full_layer(x, w, heads, kv_heads, eps, theta)
        for a in range(0, ids.shape[1], HEAD_BLOCK):
            out[r, a:a + HEAD_BLOCK] = np.asarray(_head(
                x[a:a + HEAD_BLOCK], weights["norm_f_g"], weights["head_w"],
                eps))
    return out
