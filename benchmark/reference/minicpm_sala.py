"""A hybrid decoder of lightning-attention layers and block-sparse softmax
attention layers (the MiniCPM-SALA family; Lightning Attention-2, Qin et
al., arXiv:2401.04658; the sparse layer after MiniCPM4's, arXiv:2506.07900)
in plain float32 jax.numpy: the recurrence TOKEN BY TOKEN (a ``lax.scan``
over time), the sparse layer's selection as a MASK over dense scores, no
cache, no pages, no chunks, no kernels; matmuls at precision "highest".
Imports nothing of the program; its weights come from
``benchmark/models/minicpm_sala.py`` in the benchmark's own layout (one
matrix a projection).

Block (pre-norm, muP; L the PUBLISHED depth whatever is held)::

    x0 = scale_emb * E[id]
    h   = x + (scale_depth / sqrt(L)) * mixer(rmsnorm(x))
    out = h + (scale_depth / sqrt(L)) * W_down(silu(W_gate n) * W_up n),  n = rmsnorm(h)
    logits = W_head (rmsnorm(x_last) / (hidden_size / dim_model_base))

``lightning-attn``, per head (d wide, decay lam_h = exp(-2^(-8 h / H)))::

    q, k <- rmsnorm_head(W_q n), rmsnorm_head(W_k n), rotated at the position
    S_t = lam_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t / sqrt(d)
    y = W_o (rmsnorm(o) * sigmoid(W_g n))            (the norm over all heads)

``minicpm4``, per KV group (q, k RMS-normed per head, no rotation), a query
at position t:

1. compressed keys C_j = mean(K[stride j : stride j + kernel]) for every
   span that ends at or before t;
2. per head p_j = softmax_j(q . C_j / sqrt(d)) over the visible j, summed
   over the group's heads;
3. block b's score: the highest p_j of the spans that overlap the block;
4. the first ``init_blocks`` blocks and the blocks that hold the last
   ``window_size`` tokens are taken, then the highest-scored until ``topk``
   are taken in all (ties to the earlier block);
5. causal softmax at 1 / sqrt(d) over the tokens s <= t of the blocks taken;
6. a query at t < ``dense_len`` attends to every s <= t;

``y = W_o (o * sigmoid(W_g n))``. One row at a time, weights cast a layer at
a time, queries, the MLP and the head in blocks, so that a row of 32768
positions fits beside the weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256        # queries attended at once
ROW_BLOCK = 4096     # positions through the MLP, a projection or the head at once
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _by_rows(fn, x):
    """``fn`` over ``x`` (T, ...) in blocks of ROW_BLOCK positions."""
    return jnp.concatenate([fn(x[a:a + ROW_BLOCK])
                            for a in range(0, x.shape[0], ROW_BLOCK)])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mlp(h, w, eps, scale):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        n = _rms(h, w["mlp_norm_g"], eps)
        return h + scale * (
            (jax.nn.silu(n @ w["gate_w"].T) * (n @ w["up_w"].T))
            @ w["down_w"].T)


def _rotate(x, positions, theta):
    """(T, H, D), interleaved feature pairs rotated (RoFormer)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _lightning_tokens(x, w, state, t0, heads, d, theta, eps, scale,
                      state_dtype):
    """``x`` (T, D): tokens ``t0 ..`` of one row, ``state`` (H, d, d) the
    state before them -> (``x + scale * mixer(rmsnorm(x))``, the state after
    them). ``state_dtype`` is float32; the precision control passes bfloat16
    (the state rounded after every token)."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        t = x.shape[0]
        n = _rms(x, w["mixer_norm_g"], eps)
        q = _rms((n @ w["q_w"].T).reshape(t, heads, d), w["q_norm_g"], eps)
        k = _rms((n @ w["k_w"].T).reshape(t, heads, d), w["k_norm_g"], eps)
        v = (n @ w["v_w"].T).reshape(t, heads, d)
        if theta is not None:
            pos = t0 + jnp.arange(t)
            q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, heads + 1) / heads)))  # (H,)

        def token(s, xs):
            q_t, k_t, v_t = xs
            s = lam[:, None, None] * s.astype(jnp.float32) \
                + jnp.einsum("hk,hv->hkv", k_t, v_t)
            return s.astype(state_dtype), jnp.einsum("hkv,hk->hv", s, q_t)

        state, o = jax.lax.scan(token, state, (q, k, v))
        o = (o / jnp.sqrt(jnp.float32(d))).reshape(t, heads * d)
        y = _rms(o, w["o_norm_g"], eps) * jax.nn.sigmoid(n @ w["g_w"].T)
        return x + scale * (y @ w["o_w"].T), state


def _lightning_mixer(x, w, heads, d, theta, eps, scale, state_dtype):
    """One row ``x`` (T, D) token by token, ROW_BLOCK tokens a call (the
    state carried from call to call), so that a long row's projections fit."""
    state = jnp.zeros((heads, d, d), state_dtype)
    out = []
    for a in range(0, x.shape[0], ROW_BLOCK):
        y, state = _lightning_tokens(x[a:a + ROW_BLOCK], w, state, a, heads,
                                     d, theta, eps, scale, state_dtype)
        out.append(y)
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _sparse_qkv(x, w, heads, groups, d, eps):
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        t = x.shape[0]
        n = _rms(x, w["mixer_norm_g"], eps)
        q = _rms((n @ w["q_w"].T).reshape(t, heads, d), w["q_norm_g"], eps)
        k = _rms((n @ w["k_w"].T).reshape(t, groups, d), w["k_norm_g"], eps)
        v = (n @ w["v_w"].T).reshape(t, groups, d)
        return q, k, v, jax.nn.sigmoid(n @ w["g_w"].T)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _compressed_keys(k, n_spans, kernel, stride):
    """``k`` (T, G, D) -> (J, G, D): span j is the mean of K[stride j :
    stride j + kernel]."""
    at = stride * jnp.arange(n_spans)[:, None] + jnp.arange(kernel)[None]
    return jnp.mean(k[at], axis=1)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _sparse_queries(q, k, v, c, t0, z, rule, select_dtype=None):
    """Queries ``q`` (Qb, H, D) at positions ``t0 ..`` over the row's keys
    ``k``, ``v`` (T, G, D) and compressed keys ``c`` (J, G, D) -> (Qb, H * D)
    and, for the check's agreement count, the blocks taken (G, Qb, NB).
    ``z`` is the sparse sizes as a sorted tuple of pairs; ``rule`` is
    "select" (steps 1-6), "dense" (every position attends to all: a
    control) or "forced" (no top-k: the first blocks and the window only:
    a control). ``select_dtype`` (the agreement reading alone): the
    scoring step's operands rounded to it first."""
    z = dict(z)
    kernel, stride, block = z["kernel_size"], z["kernel_stride"], z["block_size"]
    with jax.default_matmul_precision("highest"):
        qb, heads, d = q.shape
        t, groups, _ = k.shape
        n_spans = c.shape[0]
        nb = -(-t // block)
        pos = t0 + jnp.arange(qb)                              # (Qb,)
        qg = q.reshape(qb, groups, heads // groups, d)
        root = jnp.sqrt(jnp.float32(d))
        # 1-2: the visible spans' share of each head's softmax, summed
        lo = (lambda a: a) if select_dtype is None else (
            lambda a: a.astype(select_dtype).astype(jnp.float32))
        s = jnp.einsum("qgrd,jgd->grqj", lo(qg), lo(c)) / root
        ends = stride * jnp.arange(n_spans) + kernel - 1
        s = jnp.where((ends[None, :] <= pos[:, None])[None, None], s, -jnp.inf)
        top = jnp.max(s, -1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
        den = jnp.sum(e, -1, keepdims=True)
        share = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=1)  # (G, Qb, J)
        # 3: a block's score over the spans that overlap it (a span not
        # seen has no share)
        per = block // stride
        score = jnp.zeros((groups, qb, nb), jnp.float32)
        for off in range(1 - kernel // stride, per):
            j = per * jnp.arange(nb) + off
            there = (j >= 0) & (j < n_spans)
            score = jnp.maximum(score, jnp.where(
                there[None, None], share[:, :, jnp.clip(j, 0, n_spans - 1)],
                0.0))
        # 4: forced blocks, then the best of the rest up to topk
        blk = jnp.arange(nb)
        own = pos // block
        visible = blk[None] <= own[:, None]                    # (Qb, NB)
        window = jnp.maximum(pos - z["window_size"] + 1, 0) // block
        forced = (blk[None] < z["init_blocks"]) | (blk[None] >= window[:, None])
        key = jnp.where(visible[None], jnp.where(forced[None], jnp.inf, score),
                        -jnp.inf)
        order = jnp.argsort(-key, axis=-1)            # stable: ties keep order
        rank = jnp.argsort(order, axis=-1)
        taken = (rank < z["topk"]) & visible[None]
        if rule == "forced":
            taken = forced[None] & visible[None]
        # 6: under dense_len a query attends to everything
        everything = (pos < z["dense_len"])[None, :, None] | (rule == "dense")
        taken = jnp.where(everything, visible[None], taken)    # (G, Qb, NB)
        # 5: causal softmax over the tokens of the blocks taken
        at = jnp.arange(t)
        ok = taken[:, :, at // block] & (at[None] <= pos[:, None])[None]
        s = jnp.einsum("qgrd,kgd->grqk", qg, k) / root
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf), -1)
        o = jnp.einsum("grqk,kgd->qgrd", p, v)
        return o.reshape(qb, heads * d), taken


@functools.partial(jax.jit, static_argnums=(4,))
def _sparse_out(x, o, gate, o_w, scale):
    with jax.default_matmul_precision("highest"):
        return x + scale * ((o * gate) @ o_w.astype(jnp.float32).T)


def _sparse_mixer(x, w, heads, groups, d, eps, scale, z, rule, taken_out,
                  select_dtype=None):
    t = x.shape[0]
    q, k, v, gate = _sparse_qkv(x, w, heads, groups, d, eps)
    # at least one row (a span past the end is never seen)
    n_spans = max(1, (t - z["kernel_size"]) // z["kernel_stride"] + 1)
    c = _compressed_keys(
        k if select_dtype is None
        else k.astype(select_dtype).astype(jnp.float32),
        n_spans, z["kernel_size"], z["kernel_stride"])
    zt = tuple(sorted((a, int(b)) for a, b in z.items()
                      if isinstance(b, (int, np.integer))))
    out = []
    for a in range(0, t, Q_BLOCK):
        o, taken = _sparse_queries(q[a:a + Q_BLOCK], k, v, c, a, zt, rule,
                                   select_dtype)
        out.append(o)
        if taken_out is not None:
            taken_out.append(np.asarray(taken))
    return _sparse_out(x, jnp.concatenate(out), gate, w["o_w"], scale)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, g, head_w, eps, divide):
    with jax.default_matmul_precision("highest"):
        return (_rms(x, g.astype(jnp.float32), eps) / divide) \
            @ head_w.astype(jnp.float32).T


def layer_kinds(z):
    """The layers held: the slice of the published ``mixer_types`` that
    ``layers_held`` names."""
    a, b = z["layers_held"]
    kinds = list(z["mixer_types"])[a:b]
    assert len(kinds) == int(z["num_hidden_layers"])
    return kinds


def forward(weights, ids, config, sparse_rule="select",
            state_dtype=jnp.float32, taken=None, select_dtype=None):
    """(rows, time) int ids -> (rows, time, vocab) float32 logits on the
    host. ``weights`` in the layout of ``benchmark.models.minicpm_sala
    .weights``, any floating type: read as float32 values. Of ``config`` (the
    configuration file) ``sizes`` and ``published`` (the depth the residual
    scale goes by) are read.
    For the controls alone: ``sparse_rule`` ("dense": the sparse layers
    attend to everything at every position; "forced": they take the first
    blocks and the window and nothing else) and ``state_dtype`` (bfloat16:
    the lightning state rounded after every token). ``taken``: a list that
    receives, a row, the sparse layers' selections (layers, G, T, NB);
    ``select_dtype``: the selection's operands (q, K before it is compressed)
    rounded to it, as a program that keeps them in bfloat16 scores them."""
    z = config["sizes"]
    if z.get("attn_use_rope"):
        raise ValueError("attn_use_rope: the sparse layers do not rotate")
    sparse = dict(z["sparse_config"])
    heads, groups = int(z["num_attention_heads"]), int(z["num_key_value_heads"])
    d, eps = int(z["head_dim"]), float(z["rms_norm_eps"])
    depth = int(config.get("published", {}).get(
        "num_hidden_layers", z["num_hidden_layers"]))
    scale = float(z["scale_depth"]) / float(np.sqrt(depth))
    theta = float(z["rope_theta"]) if z["lightning_use_rope"] else None
    divide = float(z["hidden_size"]) / float(z["dim_model_base"])
    ids = np.asarray(ids, np.int32)
    out = np.zeros(ids.shape + (int(z["vocab_size"]),), np.float32)
    for r in range(ids.shape[0]):
        x = float(z["scale_emb"]) * jnp.take(
            weights["embed"], jnp.asarray(ids[r]), axis=0).astype(jnp.float32)
        row_taken = []
        for kind, w in zip(layer_kinds(z), weights["layers"]):
            if kind == LIGHTNING:
                x = _lightning_mixer(
                    x, w, int(z["lightning_nh"]), int(z["lightning_head_dim"]),
                    theta, eps, scale, jnp.dtype(state_dtype))
            else:
                got = [] if taken is not None else None
                x = _sparse_mixer(x, w, heads, groups, d, eps, scale, sparse,
                                  sparse_rule, got, select_dtype)
                if got is not None:
                    row_taken.append(np.concatenate(got, axis=1))
            x = _by_rows(lambda h: _mlp(h, w, eps, scale), x)
        if taken is not None:
            taken.append(np.stack(row_taken))
        for a in range(0, ids.shape[1], ROW_BLOCK):
            out[r, a:a + ROW_BLOCK] = np.asarray(_head(
                x[a:a + ROW_BLOCK], weights["norm_f_g"], weights["head_w"],
                eps, divide))
    return out
