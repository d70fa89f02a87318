"""Gated delta-rule linear attention (Yang, Kautz & Hatamizadeh 2024,
"Gated Delta Networks", arXiv:2412.06464) and the gated MLP that sits
beside it in hybrid decoders.

A layer keeps, per sequence, a matrix ``S`` (dk x dv per head, float32)
in place of a KV cache, and the last ``conv - 1`` inputs of a short
causal depthwise convolution. Per token and head::

    S <- alpha * S                              alpha in (0, 1), a scalar
    S <- S + k (beta * (v - S^T k))^T           the delta rule, beta in (0, 2)
    o  = S^T q

Three forms compute it and must agree (tests/test_gated_delta.py):

- :meth:`GatedDeltaNet.forward_step`: one token, ``S`` read and written
  once (decode);
- :meth:`GatedDeltaNet.forward_chunk`: a chunk of tokens from a carried
  state (prefill): the chunk is cut into sub-chunks of ``SUB`` tokens,
  everything inside a sub-chunk is matrix products (the triangular
  system ``(I + A) T = I`` too: :func:`_inverse_unit_lower`), and only
  the sub-chunk-to-sub-chunk state pass is sequential;
- :meth:`GatedDeltaNet.forward`: a whole sequence from a zero state
  (the chunked form over a padded length).

Both served forms take and return the sequence's ``(S, conv tail)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import init as bt_init
from bigdl_tpu.nn.attention import RMSNorm
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.module import Module, scoped

#: tokens whose mutual dependence is resolved by matrix products
SUB = 64
#: the triangular inverse compounds over log2(SUB) levels: its products
#: run at full float32 precision (they are a few percent of the layer)
_EXACT = jax.lax.Precision.HIGHEST
#: precision of the chunked form's other float32 products: three
#: bfloat16 passes on a TPU. One pass (the default) reads a quarter more
#: logit error on the chip (0.053 against 0.041, PERF.md, PR 34); the
#: core is a few percent of the layer's operations
_CORE = jax.lax.Precision.HIGH


def project(linear, x):
    """``x @ W^T`` of a bias-free ``Linear``: the operand cast to the
    weights' dtype, the product kept in float32. Between two matrix
    products of a bfloat16 model an activation is then rounded once, as
    the next product's operand, and not also as this one's result and
    through every elementwise step between."""
    w = linear.weight
    return jnp.matmul(x.astype(w.dtype), w.T,
                      preferred_element_type=jnp.float32)


class GatedMLP(Module):
    """``down(silu(gate(x)) * up(x))`` without biases (SwiGLU, Shazeer
    2020), float32 out (:func:`project`)."""

    def __init__(self, embed_dim: int, hidden_dim: int):
        super().__init__()
        self.embed_dim, self.hidden_dim = embed_dim, hidden_dim
        self.gate = Linear(embed_dim, hidden_dim, with_bias=False)
        self.up = Linear(embed_dim, hidden_dim, with_bias=False)
        self.down = Linear(hidden_dim, embed_dim, with_bias=False)

    @scoped("mlp")
    def forward(self, input):
        return project(self.down, jax.nn.silu(project(self.gate, input))
                       * project(self.up, input))


def _inverse_unit_lower(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., C, C), C a
    power of two, by block forward substitution made of full-size matrix
    products: with X the inverse of the diagonal blocks of size s,
    the inverse at size 2s is ``X - X a_s X`` where ``a_s`` keeps the
    entries of ``a`` that couple the two halves of each 2s block."""
    c = a.shape[-1]
    idx = np.arange(c)
    x = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    s = 1
    while s < c:
        same_pair = (idx[:, None] // (2 * s)) == (idx[None, :] // (2 * s))
        lower_left = ((idx[:, None] // s) % 2 == 1) & ((idx[None, :] // s) % 2 == 0)
        a_s = jnp.where(jnp.asarray(same_pair & lower_left), a, 0.0)
        xa = jnp.matmul(x, a_s, precision=_EXACT)
        x = x - jnp.matmul(xa, x, precision=_EXACT)
        s *= 2
    return x


def gated_delta_chunk(q, k, v, g, beta, state):
    """The chunked gated delta rule. ``q``, ``k`` (B, T, H, dk), ``v``
    (B, T, H, dv), ``g`` = log alpha and ``beta`` (B, T, H), all float32,
    ``T`` a multiple of :data:`SUB`; ``state`` (B, H, dk, dv) float32.
    A token with ``g = 0`` and ``beta = 0`` leaves the state as it was
    (how padding is masked). Returns ``(o (B, T, H, dv), state)``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // SUB

    def cut(x):        # (B, T, H, ...) -> (N, B, H, SUB, ...)
        x = x.reshape((b, n, SUB, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    gc = jnp.cumsum(g, axis=-1)                           # (N,B,H,C)
    diff = gc[..., :, None] - gc[..., None, :]            # log decay i <- j
    idx = np.arange(SUB)
    incl = jnp.asarray(idx[:, None] >= idx[None, :])
    strict = jnp.asarray(idx[:, None] > idx[None, :])
    decay = jnp.exp(jnp.where(incl, diff, -jnp.inf))      # 0 above the diagonal
    kb = k * beta[..., None]
    mm = lambda x, y: jnp.matmul(x, y, precision=_CORE)
    a = jnp.where(strict, mm(kb, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    tri = _inverse_unit_lower(a)                          # (I + A)^-1
    w = mm(tri, kb * jnp.exp(gc)[..., None])              # (N,B,H,C,dk)
    u = mm(tri, v * beta[..., None])                      # (N,B,H,C,dv)
    qk = mm(q, jnp.swapaxes(k, -1, -2)) * decay           # lower incl. diagonal
    q_in = q * jnp.exp(gc)[..., None]
    last = gc[..., -1]                                    # (N,B,H)
    k_out = k * jnp.exp(last[..., None] - gc)[..., None]

    def sub_chunk(s, xs):
        w_n, u_n, qk_n, q_n, k_n, last_n = xs
        v_new = u_n - mm(w_n, s)                          # (B,H,C,dv)
        o = mm(q_n, s) + mm(qk_n, v_new)
        s = s * jnp.exp(last_n)[..., None, None] + mm(
            jnp.swapaxes(k_n, -1, -2), v_new)
        return s, o

    state, o = jax.lax.scan(sub_chunk, state, (w, u, qk, q_in, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)         # (B,N,C,H,dv)
    return o.reshape(b, t, h, dv), state


def gated_delta_step(q, k, v, g, beta, state):
    """One token: ``q``, ``k`` (B, H, dk), ``v`` (B, H, dv), ``g``,
    ``beta`` (B, H), ``state`` (B, H, dk, dv), float32 throughout.
    Returns ``(o (B, H, dv), state)``."""
    s = state * jnp.exp(g)[..., None, None]
    u = jnp.sum(s * k[..., :, None], axis=-2)             # S^T k
    s = s + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


class GatedDeltaNet(Module):
    """The mixer of a gated delta-rule layer: ``embed_dim`` ->
    ``num_heads`` heads of ``key_dim`` (q, k) and ``value_dim`` (v),
    a causal depthwise convolution of ``conv_kernel`` taps and SiLU on
    q, k and v, l2-normalized q (scaled by ``key_dim ** -0.5``) and k,
    ``beta = sigmoid(b . x)`` (doubled under ``allow_neg_eigval``),
    ``alpha = exp(-exp(A_log) * softplus(a . x + dt_bias))``, the delta
    rule, then ``out(rmsnorm(o) * silu(gate(x)))`` per head. No biases;
    float32 out whatever the weights' dtype (:func:`project`).

    The sequence's state is ``(S, tail)``: ``S`` (B, H, dk, dv) float32,
    ``tail`` (B, conv_kernel - 1, H * (2 dk + dv)) the convolution's
    last inputs in the activations' dtype (:meth:`init_state`)."""

    def __init__(self, embed_dim: int, num_heads: int, key_dim: int,
                 value_dim: int, conv_kernel: int = 4,
                 allow_neg_eigval: bool = True, norm_eps: float = 1e-6):
        super().__init__()
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_kernel = conv_kernel
        self.allow_neg_eigval = allow_neg_eigval
        self.conv_dim = num_heads * (2 * key_dim + value_dim)
        self.qkv = Linear(embed_dim, self.conv_dim, with_bias=False)
        self.gate = Linear(embed_dim, num_heads * value_dim, with_bias=False)
        # a . x (the decay's input) then b . x (beta's), one row a head
        self.ab = Linear(embed_dim, 2 * num_heads, with_bias=False)
        self.out_proj = Linear(num_heads * value_dim, embed_dim,
                               with_bias=False)
        self.o_norm = RMSNorm(value_dim, norm_eps)
        # tap j multiplies the input conv_kernel - 1 - j steps back
        self.register_parameter("conv_weight", bt_init.RandomUniform(
            -conv_kernel ** -0.5, conv_kernel ** -0.5)(
                (conv_kernel, self.conv_dim)))
        # the reference implementation's initialisation: A uniform in
        # (0, 16), the step dt log-uniform in (1e-3, 1e-1) through the
        # inverse of softplus, so alpha spans (0, 1) across heads
        self.register_parameter("A_log", jnp.log(
            bt_init.RandomUniform(1e-3, 16.0)((num_heads,))))
        dt = jnp.exp(bt_init.RandomUniform(
            float(np.log(1e-3)), float(np.log(1e-1)))((num_heads,)))
        self.register_parameter("dt_bias", dt + jnp.log(-jnp.expm1(-dt)))

    def init_state(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.num_heads, self.key_dim,
                           self.value_dim), jnp.float32),
                jnp.zeros((batch, self.conv_kernel - 1, self.conv_dim),
                          dtype))

    # ------------------------------------------------------------- pieces
    def _heads(self, y):
        """Convolved, activated (..., conv_dim) -> q, k, v per head in
        float32, q and k l2-normalized (q also scaled)."""
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        lead = y.shape[:-1]
        q = y[..., :h * dk].reshape(lead + (h, dk))
        k = y[..., h * dk:2 * h * dk].reshape(lead + (h, dk))
        v = y[..., 2 * h * dk:].reshape(lead + (h, dv))
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
        return unit(q) * dk ** -0.5, unit(k), v

    def _gates(self, x):
        """(..., embed) -> log alpha, beta (..., H) in float32."""
        ab = project(self.ab, x)
        a, b = ab[..., :self.num_heads], ab[..., self.num_heads:]
        g = -jnp.exp(self.A_log.astype(jnp.float32)) * jax.nn.softplus(
            a + self.dt_bias.astype(jnp.float32))
        beta = jax.nn.sigmoid(b)
        return g, 2.0 * beta if self.allow_neg_eigval else beta

    @scoped("attn/out")
    def _output(self, o, x):
        """Per-head o (..., H, dv) float32 and the layer's input ->
        the layer's output."""
        lead = o.shape[:-2]
        gate = project(self.gate, x).reshape(
            lead + (self.num_heads, self.value_dim))
        y = self.o_norm(o) * jax.nn.silu(gate)
        return project(self.out_proj, y.reshape(lead + (-1,)))

    # --------------------------------------------------------------- forms
    def forward_step(self, x_t, state, active=None):
        """One token a row: ``x_t`` (B, embed), ``state`` the rows'
        ``(S, tail)``. ``active`` (B,) bool: a row that is False keeps
        its state bit for bit (its output is junk the caller ignores)."""
        s, tail = state
        with jax.named_scope("attn/qkv"):
            w = self.conv_weight.astype(jnp.float32)
            window = jnp.concatenate(
                [tail, project(self.qkv, x_t)[:, None].astype(tail.dtype)],
                axis=1)
            y = jax.nn.silu(jnp.einsum(
                "bjc,jc->bc", window.astype(jnp.float32), w))
            q, k, v = self._heads(y)
            g, beta = self._gates(x_t)
        with jax.named_scope("gdn/step"):
            o, s_new = gated_delta_step(q, k, v, g, beta, s)
            tail_new = window[:, 1:]
            if active is not None:
                s_new = jnp.where(active[:, None, None, None], s_new, s)
                tail_new = jnp.where(active[:, None, None], tail_new, tail)
        return self._output(o, x_t), (s_new, tail_new)

    def forward_chunk(self, x, state, n_valid=None):
        """A chunk a row from a carried state: ``x`` (B, T, embed);
        ``n_valid`` (B,) says how many leading tokens of each row are
        real (the rest is right-padding that must not touch the state;
        None: all). Outputs at padded positions are junk."""
        b, t, _ = x.shape
        s, tail = state
        keep = self.conv_kernel - 1
        n_valid = (jnp.full((b,), t, jnp.int32) if n_valid is None
                   else n_valid.astype(jnp.int32))
        with jax.named_scope("attn/qkv"):
            full = jnp.concatenate(
                [tail, project(self.qkv, x).astype(tail.dtype)], axis=1)
            w = self.conv_weight.astype(jnp.float32)
            y = sum(full[:, j:j + t].astype(jnp.float32) * w[j]
                    for j in range(self.conv_kernel))
            q, k, v = self._heads(jax.nn.silu(y))
            g, beta = self._gates(x)
            real = (jnp.arange(t)[None, :] < n_valid[:, None])[..., None]
            g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
            pad = -t % SUB
            if pad:
                widen = lambda a: jnp.pad(
                    a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
        with jax.named_scope("gdn/chunk"):
            o, s_new = gated_delta_chunk(q, k, v, g, beta, s)
            # the last inputs BEFORE the padding: rows n_valid ..
            # n_valid+keep of [tail, inputs]
            tail_new = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
                f, n, keep, axis=0))(full, n_valid)
            o = o[:, :t]
        return self._output(o, x), (s_new, tail_new)

    def forward(self, input):
        out, _ = self.forward_chunk(
            input, self.init_state(input.shape[0], input.dtype))
        return out
