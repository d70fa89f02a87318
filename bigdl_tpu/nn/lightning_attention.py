"""Lightning attention (Qin et al. 2024, "Lightning Attention-2",
arXiv:2401.04658): linear attention with a FIXED decay a head. A layer
keeps, per sequence, a matrix ``S`` (d x d per head, float32) in place of
a KV cache. Per token and head, with ``lam`` the head's decay::

    S <- lam * S + k^T v
    o  = q S / sqrt(d)

q and k are RMS-normed per head and then rotated at the token's absolute
position, so the state a lane carries already holds rotated keys.

Three forms compute it and must agree (tests/test_lightning_attention.py):

- :meth:`LightningAttention.forward_step`: one token, ``S`` read and
  written once (decode);
- :meth:`LightningAttention.forward_chunk`: a chunk of tokens from a
  carried state (prefill), cut into sub-chunks of ``SUB`` tokens. Inside
  a sub-chunk everything is matrix products: ``O = ((Q K^T) * D) V +
  diag(lam^i) Q S`` with ``D_ij = lam^(i-j)`` formed directly from the
  difference (never as ``lam^i * lam^-j``: the fastest head's
  ``lam^-256`` overflows), and ``S <- lam^n S + sum_i lam^(n-i) k_i^T
  v_i`` over the sub-chunk's ``n`` real tokens;
- :meth:`LightningAttention.forward`: a whole sequence from a zero state
  (the chunked form over a padded length).

The lane contract is ``nn/gated_delta.py``'s with no convolution tail:
both served forms take and return the sequence's ``(S,)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.attention import RMSNorm, rotary_embedding_tokens
from bigdl_tpu.nn.gated_delta import project
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.module import Module, scoped

#: tokens resolved by matrix products between two sequential state passes
SUB = 256
#: precision of the chunked form's float32 products: three bfloat16
#: passes on a TPU (nn/gated_delta.py ``_CORE`` has the reading)
_CORE = jax.lax.Precision.HIGH


def decay_slopes(num_heads: int) -> np.ndarray:
    """``-log lam_h = 2^(-8 h / H)``, h = 1..H: the ALiBi-style slopes of
    Lightning Attention-2, the same in every layer."""
    return 2.0 ** (-8.0 * np.arange(1, num_heads + 1) / num_heads)


def lightning_step(q, k, v, log_decay, state):
    """One token: ``q``, ``k``, ``v`` (B, H, d) float32, ``log_decay``
    (H,) = log lam, ``state`` (B, H, d, d). Returns ``(o (B, H, d), state)``
    with ``o`` not yet scaled."""
    s = state * jnp.exp(log_decay)[None, :, None, None] \
        + k[..., :, None] * v[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def lightning_chunk(q, k, v, log_decay, state, n_valid):
    """The chunked form. ``q``, ``k``, ``v`` (B, T, H, d) float32, ``T`` a
    multiple of :data:`SUB`; ``state`` (B, H, d, d); ``n_valid`` (B,) the
    leading tokens of each row that are real: the rest neither decays nor
    feeds the state (outputs there are junk). Returns ``(o (B, T, H, d),
    state)``, ``o`` not yet scaled."""
    b, t, h, d = q.shape
    n = t // SUB

    def cut(x):        # (B, T, H, d) -> (N, B, H, SUB, d)
        return jnp.moveaxis(x.reshape(b, n, SUB, h, d), (1, 3), (0, 2))

    idx = np.arange(SUB)
    lag = jnp.asarray(idx[:, None] - idx[None, :], jnp.float32)
    ld = log_decay[:, None, None]                          # (H, 1, 1)
    decay = jnp.where(lag >= 0, jnp.exp(ld * jnp.maximum(lag, 0.0)), 0.0)
    q_in = jnp.exp(log_decay[:, None] * jnp.asarray(idx + 1, jnp.float32))
    mm = lambda x, y: jnp.matmul(x, y, precision=_CORE)
    # real tokens of each sub-chunk: (N, B)
    real = jnp.clip(n_valid[None, :] - SUB * jnp.arange(n)[:, None], 0, SUB)

    def sub_chunk(s, xs):
        q_n, k_n, v_n, n_n = xs                            # (B,H,C,d), (B,)
        o = mm(mm(q_n, jnp.swapaxes(k_n, -1, -2)) * decay, v_n) \
            + mm(q_n, s) * q_in[None, :, :, None]
        # lam^(n-i) for the real tokens i = 1..n, 0 behind them
        left = n_n[:, None, None] - jnp.asarray(idx + 1)[None, None, :]
        w = jnp.where(left >= 0, jnp.exp(
            log_decay[None, :, None] * jnp.maximum(left, 0)), 0.0)
        s = s * jnp.exp(log_decay[None, :] * n_n[:, None])[..., None, None] \
            + mm(jnp.swapaxes(k_n * w[..., None], -1, -2), v_n)
        return s, o

    state, o = jax.lax.scan(sub_chunk, state, (cut(q), cut(k), cut(v), real))
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t, h, d), state


class LightningAttention(Module):
    """The mixer of a lightning-attention layer: ``embed_dim`` ->
    ``num_heads`` heads of ``head_dim``; q and k RMS-normed per head (one
    gain of ``head_dim`` each, shared by the heads) and rotated (pairs
    interleaved, ``rotary_base``; None: no rotation), the fixed-decay
    recurrence, then ``out(rmsnorm(o) * sigmoid(gate(x)))`` with the norm
    over all heads' outputs side by side. No biases; float32 out whatever
    the weights' dtype (:func:`project`).

    The sequence's state is ``(S,)``: ``S`` (B, H, d, d) float32
    (:meth:`init_state`)."""

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 rotary_base=10000.0, norm_eps: float = 1e-6):
        super().__init__()
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim = head_dim
        self.rotary_base = rotary_base
        inner = num_heads * head_dim
        self.qkv = Linear(embed_dim, 3 * inner, with_bias=False)
        self.gate = Linear(embed_dim, inner, with_bias=False)
        self.out_proj = Linear(inner, embed_dim, with_bias=False)
        self.q_norm = RMSNorm(head_dim, norm_eps)
        self.k_norm = RMSNorm(head_dim, norm_eps)
        self.o_norm = RMSNorm(inner, norm_eps)
        #: log lam a head: a constant of the architecture, not a weight
        self.log_decay = -decay_slopes(num_heads).astype(np.float32)

    def init_state(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.num_heads, self.head_dim,
                           self.head_dim), jnp.float32),)

    # ------------------------------------------------------------- pieces
    @scoped("attn/qkv")
    def _heads(self, x, positions):
        """(..., embed) at ``positions`` (...,) -> q, k, v (..., H, d)
        float32, q and k normed and rotated."""
        lead = x.shape[:-1]
        qkv = project(self.qkv, x).reshape(
            lead + (3, self.num_heads, self.head_dim))
        q = self.q_norm(qkv[..., 0, :, :])
        k = self.k_norm(qkv[..., 1, :, :])
        if self.rotary_base is not None:
            q = rotary_embedding_tokens(q, positions, self.rotary_base)
            k = rotary_embedding_tokens(k, positions, self.rotary_base)
        return q, k, qkv[..., 2, :, :]

    @scoped("attn/out")
    def _output(self, o, x):
        """Per-head o (..., H, d) float32, unscaled, and the layer's
        input -> the layer's output."""
        lead = o.shape[:-2]
        y = self.o_norm((o * self.head_dim ** -0.5).reshape(lead + (-1,)))
        return project(self.out_proj,
                       y * jax.nn.sigmoid(project(self.gate, x)))

    # --------------------------------------------------------------- forms
    def forward_step(self, x_t, state, pos, active=None):
        """One token a row at position ``pos`` (B,): ``x_t`` (B, embed),
        ``state`` the rows' ``(S,)``. ``active`` (B,) bool: a row that is
        False keeps its state bit for bit (its output is junk)."""
        (s,) = state
        q, k, v = self._heads(x_t, pos)
        with jax.named_scope("lightning/step"):
            o, s_new = lightning_step(q, k, v, jnp.asarray(self.log_decay), s)
            if active is not None:
                s_new = jnp.where(active[:, None, None, None], s_new, s)
        return self._output(o, x_t), (s_new,)

    def forward_chunk(self, x, state, pos0, n_valid=None):
        """A chunk a row from a carried state: ``x`` (B, T, embed) whose
        first token stands at ``pos0`` (B,); ``n_valid`` (B,) says how
        many leading tokens of each row are real (None: all)."""
        b, t, _ = x.shape
        (s,) = state
        n_valid = (jnp.full((b,), t, jnp.int32) if n_valid is None
                   else n_valid.astype(jnp.int32))
        q, k, v = self._heads(x, pos0[:, None] + jnp.arange(t)[None, :])
        with jax.named_scope("lightning/chunk"):
            pad = -t % SUB
            if pad:
                widen = lambda a: jnp.pad(
                    a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                q, k, v = widen(q), widen(k), widen(v)
            o, s_new = lightning_chunk(q, k, v, jnp.asarray(self.log_decay),
                                       s, n_valid)
            o = o[:, :t]
        return self._output(o, x), (s_new,)

    def forward(self, input):
        b = input.shape[0]
        out, _ = self.forward_chunk(input, self.init_state(b),
                                    jnp.zeros((b,), jnp.int32))
        return out
