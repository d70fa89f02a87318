"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434 section
2.1, as the DeepSeek-V3 family serves it): keys and values of every head are
low-rank functions of ONE latent a token, and the cache holds the latent,
not the heads.

For a token ``x`` at position ``t`` (``n``: RMSNorm; ``R_t``: the rotary map
over interleaved feature pairs)::

    c_q = n(W_qa x);  q_h = W_qb^h c_q = [q_nope_h | q_rope_h],  q_rope_h <- R_t(q_rope_h)
    [c | k_r] = W_kva x;  c_kv = n(c);  k_rope = R_t(k_r)      (ONE head, shared)
    [k_nope_h | v_h] = W_kvb^h c_kv
    score_h(t, s) = (q_nope_h . k_nope_h,s + q_rope_h . k_rope_s) / sqrt(nope + rope)
    o_h = sum_s softmax_s(score_h) v_h,s;   y = W_o [o_1 .. o_H]

The cache row of a token is ``[c_kv | k_rope]`` (``kv_lora_rank + rope``
elements, after the norm and the rotation): a pool entry is ONE leaf
``(max_pages, page_size, row_width)`` with no head axis, written and
gathered as the other mixers' leaves are (whole rows, page and offset
leading). ``row_width`` is the row's elements up to whole 128-lane tiles,
zeros behind (576 -> 640 at the served widths): a TPU pads the minor
dimension to that in memory whatever the leaf says, and a leaf whose minor
dimension is not whole tiles is stored pages-minor by the runtime, so that
every write re-lays the whole leaf (the compile rehearsal for a v5e showed a
copy of every layer's leaf in the chunk program; PERF.md, PR 48 and PR 27).

Two forms over pages that compute the same thing. The prefill chunk is
EXPANDED: it walks its rows' pages by key blocks, makes each block's
``k_nope`` and ``v`` of every head from the gathered latents (``mla/expand``)
and attends per head with a running maximum and sum. The decode step is
WEIGHT-ABSORBED (``mla/absorb``): ``q~_h = (W_kvb^{K,h})^T q_nope_h`` meets
the latent itself, ``score = (q~_h . c_kv + q_rope_h . k_rope) / sqrt(..)``,
``o~_h = sum p c_kv`` and ``o_h = W_kvb^{V,h} o~_h``: one query token a row
never forms a key or a value. Matrix operands are the weights' dtype with
float32 accumulation; norms, rotation, softmax and the output are float32.

What the absorbed step READS is the engine's to say
(``forward_step_paged(decode_attention=)``). On one TPU chip the
paged-attention kernel (``ops/paged_attention.py``, its one-leaf form)
walks each row's table to the row's own position and reads every cached row
once, from the leaf where it lies, for the score and for the value product
alike. Everywhere else every slot of every table is gathered into a copy
first and the copy is read twice: the form off a TPU, and the reference the
tests hold the kernel to.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import (
    MultiHeadAttention, RMSNorm, _gather_pages, _key_block_pages,
    _known_decode_form, rotary_embedding_tokens,
)
from bigdl_tpu.nn.gated_delta import project
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.module import Module

#: a TPU tile's minor width
LANES = 128


def _attend_rows(q, rows, pos, scale: float):
    """Whole-row queries ``q`` (B, H, C) over every lane's gathered rows
    (B, N, C), keys past ``pos`` (B,) masked: ``softmax(scale * q . rows)
    @ rows``, float32 (B, H, C). A cached row is key and value at once."""
    s = jnp.einsum("bhc,bnc->bhn", q, rows,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhn,bnc->bhc", p.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32)


class LatentAttention(Module):
    """``embed_dim`` -> ``num_heads`` heads of ``qk_nope_head_dim +
    qk_rope_head_dim`` query/key features and ``v_head_dim`` values, through
    a query latent of ``q_lora_rank`` and a key-value latent of
    ``kv_lora_rank``. Causal; no biases; float32 out."""

    def __init__(self, embed_dim: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6):
        super().__init__()
        if qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {qk_rope_head_dim}: the "
                             "rotation takes feature PAIRS")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.nope, self.rope = qk_nope_head_dim, qk_rope_head_dim
        self.v_head_dim, self.rope_theta = v_head_dim, float(rope_theta)
        #: elements of a token's cache row, and the leaf's minor dimension:
        #: the row up to whole lanes
        self.row_elems = kv_lora_rank + qk_rope_head_dim
        self.row_width = -(-self.row_elems // LANES) * LANES
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        lin = lambda i, o: Linear(i, o, with_bias=False)
        self.q_a = lin(embed_dim, q_lora_rank)
        self.q_a_norm = RMSNorm(q_lora_rank, norm_eps)
        self.q_b = lin(q_lora_rank, num_heads * (self.nope + self.rope))
        self.kv_a = lin(embed_dim, self.row_elems)
        self.kv_a_norm = RMSNorm(kv_lora_rank, norm_eps)
        self.kv_b = lin(kv_lora_rank, num_heads * (self.nope + v_head_dim))
        self.out_proj = lin(num_heads * v_head_dim, embed_dim)

    # ---------------------------------------------------------------- pieces
    def _queries(self, x, positions):
        """(..., embed) at ``positions`` (...,) -> ``q_nope`` (..., H, nope)
        and the rotated ``q_rope`` (..., H, rope), float32."""
        with jax.named_scope("attn/qkv"):
            q = project(self.q_b, self.q_a_norm(project(self.q_a, x)))
            q = q.reshape(x.shape[:-1] + (self.num_heads, -1))
            return q[..., :self.nope], rotary_embedding_tokens(
                q[..., self.nope:], positions, self.rope_theta)

    def _latent(self, x, positions):
        """(..., embed) -> the tokens' cache rows (..., row_width) float32:
        the normed latent, the one rotated key head, zeros up to whole
        lanes."""
        with jax.named_scope("attn/qkv"):
            y = project(self.kv_a, x)
            r = self.kv_lora_rank
            k_rope = rotary_embedding_tokens(
                y[..., None, r:], positions, self.rope_theta)[..., 0, :]
            return self._whole_lanes(jnp.concatenate(
                [self.kv_a_norm(y[..., :r]), k_rope], -1))

    def _whole_lanes(self, rows):
        """(..., row_elems) -> (..., row_width), zeros behind."""
        return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1)
                       + ((0, self.row_width - self.row_elems),))

    def _kv_b_heads(self):
        """``W_kvb`` as (H, nope + v, kv_lora_rank): a head's key rows,
        then its value rows."""
        return self.kv_b.weight.reshape(self.num_heads, -1,
                                        self.kv_lora_rank)

    def _output(self, o):
        """(..., H, v) -> (..., embed)."""
        with jax.named_scope("attn/out"):
            return project(self.out_proj, o.reshape(o.shape[:-2] + (-1,)))

    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32):
        """The layer's ONE leaf: a page's tokens as rows of the latent and
        the shared rotated key (whole lanes wide), no head axis."""
        return jnp.zeros((max_pages, page_size, self.row_width), dtype)

    @staticmethod
    def _write(leaf, rows, tables, positions):
        """Rows (B, T, row_width) at ``positions`` (B, T) into the leaf
        through the block tables (whole rows, the indexed dimensions lead:
        an in-place scatter as ``_scatter_kv_paged``'s)."""
        with jax.named_scope("attn/kv_write"):
            ps = leaf.shape[1]
            pg = jnp.take_along_axis(tables, positions // ps, axis=1)
            return leaf.at[pg, positions % ps].set(rows.astype(leaf.dtype))

    # ------------------------------------------------------------- the forms
    def forward_step_paged(self, x_t, leaf, tables, pos,
                           decode_attention="rows"):
        """One token a row, weight-absorbed: ``x_t`` (B, embed) at ``pos``
        (B,). The row is written and the query meets the latent itself,
        every head against whole rows of the leaf. ``decode_attention``
        (``MultiHeadAttention.forward_step_paged``'s word, decided by the
        engine for the whole model) names how the rows are read.
        ``"kernel"`` gathers nothing: ``ops/paged_attention.py`` walks each
        row's table to the row's own ``pos`` and reads each page from the
        leaf where it lies, once for both products (one TPU chip). Any other
        word gathers every slot of every table as rows first (the clipped
        take of :func:`_gather_pages`; slots past ``pos`` are masked): the
        form off a TPU, and the kernel's parity reference."""
        _known_decode_form(decode_attention)
        in_place = decode_attention == "kernel"
        r, dtype = self.kv_lora_rank, leaf.dtype
        q_nope, q_rope = self._queries(x_t, pos)
        leaf = self._write(leaf, self._latent(x_t, pos)[:, None], tables,
                           pos[:, None])
        if not in_place:
            rows = _gather_pages(leaf, tables)               # (B, N, row_width)
        w = self._kv_b_heads()
        with jax.named_scope("attn/qkv"), jax.named_scope("mla/absorb"):
            q_lat = jnp.einsum("bhd,hdc->bhc", q_nope.astype(dtype),
                               w[:, :self.nope],
                               preferred_element_type=jnp.float32)
        with jax.named_scope("attn/attend"):
            q = self._whole_lanes(
                jnp.concatenate([q_lat, q_rope], -1)).astype(dtype)
            # over the whole row either way: the columns behind the latent
            # are dropped after the product, where slicing the cached rows
            # would copy them
            if in_place:
                from bigdl_tpu.ops.paged_attention import (
                    paged_latent_attention)

                o_lat = paged_latent_attention(q, leaf, tables, pos,
                                               self.scale)[..., :r]
            else:
                o_lat = _attend_rows(q, rows, pos, self.scale)[..., :r]
        with jax.named_scope("attn/out"), jax.named_scope("mla/absorb"):
            o = jnp.einsum("bhc,hdc->bhd", o_lat.astype(dtype),
                           w[:, self.nope:],
                           preferred_element_type=jnp.float32)
        return self._output(o), leaf

    def forward_chunk_paged(self, x, leaf, tables, pos0):
        """A chunk a row, expanded: ``x`` (B, T, embed) whose first token
        stands at ``pos0`` (B,) (the caller's contract is
        ``MultiHeadAttention.forward_chunk_paged``'s)."""
        with jax.named_scope("attn/qkv"):
            positions = pos0[:, None] + jnp.arange(x.shape[1])[None]
        q_nope, q_rope = self._queries(x, positions)
        leaf = self._write(leaf, self._latent(x, positions), tables,
                           positions)
        o = self._attend_key_blocks(q_nope, q_rope, leaf, tables, positions)
        return self._output(o), leaf

    def _attend_key_blocks(self, q_nope, q_rope, leaf, tables, positions):
        """``q_nope`` / ``q_rope`` (B, T, H, .) at ``positions`` (B, T) over
        the pages the rows hold, :func:`_key_block_pages` pages a round up
        to the furthest position of the dispatch (``_attend_key_blocks`` of
        nn/attention.py, with each round's keys and values made from the
        gathered latents). Returns (B, T, H, v) float32."""
        b, t, h, _ = q_nope.shape
        ps, r, dtype = leaf.shape[1], self.kv_lora_rank, leaf.dtype
        kp = _key_block_pages(ps, tables.shape[1])
        tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
        width = kp * ps
        qn, qr = q_nope.astype(dtype), q_rope.astype(dtype)
        w = self.kv_b.weight

        def some_keys(i, carry):
            top, den, acc = carry
            tb = jax.lax.dynamic_slice_in_dim(tables, i * kp, kp, axis=1)
            lat = _gather_pages(leaf, tb)                    # (B, N, row_width)
            with jax.named_scope("mla/expand"):
                kv = jnp.matmul(lat[..., :r], w.T,
                                preferred_element_type=jnp.float32
                                ).astype(dtype).reshape(b, width, h, -1)
            s = (jnp.einsum("bthd,bnhd->bhtn", qn, kv[..., :self.nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthd,bnd->bhtn", qr,
                              lat[..., r:self.row_elems],
                              preferred_element_type=jnp.float32)
                 ) * self.scale
            live = (i * width + jnp.arange(width))[None, None] \
                <= positions[:, :, None]                         # (B, T, N)
            s = jnp.where(live[:, None], s, -jnp.inf)
            # key 0 is live for every query: every maximum is finite
            new = jnp.maximum(top, jnp.max(s, axis=-1))
            p = jnp.exp(s - new[..., None])
            shrink = jnp.exp(top - new)
            den = den * shrink + jnp.sum(p, axis=-1)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bhtn,bnhd->bhtd", p.astype(dtype), kv[..., self.nope:],
                preferred_element_type=jnp.float32)
            return new, den, acc

        with jax.named_scope("attn/attend"):
            rounds = (jnp.max(positions) + width) // width
            _, den, acc = jax.lax.fori_loop(0, rounds, some_keys, (
                jnp.full((b, h, t), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, t), jnp.float32),
                jnp.zeros((b, h, t, self.v_head_dim), jnp.float32)))
            return (acc / den[..., None]).transpose(0, 2, 1, 3)

    def forward(self, input):
        """A whole sequence with no cache (B, T, embed), expanded, dense
        causal scores."""
        b, t, _ = input.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        q_nope, q_rope = self._queries(input, positions)
        lat = self._latent(input, positions)[..., :self.row_elems]
        r = self.kv_lora_rank
        with jax.named_scope("attn/attend"):
            kv = project(self.kv_b, lat[..., :r]).reshape(
                b, t, self.num_heads, -1)
            s = (jnp.einsum("bthd,bnhd->bhtn", q_nope, kv[..., :self.nope])
                 + jnp.einsum("bthd,bnd->bhtn", q_rope, lat[..., r:])
                 ) * self.scale
            causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
            o = jnp.einsum("bhtn,bnhd->bthd", p, kv[..., self.nope:])
        return self._output(o)

    # ------------------------------------------------------ host arithmetic
    #: what :meth:`forward_chunk_paged` gathers: the same walk by key
    #: blocks as the full-attention chunk's
    chunk_read_counts = staticmethod(MultiHeadAttention.chunk_read_counts)

    #: what :meth:`forward_step_paged` reads under each word: the kernel
    #: the pages up to each row's ``pos``, the gathered form every slot of
    #: every table, as the full-attention step does
    step_read_counts = staticmethod(MultiHeadAttention.step_read_counts)
