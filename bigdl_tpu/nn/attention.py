"""Attention / transformer layers — beyond-parity, TPU-first.

The reference has no attention stack (SURVEY.md §5 "Long-context /
sequence parallelism: Absent"); its sequence workloads are RNNs. This
module supplies the modern long-context path the north star requires:
fused-QKV multi-head attention whose math lives in one MXU-friendly
einsum chain, with optional **ring attention** sequence parallelism
(bigdl_tpu.parallel.ring_attention) when the sequence axis is sharded
over the mesh.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, scoped
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.dropout import Dropout


class LayerNorm(Module):
    """Layer normalization over the last dim (no reference analog; required
    by the transformer stack)."""

    def __init__(self, n_output: int, eps: float = 1e-5, affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.affine = affine
        if affine:
            self.register_parameter("weight", jnp.ones((n_output,)))
            self.register_parameter("bias", jnp.zeros((n_output,)))

    def forward(self, input):
        x = input.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        y = y.astype(input.dtype)
        if self.affine:
            y = y * self.weight + self.bias
        return y


def dot_product_attention(q, k, v, causal: bool = False, mask=None,
                          scale: Optional[float] = None):
    """(B, H, T, D) attention; softmax statistics in f32 for bf16 inputs."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    masked = causal or mask is not None
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    if masked:
        # rows with every key masked (e.g. causal with tq > tk) would
        # softmax to NaN (and poison gradients); run them through a benign
        # uniform softmax and zero the weights after, matching the pallas
        # kernel's finalize guard which emits 0 for such rows
        dead = jnp.all(scores == -jnp.inf, axis=-1, keepdims=True)
        scores = jnp.where(dead, 0.0, scores)
        w = jax.nn.softmax(scores, axis=-1)
        w = jnp.where(dead, 0.0, w).astype(v.dtype)
    else:
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def quantize_kv(x):
    """Symmetric per-(row, head, position) int8 quantization of a KV
    block ``x`` (..., T, D): scale = max|x| over D / 127 (1.0/127
    where the slice is all-zero, so zeros round-trip to zeros),
    q = round(x / scale) clipped to [-127, 127]. Returns
    ``(q int8, scale f32)`` with scale shaped (..., T, 1) — the
    sidecar that rides next to each quantized cache buffer.

    Deterministic: identical float inputs quantize to identical bytes,
    which is what keeps prefix-cache reuse token-identical and a
    demote→promote round-trip bit-identical under quantized serving."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: int8 codes × their per-position
    scales, cast to ``dtype``. Called INSIDE the fused attention math
    (never on the persistent pools), so the only full-precision view of
    a quantized cache is the transient one XLA fuses into the score
    einsum."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _write_kv(cache, k_t, v_t, write):
    """Write one K/V block into ``cache`` via ``write(buf, block)`` and
    return ``(new_cache, k_read, v_read)`` — the buffers attention must
    attend over. The float 2-tuple form writes the block as-is and
    reads the raw buffers; the quantized 4-tuple form
    ``(k_q, v_q, k_scale, v_scale)`` quantizes the incoming block and
    writes int8 codes + scales (the scale sidecar shares ``write``'s
    index math: same rank, last dim 1), then returns dequantized
    views — so what is attended is EXACTLY what is stored, and a warm
    prefix-cache hit replays the same numerics as the cold pass."""
    if len(cache) == 2:
        k_cache, v_cache = cache
        k_cache = write(k_cache, k_t.astype(k_cache.dtype))
        v_cache = write(v_cache, v_t.astype(v_cache.dtype))
        return (k_cache, v_cache), k_cache, v_cache
    k_q, v_q, k_s, v_s = cache
    kq, ks = quantize_kv(k_t)
    vq, vs = quantize_kv(v_t)
    k_q = write(k_q, kq)
    v_q = write(v_q, vq)
    k_s = write(k_s, ks.astype(k_s.dtype))
    v_s = write(v_s, vs.astype(v_s.dtype))
    return ((k_q, v_q, k_s, v_s),
            dequantize_kv(k_q, k_s, k_t.dtype),
            dequantize_kv(v_q, v_s, v_t.dtype))


def _kv_buffers(shape, scale_shape, dtype, sharding, kv_dtype):
    """Zero KV buffers of ``shape``: ``(k, v)`` in ``dtype``, or for
    ``kv_dtype="int8"`` the quantized form ``(k_q, v_q, k_scale,
    v_scale)`` with f32 sidecars of ``scale_shape``. ``sharding``
    allocates each buffer directly with that layout."""
    def mk(shp, dt):
        return jnp.zeros(shp, dt, device=sharding) \
            if sharding is not None else jnp.zeros(shp, dt)

    if kv_dtype is None:
        return mk(shape, dtype), mk(shape, dtype)
    if str(kv_dtype) != "int8":
        raise ValueError(
            f"kv_dtype must be None (full precision) or 'int8', "
            f"got {kv_dtype!r}")
    return (mk(shape, jnp.int8), mk(shape, jnp.int8),
            mk(scale_shape, jnp.float32), mk(scale_shape, jnp.float32))


def _gather_pages(leaf, tables, heads=None):
    """Assemble one logical KV row per batch entry from a page pool:
    ``leaf`` is a pool buffer (max_pages, page_size, H * D) — one
    token's heads side by side in the minor dimension (the scale
    sidecars: (max_pages, page_size, H)) — and ``tables``
    (B, table_len) the per-row page ids: position ``i`` of row ``b``
    lives at ``leaf[tables[b, i // page_size], i % page_size]``.

    Hands out one of two views of the same gathered pages. With
    ``heads=None``, ROWS (B, table_len * page_size, H * D): only
    (table_len, page_size) merge, the minor dimension stays what the
    pool stores, whole 128-lane tiles at the widths served — what the
    decode step contracts over (:func:`_attend_pages_rows`). With
    ``heads`` given, the TOKEN-MAJOR per-head view (B, table_len *
    page_size, H, D) the chunk's and the mesh step's per-head einsums
    read; splitting the minor 1280 into (20, 64) makes a TPU re-lay
    every gathered byte into padded tiles (PERF.md, PR 30), which a
    chunk of 128 query tokens earns back and one decode token does not.

    XLA lowers the take to one gather, so compiled shape depends only
    on the POOL geometry, never on any request's length. Table slots
    past a request's reservation point at the scratch page — garbage
    the caller's causal mask must (and does) discard, and bytes the
    gather moves all the same: a decode step that takes whole tables
    reads 35 and 9 times what its rows hold at the two loads served
    (PERF.md, PR 44). Who calls this: the chunk's key-block loop, a
    few pages a round; the decode step off a TPU and on a mesh, whole
    tables. The decode step on one TPU chip gathers nothing
    (``ops/paged_attention.py``, under this same contract).

    CALLER CONTRACT: every id in ``tables`` lies in ``[0, max_pages)``,
    and a slot that holds nothing names the scratch page (id 0), which
    is a page like any other. The take CLIPS: it pays for the gather
    alone, where ``jnp.take``'s default fills what an out-of-range id
    would name and so runs a select over every gathered byte (seven
    tenths of a decode step at GPT-2 Large's widths; PERF.md, PR 41).
    The ids are in range by construction — ``PagePool`` hands out
    ``1 .. max_pages - 1``, ``BlockTable.as_array`` and the engine's
    slot tables pad with ``SCRATCH_PAGE`` — and
    ``tests/test_paged_kv.py`` holds every dispatched table to it. An
    id out of range would read the nearest end's page instead of NaN:
    a fault of the allocator either way, never a served result."""
    b, tlen = tables.shape
    with jax.named_scope("attn/kv_gather"):
        # (B, table_len, ps, H*D)
        g = jnp.take(leaf, tables, axis=0, mode="clip")
        rows = g.reshape(b, tlen * g.shape[2], g.shape[3])
        if heads is None:
            return rows
        return rows.reshape(b, rows.shape[1], heads, -1)


def _dequantize_kv_rows(codes, scale, dtype):
    """:func:`dequantize_kv` on the ROWS view: ``codes`` (B, T, H * D)
    int8, ``scale`` (B, T, H) — each head's scale spread over that
    head's D columns, then the same float32 product and the same cast,
    so what is attended is the stored value to the bit.

    The spread is a product with a one-hot (H, H * D) matrix at the
    highest precision, which is exact (every output is one scale times
    1.0) and lands in rows of H * D as the MXU writes them; a
    ``jnp.repeat`` goes through (H, D) tiles and a re-lay of the whole
    float32 view (3.4 against 1.7 ms a layer on the chip, PERF.md,
    PR 30)."""
    h = scale.shape[-1]
    d = codes.shape[-1] // h
    spread = (jnp.arange(h * d)[None, :] // d
              == jnp.arange(h)[:, None]).astype(scale.dtype)
    scale_rows = jnp.einsum("bth,hc->btc", scale, spread,
                            precision=jax.lax.Precision.HIGHEST)
    return (codes.astype(jnp.float32) * scale_rows).astype(dtype)


def _scatter_kv_paged(pool, k_t, v_t, tables, positions):
    """Scatter one K/V block (B, H, T, D) into the page-pool buffers
    through per-row block tables and return the pool. ``positions`` is
    (B,) (one decode token per row) or (B, T) (a ragged chunk); token
    ``t`` of row ``b`` scatters to page
    ``tables[b, positions[b,t] // page_size]`` at offset
    ``positions[b, t] % page_size``.

    The write is ``buf.at[page, offset].set(rows)`` on a leaf
    (max_pages, page_size, H * D): the two indexed dimensions LEAD and
    the window is one whole row of H * D, so the scatter updates the
    donated leaf in place. Any layout that breaks either half re-lays
    the WHOLE leaf around every scatter: heads between page and
    offset, or a 4-D leaf whose minor dimension is a 64-wide head,
    which the TPU runtime stores pages-MINOR to dodge lane padding
    (PERF.md, PR 27; tests/test_chip_compile.py holds it).

    The quantized 4-tuple form mirrors the dense path exactly: codes
    and scale sidecars share the scatter index math.

    Rows whose table slots are the scratch page (idle dispatch lanes)
    scatter junk there — multiple lanes may collide on it, which is
    fine precisely because nothing gathered from the scratch page ever
    survives the position mask."""
    if jnp.ndim(positions) == 1:
        positions = positions[:, None]          # decode step: T == 1
    ps = pool[0].shape[1]

    def write(buf, blk):
        # blk (B, H, T, D') -> one row of H * D' per token; the
        # transpose is of the small block, never of the leaf
        b, h, t, d = blk.shape
        rows = blk.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        return buf.at[pg, off].set(rows.astype(buf.dtype))

    with jax.named_scope("attn/kv_write"):
        pg = jnp.take_along_axis(tables, positions // ps, axis=1)  # (B, T)
        off = positions % ps
        if len(pool) == 2:
            k_buf, v_buf = pool
            return write(k_buf, k_t), write(v_buf, v_t)
        k_q, v_q, k_s, v_s = pool
        kq, ks = quantize_kv(k_t)
        vq, vs = quantize_kv(v_t)
        return (write(k_q, kq), write(v_q, vq), write(k_s, ks),
                write(v_s, vs))


def _write_kv_paged(pool, k_t, v_t, tables, positions, rows=False):
    """Paged twin of :func:`_write_kv` for the decode step:
    :func:`_scatter_kv_paged`, then gather the dense per-row views
    attention attends over: per head, (B, T_total, H, D), or with
    ``rows=True`` as the pool stores them, (B, T_total, H * D) (the two
    views of :func:`_gather_pages`; the decode step on one device
    that is no TPU asks for rows, the mesh step for heads; on one TPU
    chip the step takes :func:`_scatter_kv_paged` alone and a kernel
    reads the pages in place, ``ops/paged_attention.py``; the chunk
    gathers by key blocks, :func:`_attend_key_blocks`).

    With the quantized 4-tuple what is attended is the dequantized
    STORED view, so a paged cold pass attends the values a dense
    engine's pass attends."""
    heads = k_t.shape[1]
    pool = _scatter_kv_paged(pool, k_t, v_t, tables, positions)
    view = None if rows else heads
    if len(pool) == 2:
        k_buf, v_buf = pool
        return (pool,
                _gather_pages(k_buf, tables, view),
                _gather_pages(v_buf, tables, view))
    k_q, v_q, k_s, v_s = pool
    # the sidecar's own per-head view is (B, T, H, 1); as rows it stays
    # (B, T, H) and is spread over each head's columns
    dequant = _dequantize_kv_rows if rows else dequantize_kv
    with jax.named_scope("attn/kv_gather"):
        return (pool,
                dequant(_gather_pages(k_q, tables, view),
                        _gather_pages(k_s, tables, view), k_t.dtype),
                dequant(_gather_pages(v_q, tables, view),
                        _gather_pages(v_s, tables, view), v_t.dtype))


#: keys a round of the chunk's paged attention scores at once. On the
#: chip the cost follows the keys read, rounded up to whole rounds: at
#: head size 128 rounds of 256 to 1024 keys read alike and 128 pays for
#: its trips, at head size 64 rounds of 64 to 256 read alike and wider
#: ones pay for scratch slots (PERF.md, PR 38)
KEY_BLOCK_TOKENS = 256


def _key_block_pages(page_size: int, table_len: int) -> int:
    """Pages of keys :func:`_attend_key_blocks` gathers a round: the
    whole pages of ``KEY_BLOCK_TOKENS`` keys, no more than the table
    has. From shapes alone, so one compiled width a pool geometry; a
    table no longer than a round is read in one, which is the dense
    form."""
    return max(1, min(table_len, KEY_BLOCK_TOKENS // page_size))


@scoped("attn/attend")
def _attend_key_blocks(q, pool, tables, positions):
    """A chunk's causal softmax over the pages its rows hold: ``q``
    (B, H, T, D) whose token ``t`` of row ``b`` stands at
    ``positions[b, t]`` and attends the keys at or before it, ``pool``
    the leaves the chunk was just scattered into (the float pair, or
    the int8 4-tuple, dequantized block by block as stored). Returns
    (B, H, T, D) float32.

    The table is walked :func:`_key_block_pages` pages a round with a
    running maximum, sum and float32 accumulator, up to the furthest
    position any row of the dispatch has reached and nothing behind it
    (the trip count is traced, the width is not): a row's scratch
    slots past that are never gathered, where the dense form gathered
    every row's whole table and made (B, H, T, table_len * page_size)
    float32 scores whatever the rows held. The page ids are the
    table's own, so the take clips and needs no fill. GQA runs grouped
    against the un-expanded block; heads stay a batch dimension of
    both products, so a heads-sharded pool needs no collective."""
    b, h, t, d = q.shape
    ps = pool[0].shape[1]
    h_kv = pool[0].shape[2] // d
    kp = _key_block_pages(ps, tables.shape[1])
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
    width = kp * ps
    qg = q.reshape(b, h_kv, h // h_kv, t, d)
    scale = 1.0 / math.sqrt(d)

    def block(leaves, tb):
        # (B, width, H_kv, D) in q's dtype: the pages as stored, or the
        # codes times their scales (dequantize_kv's float32 product)
        with jax.named_scope("attn/kv_gather"):
            got = [_gather_pages(leaf, tb, h_kv) for leaf in leaves]
            return got[0] if len(got) == 1 else dequantize_kv(*got, q.dtype)

    def some_keys(i, carry):
        top, den, acc = carry
        tb = jax.lax.dynamic_slice_in_dim(tables, i * kp, kp, axis=1)
        # (k, v) or (k_q, v_q, k_s, v_s): K's leaves are the even ones
        k_i, v_i = block(pool[0::2], tb), block(pool[1::2], tb)
        s = jnp.einsum("bgrtd,bngd->bgrtn", qg, k_i,
                       preferred_element_type=jnp.float32) * scale
        live = (i * width + jnp.arange(width))[None, None] \
            <= positions[:, :, None]                         # (B, T, N)
        s = jnp.where(live[:, None, None], s, -jnp.inf)
        # key 0 is live for every query, so from the first round on
        # every maximum is finite
        new = jnp.maximum(top, jnp.max(s, axis=-1))
        p = jnp.exp(s - new[..., None])
        shrink = jnp.exp(top - new)
        den = den * shrink + jnp.sum(p, axis=-1)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bgrtn,bngd->bgrtd", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32)
        return new, den, acc

    rounds = (jnp.max(positions) + width) // width
    _, den, acc = jax.lax.fori_loop(0, rounds, some_keys, (
        jnp.full(qg.shape[:-1], -jnp.inf, jnp.float32),
        jnp.zeros(qg.shape[:-1], jnp.float32),
        jnp.zeros(qg.shape, jnp.float32)))
    return (acc / den[..., None]).reshape(b, h, t, d)


@scoped("attn/attend")
def _attend_pages_heads(q, k_read, v_read, pos):
    """One query token a row over gathered pages, PER HEAD: ``q``
    (B, H, D), ``k_read`` / ``v_read`` the token-major per-head view
    (B, T, H_kv, D), ``pos`` (B,) the last live position of each row.
    GQA runs grouped against the un-expanded view; scores accumulate
    in float32. Returns (B, H, D) in ``v_read``'s dtype.

    Heads stay a batch dimension of both einsums, so with heads
    sharded over a model axis every device attends its own heads and
    the attention needs no collective: the tensor-parallel engine's
    form. On one TPU chip the 64-wide minor dimension costs a re-lay
    of everything gathered (:func:`_attend_pages_rows` is that case's
    form)."""
    b, h, d = q.shape
    h_kv = k_read.shape[2]
    qg = q.reshape(b, h_kv, h // h_kv, d)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, k_read,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
    live = jnp.arange(k_read.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v_read.dtype)
    return jnp.einsum("bgrt,btgd->bgrd", p, v_read).reshape(b, h, d)


@scoped("attn/attend")
def _attend_pages_rows(q, k_rows, v_rows, pos):
    """One query token a row over gathered pages left as ROWS: ``q``
    (B, H, D), ``k_rows`` / ``v_rows`` (B, T, H_kv * D) as the pool
    stores them, ``pos`` (B,). Returns (B, H, D) in ``v_rows``' dtype.

    ``q`` is spread onto a block diagonal (B, H, H_kv * D) — head
    ``h``'s D values in the columns of its own kv head, zeros
    elsewhere (GQA's ``rep`` query heads share a column block) — so
    scores and output are two plain matrix products over whole rows
    and the gathered bytes are read as they lie. The products with
    the zeros add nothing, both contractions accumulate in float32:
    the same sums as the per-head form in another order, for H_kv
    times its multiply-adds, which one query token a row can afford
    (and a chunk of 128 cannot). Each head then keeps its own D
    columns of the (B, H, H_kv * D) output.

    The contraction runs over the rows' last dimension, where a mesh
    shards heads: partitioned, it would be a cross-device sum of
    partial products known to be zero. The mesh engine keeps
    :func:`_attend_pages_heads`."""
    b, h, d = q.shape
    h_kv = k_rows.shape[2] // d
    own = jnp.arange(h) // (h // h_kv)          # head -> its kv head
    on_diag = own[:, None] == jnp.arange(h_kv)[None, :]      # (H, H_kv)
    q_bd = jnp.where(on_diag[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(b, h, h_kv * d)
    s = jnp.einsum("bhc,btc->bht", q_bd, k_rows,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
    live = jnp.arange(k_rows.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v_rows.dtype)
    o_full = jnp.einsum("bht,btc->bhc", p, v_rows)
    return o_full.reshape(b, h, h_kv, d)[:, jnp.arange(h), own]


#: the two forms of "attend one query token over gathered pages"; the
#: key is what ``forward_step_paged(..., decode_attention=)`` takes and
#: what ``engine.stats()["paging"]["decode_attention"]`` reports
_DECODE_ATTENTION = {"rows": _attend_pages_rows,
                     "heads": _attend_pages_heads}
#: ... and the third word gathers nothing: ``ops/paged_attention.py``
#: reads the pages where they lie
_DECODE_FORMS = sorted((*_DECODE_ATTENTION, "kernel"))


def _known_decode_form(word) -> None:
    """Refuse a ``decode_attention`` that names no form."""
    if word not in _DECODE_FORMS:
        raise ValueError(
            f"decode_attention must be one of {_DECODE_FORMS}, got "
            f"{word!r}")


def rotary_embedding(x, positions, base: float = 10000.0):
    """RoPE: rotate interleaved feature pairs of x (..., T, D) by
    per-position angles (RoFormer). ``positions`` is (T,) absolute
    positions — correct under sequence/ring parallelism too, because the
    rotation happens before K blocks travel."""
    d = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_embedding_rowwise(x, positions, base: float = 10000.0):
    """RoPE at PER-ROW positions: each batch row of x (B, H, T, D)
    rotated by its own absolute positions — ``positions`` is (B,) for
    a one-token decode step or (B, T) for a ragged chunk (rows at
    different sequence depths, the mixed-depth serving paths). One
    formula: vmap of :func:`rotary_embedding` over the batch, so the
    rotation math can never diverge between paths."""
    if jnp.ndim(positions) == 1:
        positions = positions[:, None]
    return jax.vmap(
        lambda xi, pi: rotary_embedding(xi, pi, base))(x, positions)


def rotary_embedding_tokens(x, positions, base: float = 10000.0):
    """RoPE on the token-major layout: ``x`` (..., H, D) with one
    absolute position a token, ``positions`` (...,); the pairs and angles
    of :func:`rotary_embedding`."""
    d = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(Module):
    """Root-mean-square normalization over the last dim with a learned
    gain and no bias or mean subtraction (Zhang & Sennrich 2019): the
    statistic in float32, the result in the input's dtype."""

    def __init__(self, n_output: int, eps: float = 1e-6):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.register_parameter("weight", jnp.ones((n_output,)))

    def forward(self, input):
        x = input.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (y * self.weight.astype(jnp.float32)).astype(input.dtype)


def normed(norm, x):
    """``norm(x)`` for a LayerNorm / RMSNorm a block calls on its own:
    its operations carry the ``norm`` scope."""
    with jax.named_scope("norm"):
        return norm(x)


class MultiHeadAttention(Module):
    """Fused-QKV multi-head self/cross attention.

    ``sequence_parallel`` names a mesh axis: inside a shard_map over that
    axis the layer switches to ring attention (each device holds a sequence
    block; K/V blocks rotate over ICI via ppermute).

    ``rotary=True`` applies RoPE to q/k after the projection (no learned
    positional table needed upstream); composes with GQA, flash, ring
    attention, and the KV cache (the cache stores rotated keys).

    ``qk_norm=True`` normalizes the WHOLE q projection and the whole k
    projection with an ``RMSNorm`` each before the heads are split (and
    before any rotation): every path, cached and paged, splits through
    ``_split_kv_step``, so the cache holds normalized keys."""

    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 with_bias: bool = True, causal: bool = False,
                 sequence_parallel: Optional[str] = None,
                 use_flash: bool = False,
                 num_kv_heads: Optional[int] = None,
                 rotary: bool = False, rotary_base: float = 10000.0,
                 qk_norm: bool = False, norm_eps: float = 1e-6):
        super().__init__()
        assert embed_dim % num_heads == 0
        if rotary and (embed_dim // num_heads) % 2:
            raise ValueError(
                f"rotary embeddings need an even head_dim, got "
                f"{embed_dim // num_heads} (embed_dim {embed_dim} / "
                f"{num_heads} heads): RoPE rotates feature PAIRS")
        self.rotary = rotary
        self.rotary_base = rotary_base
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        # grouped-query attention (GQA): fewer kv heads, each shared by
        # num_heads/num_kv_heads consecutive query heads — shrinks the kv
        # projection and (with use_flash) the kv HBM traffic
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not a multiple of "
                             f"num_kv_heads {self.num_kv_heads}")
        self.causal = causal
        self.dropout_p = dropout
        self.sequence_parallel = sequence_parallel
        # opt-in pallas flash kernel (bigdl_tpu/ops/flash_attention.py):
        # O(T*D) memory instead of the dense (T,T) score matrix
        self.use_flash = use_flash
        kv_dim = self.num_kv_heads * self.head_dim
        self.qkv = Linear(embed_dim, embed_dim + 2 * kv_dim,
                          with_bias=with_bias)
        self.out_proj = Linear(embed_dim, embed_dim, with_bias=with_bias)
        self.qk_norm = qk_norm
        if qk_norm:
            self.q_norm = RMSNorm(embed_dim, norm_eps)
            self.k_norm = RMSNorm(kv_dim, norm_eps)
        if dropout > 0:
            self.drop = Dropout(dropout)

    def _split_heads(self, x, n_heads=None):
        b, t, _ = x.shape
        n = n_heads or self.num_heads
        return x.reshape(b, t, n, self.head_dim).transpose(0, 2, 1, 3)

    def _expand_kv(self, k, v):
        """Materialize shared kv heads for the non-flash paths (the flash
        kernel reads them via its BlockSpec index map instead)."""
        if self.num_kv_heads == self.num_heads:
            return k, v
        rep = self.num_heads // self.num_kv_heads
        return jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   sharding=None, kv_dtype=None):
        """Zero KV cache for incremental decoding: (k, v) each
        (B, H_kv, max_len, D). ``sharding`` allocates the buffers
        directly with that layout (no single-device materialization, no
        tracing) — the long-context sharded-cache serving path.

        ``kv_dtype="int8"`` returns the QUANTIZED cache form instead:
        ``(k_q, v_q, k_scale, v_scale)`` with int8 code buffers of the
        same (B, H_kv, max_len, D) shape and f32 scale sidecars
        (B, H_kv, max_len, 1) — one symmetric scale per (row, head,
        position), written/read by :func:`quantize_kv` /
        :func:`dequantize_kv` inside the attention paths. Scale
        sidecars keep rank 4 with heads at dim 1, so a heads-sharded
        pool layout (parallel/tp.py ``kv_pool_spec``) applies to the
        whole tree unchanged. (The paged engine's pool has a layout of
        its own: :meth:`init_page_pool`.)"""
        shape = (batch, self.num_kv_heads, max_len, self.head_dim)
        return _kv_buffers(shape, shape[:-1] + (1,), dtype, sharding,
                           kv_dtype)

    def _split_kv_step(self, qkv):
        kv_dim = self.num_kv_heads * self.head_dim
        q = qkv[..., :self.embed_dim]
        k = qkv[..., self.embed_dim:self.embed_dim + kv_dim]
        if self.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        q = self._split_heads(q)
        k = self._split_heads(k, self.num_kv_heads)
        v = self._split_heads(qkv[..., self.embed_dim + kv_dim:],
                              self.num_kv_heads)
        return q, k, v

    def forward_step(self, x_t, cache, pos):
        """One decode step: x_t (B, 1, C) attends over the cache filled up
        to ``pos`` (a traced scalar — static shapes, masked softmax over
        the full cache length, the XLA-friendly form). GQA runs as a
        grouped einsum against the UN-expanded cache (scores accumulated
        in f32, matching dot_product_attention) — no per-step
        num_heads-sized kv copy.

        RAGGED batches: ``pos`` may be a (B,) vector of per-row positions
        (rows at different sequence depths, the mixed-prompt-length
        serving path) — each row writes its KV at, rotates by, and masks
        against its OWN position."""
        ragged = jnp.ndim(pos) == 1
        b = x_t.shape[0]
        qkv = self.qkv(x_t.reshape(b, self.embed_dim)).reshape(b, 1, -1)
        q, k_t, v_t = self._split_kv_step(qkv)      # q (B,H,1,D)
        if self.rotary:
            if ragged:
                q = rotary_embedding_rowwise(q, pos, self.rotary_base)
                k_t = rotary_embedding_rowwise(k_t, pos, self.rotary_base)
            else:
                positions = jnp.asarray(pos)[None]
                q = self._rope(q, positions)
                k_t = self._rope(k_t, positions)
        if ragged:
            write = lambda c, blk: jax.vmap(
                lambda ci, ti, p: jax.lax.dynamic_update_slice(
                    ci, ti, (0, p, 0)))(c, blk, pos)
        else:
            write = lambda c, blk: jax.lax.dynamic_update_slice(
                c, blk, (0, 0, pos, 0))
        cache, k_read, v_read = _write_kv(cache, k_t, v_t, write)
        h_kv = self.num_kv_heads
        rep = self.num_heads // h_kv
        qg = q.reshape(b, h_kv, rep, self.head_dim)  # 1-token axis folded
        scale = 1.0 / math.sqrt(self.head_dim)
        s = jnp.einsum("bgrd,bgtd->bgrt", qg, k_read,
                       preferred_element_type=jnp.float32) * scale
        if ragged:
            live = jnp.arange(k_read.shape[2])[None, :] <= pos[:, None]
            s = jnp.where(live[:, None, None, :], s, -jnp.inf)
        else:
            live = jnp.arange(k_read.shape[2]) <= pos
            s = jnp.where(live[None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v_read.dtype)
        o = jnp.einsum("bgrt,bgtd->bgrd", p, v_read)
        o = o.reshape(b, self.embed_dim).astype(x_t.dtype)
        o = self.out_proj(o).reshape(b, 1, -1)
        return o, cache

    def forward_prefill(self, x, cache, pos0: int = 0):
        """Batched prompt prefill: one causal pass over x (B, T0, C) that
        both produces the outputs and writes K/V into the cache at
        ``pos0`` — O(T0²) once instead of T0 masked steps over max_len.

        ``pos0`` must be a static int. With ``pos0 > 0`` this is a
        *continuation* prefill: the new block's queries also attend over
        the cached prefix ``[0, pos0)`` (the cache stores rotated keys,
        so the prefix is position-correct as stored)."""
        if not isinstance(pos0, int):
            raise TypeError("forward_prefill pos0 must be a static int "
                            "(the cache prefix length is a shape)")
        b, t, _ = x.shape
        qkv = self.qkv(x.reshape(b * t, self.embed_dim)).reshape(b, t, -1)
        q, k, v = self._split_kv_step(qkv)
        if self.rotary:
            positions = pos0 + jnp.arange(t)
            q, k = self._rope(q, positions), self._rope(k, positions)
        if pos0 + t > cache[0].shape[2]:
            # dynamic_update_slice would silently CLAMP the write start,
            # corrupting the prefix — fail at trace time instead
            raise ValueError(
                f"prefill of {t} tokens at pos0={pos0} overflows the "
                f"{cache[0].shape[2]}-long KV cache")
        write = lambda c, blk: jax.lax.dynamic_update_slice(
            c, blk, (0, 0, pos0, 0))
        cache, k_read, v_read = _write_kv(cache, k, v, write)
        if pos0 or len(cache) == 4:
            # attend over cached prefix + new block; dot_product_attention's
            # causal mask (tril offset tk - tq = pos0) lets query i see
            # exactly keys [0, pos0 + i]. A QUANTIZED cache takes this
            # branch even at pos0 == 0: attending the dequantized stored
            # rows (not the pre-quantization block) keeps the cold pass
            # numerically identical to every later warm read of the same
            # rows — the prefix-cache reuse invariant.
            k = jax.lax.slice_in_dim(k_read, 0, pos0 + t, axis=2) \
                .astype(q.dtype)
            v = jax.lax.slice_in_dim(v_read, 0, pos0 + t, axis=2) \
                .astype(q.dtype)
        kx, vx = self._expand_kv(k, v)
        o = dot_product_attention(q, kx, vx, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, self.embed_dim)
        o = self.out_proj(o.reshape(b * t, self.embed_dim)).reshape(b, t, -1)
        return o, cache

    def forward_chunk(self, x, cache, pos0):
        """Chunked continuation prefill with a TRACED ``pos0``: a fixed
        chunk length compiles ONCE and serves every offset (unlike
        forward_prefill, whose static pos0 is a shape and recompiles per
        offset). The chunk's queries attend over the FULL cache under a
        position mask — O(T_chunk · max_len) scores, the standard
        chunked-prefill form; GQA runs grouped against the un-expanded
        cache like forward_step.

        RAGGED batches: ``pos0`` may be a (B,) vector of per-row offsets
        (each row's chunk lands at its OWN depth — the multi-admission
        batched-prefill serving path): each row writes its KV at,
        rotates by, and masks against its own ``pos0 + i`` positions,
        so one dispatch advances several independent prefills at once.

        CALLER CONTRACT: ``pos0 + T_chunk <= cache length`` must hold
        (per row, when ragged) — pos0 is traced, so it cannot be checked
        at trace time the way forward_prefill checks its static offset,
        and an overflowing write would be silently CLAMPED by
        dynamic_update_slice (corrupting the prefix) while the mask
        still assumes positions pos0..pos0+T. generate()'s _decode_setup
        validates this; standalone users (e.g. the exported serving
        program) must too."""
        ragged = jnp.ndim(pos0) == 1
        b, t, _ = x.shape
        qkv = self.qkv(x.reshape(b * t, self.embed_dim)).reshape(b, t, -1)
        q, k, v = self._split_kv_step(qkv)
        if self.rotary:
            if ragged:
                positions = pos0[:, None] + jnp.arange(t)[None]  # (B, T)
                q = rotary_embedding_rowwise(q, positions,
                                             self.rotary_base)
                k = rotary_embedding_rowwise(k, positions,
                                             self.rotary_base)
            else:
                positions = pos0 + jnp.arange(t)
                q, k = self._rope(q, positions), self._rope(k, positions)
        if ragged:
            write = lambda c, blk: jax.vmap(
                lambda ci, bi, p: jax.lax.dynamic_update_slice(
                    ci, bi, (0, p, 0)))(c, blk, pos0)
        else:
            write = lambda c, blk: jax.lax.dynamic_update_slice(
                c, blk, (0, 0, pos0, 0))
        cache, k_read, v_read = _write_kv(cache, k, v, write)
        h_kv = self.num_kv_heads
        rep = self.num_heads // h_kv
        qg = q.reshape(b, h_kv, rep, t, self.head_dim)
        scale = 1.0 / math.sqrt(self.head_dim)
        s = jnp.einsum("bgrtd,bgTd->bgrtT", qg, k_read,
                       preferred_element_type=jnp.float32) * scale
        ln = k_read.shape[2]
        if ragged:
            live = (jnp.arange(ln)[None, None, :]
                    <= (pos0[:, None] + jnp.arange(t)[None])[:, :, None])
            s = jnp.where(live[:, None, None], s, -jnp.inf)
        else:
            live = jnp.arange(ln)[None, :] <= (pos0 + jnp.arange(t))[:, None]
            s = jnp.where(live[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v_read.dtype)
        o = jnp.einsum("bgrtT,bgTd->bgrtd", p, v_read)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, self.embed_dim)
        o = self.out_proj(o.reshape(b * t, self.embed_dim).astype(x.dtype))
        return o.reshape(b, t, -1), cache

    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32, sharding=None, kv_dtype=None):
        """Zero PAGE-POOL buffers for paged serving: the tree forms of
        :meth:`init_cache` ((k, v), or the int8 4-tuple with f32 scale
        sidecars) in a layout of their own — each leaf is
        (max_pages, page_size, H_kv * D), a page's tokens as rows of
        all heads side by side (sidecars (max_pages, page_size, H_kv)).
        Page and offset, the two dimensions the KV write indexes, LEAD,
        so the write is an in-place scatter of whole rows into the
        donated leaf (see :func:`_write_kv_paged`), and at the widths
        served the merged minor dimension is whole 128-lane tiles
        (20 x 64 = 1280): a page takes exactly its logical bytes on a
        TPU. Heads sit in the LAST dimension, so the heads-sharded
        layout is parallel/tp.py ``kv_page_pool_spec``, not the dense
        cache's ``kv_pool_spec``. The page stays dimension 0: whatever
        treats a leaf as rows of pages (PagePool, copy_page, the host
        tier) is layout-blind."""
        shape = (max_pages, page_size, self.num_kv_heads * self.head_dim)
        return _kv_buffers(shape, shape[:-1] + (self.num_kv_heads,),
                           dtype, sharding, kv_dtype)

    def forward_step_paged(self, x_t, pool, tables, pos,
                           decode_attention="rows"):
        """One RAGGED decode step against a page pool: identical math
        to the ragged form of :meth:`forward_step`, but each row's KV
        row is the concatenation of the pool pages its block table
        names — the write scatters through ``tables`` and the read
        gathers through ``tables`` inside the same dispatch, so
        compiled shapes depend only on ``(max_pages, table_len,
        page_size)``. ``pos`` is the (B,) per-row position vector;
        rows parked on the scratch page (all-zero tables) are idle
        lanes whose output the caller ignores.

        ``decode_attention`` names how the one query token of a row
        meets its pages. ``"kernel"`` gathers nothing: behind the write
        a Pallas kernel (``ops/paged_attention.py``) walks each row's
        table to the row's own ``pos`` and reads the pages from the
        pool's leaves where they lie, the form for one TPU chip. The
        other two gather every slot of every row's table into a new
        array first (:func:`_write_kv_paged`): ``"rows"`` hands the
        gathered K and V out as rows of H_kv * D and contracts a
        block-diagonal q with them (:func:`_attend_pages_rows`: no
        re-lay of what was gathered; one device that is no TPU, and
        the kernel's parity reference); ``"heads"`` hands out the
        per-head view (:func:`_attend_pages_heads`: no collective
        under a heads-sharded pool, the form for a mesh). Whoever
        builds the program knows which it is; the engine decides from
        its mesh, its backend and the pool's leaves
        (``ContinuousBatchingEngine._decode_form``).
        :meth:`forward_chunk_paged`, many query tokens a row, attends
        by key blocks."""
        _known_decode_form(decode_attention)
        b = x_t.shape[0]
        with jax.named_scope("attn/qkv"):
            qkv = self.qkv(x_t.reshape(b, self.embed_dim)).reshape(b, 1, -1)
            q, k_t, v_t = self._split_kv_step(qkv)      # q (B,H,1,D)
            if self.rotary:
                q = rotary_embedding_rowwise(q, pos, self.rotary_base)
                k_t = rotary_embedding_rowwise(k_t, pos, self.rotary_base)
        if decode_attention == "kernel":
            from bigdl_tpu.ops.paged_attention import paged_attention

            pool = _scatter_kv_paged(pool, k_t, v_t, tables, pos)
            with jax.named_scope("attn/attend"):
                o = paged_attention(q[:, :, 0], *pool, tables, pos)
        else:
            pool, k_read, v_read = _write_kv_paged(
                pool, k_t, v_t, tables, pos,
                rows=decode_attention == "rows")
            o = _DECODE_ATTENTION[decode_attention](q[:, :, 0], k_read,
                                                    v_read, pos)
        with jax.named_scope("attn/out"):
            o = o.reshape(b, self.embed_dim).astype(x_t.dtype)
            o = self.out_proj(o).reshape(b, 1, -1)
        return o, pool

    def forward_chunk_paged(self, x, pool, tables, pos0):
        """RAGGED chunked prefill against a page pool (the paged twin
        of :meth:`forward_chunk` with a (B,) ``pos0``): each row's
        chunk scatters into its own pages and attends the pages the
        dispatch's rows hold, by key blocks under its own position
        mask (:func:`_attend_key_blocks`).

        CALLER CONTRACT (the paged form of forward_chunk's): every
        written position ``pos0 + i`` must fall inside the row's
        reserved pages — ``(pos0 + T) <= len(pages) * page_size`` per
        row. The engine reserves a request's full span at admission,
        and page-aligned reuse (``prefill_chunk % page_size == 0``)
        guarantees no chunk ever straddles into a SHARED page."""
        b, t, _ = x.shape
        with jax.named_scope("attn/qkv"):
            qkv = self.qkv(x.reshape(b * t, self.embed_dim)).reshape(
                b, t, -1)
            q, k, v = self._split_kv_step(qkv)
            positions = pos0[:, None] + jnp.arange(t)[None]  # (B, T)
            if self.rotary:
                q = rotary_embedding_rowwise(q, positions, self.rotary_base)
                k = rotary_embedding_rowwise(k, positions, self.rotary_base)
        pool = _scatter_kv_paged(pool, k, v, tables, positions)
        o = _attend_key_blocks(q, pool, tables, positions)
        with jax.named_scope("attn/out"):
            o = o.transpose(0, 2, 1, 3).reshape(b * t, self.embed_dim)
            o = self.out_proj(o.astype(x.dtype))
        return o.reshape(b, t, -1), pool

    @staticmethod
    def chunk_read_counts(pos0, t: int, page_size: int,
                          table_len: int) -> dict:
        """What :meth:`forward_chunk_paged` gathers for a dispatch whose
        rows' chunks of ``t`` tokens start at ``pos0`` (host arithmetic
        of :func:`_attend_key_blocks`' trip count and width, for the
        engine's span and counters; any mixer that walks its rows' pages
        by :func:`_key_block_pages` reads the same), summed over those
        rows: the tokens' worth of table slots gathered, and what the
        rows' whole tables hold."""
        width = _key_block_pages(page_size, table_len) * page_size
        whole = table_len * page_size
        reach = int(np.max(pos0)) + int(t)
        return {"kv_read_tokens":
                len(pos0) * min(-(-reach // width) * width, whole),
                "kv_table_tokens": len(pos0) * whole}

    @staticmethod
    def step_read_counts(pos, page_size: int, table_len: int,
                         decode_attention: str = "rows") -> dict:
        """What :meth:`forward_step_paged` reads of the pool for a
        dispatch whose rows stand at ``pos``, idle lanes (``pos`` 0)
        among them (host arithmetic, for the engine's span and
        counters), summed over the rows: the tokens' worth of pages its
        attention reads (the kernel: the pages up to each row's
        ``pos``; the gathered forms: every slot of every table), and
        what the rows' whole tables hold."""
        whole = len(pos) * table_len * page_size
        read = whole
        if decode_attention == "kernel":
            held = np.asarray(pos, np.int64) // page_size + 1
            read = int(held.sum()) * page_size
        return {"kv_read_tokens": read, "kv_table_tokens": whole}

    def _rope(self, x, positions):
        return rotary_embedding(x, positions, self.rotary_base) \
            if self.rotary else x

    def forward(self, input):
        b, t, _ = input.shape
        with jax.named_scope("attn/qkv"):
            qkv = self.qkv(input.reshape(b * t, self.embed_dim)).reshape(
                b, t, -1)
            q, k, v = self._split_kv_step(qkv)
            if self.rotary:
                pos0 = 0
                if self.sequence_parallel is not None:
                    # absolute positions of this shard's sequence block
                    pos0 = jax.lax.axis_index(self.sequence_parallel) * t
                positions = pos0 + jnp.arange(t)
                q, k = self._rope(q, positions), self._rope(k, positions)
        with jax.named_scope("attn/attend"):
            if self.sequence_parallel is not None:
                from bigdl_tpu.parallel.ring_attention import ring_attention

                # ring_attention handles GQA itself: the flash path
                # rotates the UN-expanded kv heads (group-factor less ICI
                # traffic), the dense path materializes them
                o = ring_attention(q, k, v,
                                   axis_name=self.sequence_parallel,
                                   causal=self.causal,
                                   use_flash=self.use_flash)
            elif self.use_flash:
                from bigdl_tpu.ops.flash_attention import flash_attention

                o = flash_attention(q, k, v, causal=self.causal)
            else:
                k, v = self._expand_kv(k, v)
                o = dot_product_attention(q, k, v, causal=self.causal)
        with jax.named_scope("attn/out"):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, self.embed_dim)
            o = self.out_proj(o.reshape(b * t, self.embed_dim)).reshape(
                b, t, -1)
            if self.dropout_p > 0:
                o = self.drop(o)
        return o


class TransformerBlock(Module):
    """Pre-norm block: x + MHA(LN(x)); x + MLP(LN(x)). GELU MLP sized
    ``mlp_ratio``× embed. ``n_experts > 0`` swaps the dense MLP for a
    top-k mixture of experts (parallel/moe.py MoEMLP). Read the summed
    load-balancing loss from ``TransformerLM.l_aux`` and the routing stats
    from ``TransformerLM.last_moe_stats`` (the model routes both through
    explicit outputs in every mode); the ``block.mlp.l_aux``/``last_stats``
    stashes are populated only when the BLOCK itself is called standalone
    via ``forward`` — ``forward_with_aux_stats`` (what TransformerLM uses)
    returns aux + stats instead of stashing, which is what keeps the remat
    path free of side-channel tracers."""

    def __init__(self, embed_dim: int, num_heads: int, mlp_ratio: int = 4,
                 dropout: float = 0.0, causal: bool = True,
                 sequence_parallel: Optional[str] = None,
                 use_flash: bool = False, n_experts: int = 0,
                 expert_parallel: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 rotary: bool = False):
        super().__init__()
        self.ln1 = LayerNorm(embed_dim)
        self.attn = MultiHeadAttention(embed_dim, num_heads, dropout=dropout,
                                       causal=causal,
                                       num_kv_heads=num_kv_heads,
                                       rotary=rotary,
                                       sequence_parallel=sequence_parallel,
                                       use_flash=use_flash)
        self.ln2 = LayerNorm(embed_dim)
        self.n_experts = n_experts
        if n_experts > 0:
            from bigdl_tpu.parallel.moe import MoEMLP

            self.mlp = MoEMLP(embed_dim, mlp_ratio * embed_dim, n_experts,
                              expert_parallel=expert_parallel)
        else:
            self.fc1 = Linear(embed_dim, mlp_ratio * embed_dim)
            self.fc2 = Linear(mlp_ratio * embed_dim, embed_dim)
        if dropout > 0:
            self.drop = Dropout(dropout)
        self.dropout_p = dropout

    def forward(self, input):
        if self.n_experts > 0:
            out, aux, stats = self.forward_with_aux_stats(input)
            self.mlp.l_aux = aux
            self.mlp.last_stats = stats
            return out
        return self._forward_impl(input)[0]

    def forward_with_aux(self, input):
        """(output, moe_aux_loss) with NO side-channel stash — the remat
        path must route the aux loss through explicit outputs (a stash
        inside jax.checkpoint leaves a dead tracer behind)."""
        out, aux, _ = self.forward_with_aux_stats(input)
        return out, aux

    def forward_with_aux_stats(self, input):
        """(output, moe_aux_loss, routing_stats_or_None) — stats follow the
        same explicit-output convention as the aux loss so they survive
        jax.checkpoint; see parallel/moe.py record_moe_metrics."""
        return self._forward_impl(input)

    def forward_step(self, x_t, cache, pos):
        """One decode step through the block with the attention KV cache
        ((k, v) from ``self.attn.init_cache``); returns (out, new_cache).
        Inference-time path: dropout off, MoE stats discarded."""
        h, cache = self.attn.forward_step(
            normed(self.ln1, x_t), cache, pos)
        return self._mlp_residual(x_t, h), cache

    def forward_prefill(self, x, cache, pos0: int = 0):
        """Batched prompt pass writing the attention cache (see
        MultiHeadAttention.forward_prefill)."""
        h, cache = self.attn.forward_prefill(
            normed(self.ln1, x), cache, pos0)
        return self._mlp_residual(x, h), cache

    def forward_chunk(self, x, cache, pos0):
        """Traced-offset chunk pass (see
        MultiHeadAttention.forward_chunk)."""
        h, cache = self.attn.forward_chunk(
            normed(self.ln1, x), cache, pos0)
        return self._mlp_residual(x, h), cache

    def forward_step_paged(self, x_t, pool, tables, pos,
                           decode_attention="rows"):
        """Paged decode step (see
        MultiHeadAttention.forward_step_paged)."""
        h, pool = self.attn.forward_step_paged(
            normed(self.ln1, x_t), pool, tables, pos,
            decode_attention=decode_attention)
        return self._mlp_residual(x_t, h), pool

    def forward_chunk_paged(self, x, pool, tables, pos0):
        """Paged ragged chunk pass (see
        MultiHeadAttention.forward_chunk_paged)."""
        h, pool = self.attn.forward_chunk_paged(
            normed(self.ln1, x), pool, tables, pos0)
        return self._mlp_residual(x, h), pool

    def _mlp_residual(self, x, attended):
        """``x + attended``, then the MLP branch on top of it."""
        with jax.named_scope("attn/out"):
            x = x + attended
        b, t, c = x.shape
        h = normed(self.ln2, x)
        with jax.named_scope("mlp"):
            if self.n_experts > 0:
                m, _, _ = self.mlp.forward_with_stats(h)
            else:
                m = self.fc2(jax.nn.gelu(
                    self.fc1(h.reshape(b * t, c)))).reshape(b, t, c)
            return x + m

    def _forward_impl(self, input):
        h = self.attn(normed(self.ln1, input))
        with jax.named_scope("attn/out"):
            x = input + h
        b, t, c = x.shape
        aux, stats = 0.0, None
        h = normed(self.ln2, x)
        with jax.named_scope("mlp"):
            if self.n_experts > 0:
                # MoEMLP flattens/restores internally
                h, aux, stats = self.mlp.forward_with_stats(h)
            else:
                h = self.fc1(h.reshape(b * t, c))
                h = jax.nn.gelu(h)
                h = self.fc2(h).reshape(b, t, c)
            if self.dropout_p > 0:
                h = self.drop(h)
            return x + h, aux, stats
