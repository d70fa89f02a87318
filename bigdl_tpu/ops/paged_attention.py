"""Pallas paged attention for the decode step (TPU kernel).

One query token a lane attends the pages that lane holds, read from the
page pool's leaves WHERE THEY LIE: a leaf stays (max_pages, page_size,
H_kv * D) in HBM as ``MultiHeadAttention.init_page_pool`` lays it out, and
the kernel walks each lane's block table to the lane's own length. The
XLA form (``nn/attention.py _write_kv_paged`` + ``_attend_pages_rows``)
gathers every slot of every lane's table into a new array first, scratch
slots and all, and reads that back: 35 times what the lanes hold at one
cell's load and 9 times at the other's (PERF.md, PR 44).

  grid = (lanes,), one lane a step, in order ("arbitrary": the page
  buffers and the slot that is being filled carry over from lane to lane)
  scalar prefetch (SMEM): ``pos`` (lanes,), ``tables`` flattened
  per lane: cdiv(pos + 1, block) rounds; a round's pages come by one
  async copy each into one of two VMEM buffers, only the pages that hold a
  key at or before ``pos``; while a round is scored the next round's pages
  (the next LANE's first, behind a lane's last round) are already on
  their way; scores in float32, keys past ``pos`` masked, running maximum,
  sum and float32 accumulator, ``p`` cast to V's dtype before P.V

q meets K and V as the XLA rows form does: spread onto a block diagonal
(H, H_kv * D), so both products run over whole rows of a page as stored
and no 64-wide head is ever sliced out of a 128-lane tile; each head keeps
its own D columns of the (H, H_kv * D) result. The same keys, the same
dtypes at the same places as ``_attend_pages_rows``; only the order of the
float32 sums differs.

On CPU tests the kernel runs in the TPU interpreter
(``ops/flash_attention.py default_interpret``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.flash_attention import default_interpret

#: keys a round scores at once: 8 pages of 16. K and V, two buffers each,
#: take 1.3 MB at GPT-2 Large's 1280 columns and 3.9 MB at Olmo's 3840
BLOCK_TOKENS = 128

_MASKED = -1e30


def block_pages(page_size: int, table_len: int) -> int:
    """Pages a round of the kernel holds: the whole pages of
    ``BLOCK_TOKENS`` keys, no more than a table has."""
    return max(1, min(table_len, BLOCK_TOKENS // page_size))


def supported(leaf) -> bool:
    """Whether the kernel can read a pool leaf (max_pages, page_size,
    H_kv * D) as it lies: a page has to be whole tiles of the chip's
    memory (128 columns; 8 rows of 4 bytes, 16 of 2), so that one page is
    one contiguous copy into a buffer's rows. The widths served are
    (16, 1280) and (16, 3840) in bfloat16; a toy model's (4, 16) is not,
    and keeps the gathered form."""
    _, page_size, width = leaf.shape
    rows = 32 // jnp.dtype(leaf.dtype).itemsize
    return (jnp.issubdtype(leaf.dtype, jnp.floating)
            and width % 128 == 0 and page_size % rows == 0)


def _kernel(pos_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref,
            *, table_len: int, page_size: int, pages: int, scale: float):
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    width = pages * page_size

    def each_page(lane, rnd, slot, act):
        # ``act`` on the copies of round ``rnd`` of ``lane`` into buffer
        # ``slot``: the pages that hold a key at or before the lane's pos
        left = pos_ref[lane] + 1 - rnd * width
        for i in range(pages):
            @pl.when(i * page_size < left)
            def _():
                page = tab_ref[lane * table_len + rnd * pages + i]
                rows = pl.ds(i * page_size, page_size)
                act(pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[slot, rows], sems.at[1, slot]))

                # a lane's only key needs no score (``lone_key``)
                @pl.when(pos_ref[lane] > 0)
                def _():
                    act(pltpu.make_async_copy(
                        k_hbm.at[page], k_buf.at[slot, rows],
                        sems.at[0, slot]))

    start = lambda copy: copy.start()
    wait = lambda copy: copy.wait()

    @pl.when(b == 0)
    def _():
        # rows of a buffer no copy of this call has filled are masked
        # keys: their p is 0, and 0 times what lies there must be 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        each_page(0, 0, 0, start)

    first = slot_ref[0]
    pos = pos_ref[b]
    rounds = (pos + width) // width
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def some_keys(rnd, _):
        slot = (first + rnd) % 2
        # what is scored next is fetched now: this lane's next round, or
        # the next lane's first one
        more = rnd + 1 < rounds
        nxt = jnp.where(more, b, b + 1)

        @pl.when(nxt < lanes)
        def _():
            each_page(nxt, jnp.where(more, rnd + 1, 0), 1 - slot, start)

        each_page(b, rnd, slot, wait)
        pl.when(pos == 0)(lambda: lone_key(slot))
        pl.when(pos > 0)(lambda: attend(rnd, slot))
        return ()

    def lone_key(slot):
        # one key: its softmax is 1 and the result its V whatever the
        # score, so K is not fetched and nothing is multiplied. What each
        # idle lane of a dispatch costs: 0.7 us against 1.0 (PERF.md, PR 44)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(
            v_buf[slot, 0:1, :].astype(jnp.float32), acc_ref.shape)

    def attend(rnd, slot):
        q = q_ref[0]
        s = jax.lax.dot_general(
            q, k_buf[slot].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        key = rnd * width + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(key <= pos, s, _MASKED)
        # key 0 is live in a lane's first round and every later round
        # starts at a live key: the maximum is a score from then on
        top = m_ref[...]
        new = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - new)
        shrink = jnp.exp(top - new)
        v = v_buf[slot]
        l_ref[...] = l_ref[...] * shrink + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = new

    jax.lax.fori_loop(0, rounds, some_keys, ())
    slot_ref[0] = (first + rounds) % 2
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _call(q_bd, k_pages, v_pages, tables, pos, head_dim, interpret):
    """The kernel over a block-diagonal ``q_bd`` (B, H', H_kv * D), H' whole
    sublane tiles of rows; (B, H', H_kv * D) in V's dtype. Jitted on its
    own so that a model's layers share ONE trace and one Mosaic compile of
    it: traced a layer, 36 layers' cold set-up read 180 s against 133
    (PERF.md, PR 44)."""
    lanes, heads, cols = q_bd.shape
    _, page_size, _ = k_pages.shape
    table_len = tables.shape[1]
    pages = block_pages(page_size, table_len)
    width = pages * page_size
    lane_block = pl.BlockSpec((1, heads, cols), lambda b, *_: (b, 0, 0))
    kernel = functools.partial(
        _kernel, table_len=table_len, page_size=page_size, pages=pages,
        scale=1.0 / math.sqrt(head_dim))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[lane_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane_block,
            scratch_shapes=[
                pltpu.VMEM((2, width, cols), k_pages.dtype),
                pltpu.VMEM((2, width, cols), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, cols), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q_bd.shape, v_pages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(pos.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      q_bd, k_pages, v_pages)


def paged_attention(q, k_pages, v_pages, tables, pos,
                    interpret: Optional[bool] = None):
    """One query token a lane over the pages its block table names:
    ``q`` (B, H, D), ``k_pages`` / ``v_pages`` the pool's leaves
    (max_pages, page_size, H_kv * D), ``tables`` (B, table_len) page ids
    (every id in ``[0, max_pages)``: ``_gather_pages``' caller contract),
    ``pos`` (B,) each lane's last live position: key ``i`` of lane ``b``
    lies at ``k_pages[tables[b, i // page_size], i % page_size]`` and is
    attended when ``i <= pos[b]``. Returns (B, H, D) in V's dtype.

    A lane costs the pages up to its ``pos`` (an idle lane on the scratch
    page, ``pos`` 0, one page), never its table."""
    b, h, d = q.shape
    cols = k_pages.shape[2]
    h_kv = cols // d
    if interpret is None:
        interpret = default_interpret()
    # whole sublane tiles of query rows: 8 of 4 bytes, 16 of 2
    rows = 32 // jnp.dtype(q.dtype).itemsize
    padded = -(-h // rows) * rows
    # head ``i``'s D values go to the columns of its own kv head and come
    # back from them, both ways as a product with a 0/1 matrix (D, H_kv * D)
    # under a mask: exact (every output is one value times 1.0), and whole
    # rows of H_kv * D all the way, where a reshape to (.., H_kv, D) makes
    # XLA re-lay every 64-wide head into padded tiles (0.67 of a 2.8 ms
    # step at GPT-2 Large's widths; PERF.md, PR 44)
    col = np.arange(cols)
    spread = col[None, :] % d == np.arange(d)[:, None]               # (D, C)
    own = np.arange(padded) // (h // h_kv)      # head -> its kv head
    on_diag = (col[None, :] // d == own[:, None]) \
        & (np.arange(padded)[:, None] < h)                         # (H', C)
    exact = jax.lax.Precision.HIGHEST
    q_bd = jnp.where(on_diag, jnp.einsum(
        "bhd,dc->bhc", jnp.pad(q, ((0, 0), (0, padded - h), (0, 0))),
        jnp.asarray(spread, q.dtype), precision=exact),
        jnp.zeros((), q.dtype))
    o_full = _call(q_bd, k_pages, v_pages, tables, pos, d, interpret)
    o_own = jnp.where(on_diag, o_full, jnp.zeros((), o_full.dtype))
    return jnp.einsum("bhc,dc->bhd", o_own[:, :h],
                      jnp.asarray(spread, o_full.dtype), precision=exact)
