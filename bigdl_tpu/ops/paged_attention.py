"""Pallas paged attention for the decode step (TPU kernel).

One query token a lane attends the pages that lane holds, read from the
page pool's leaves WHERE THEY LIE: a leaf stays (max_pages, page_size,
columns) in HBM as ``init_page_pool`` lays it out, and the kernel walks
each lane's block table to the lane's own length. The XLA forms
(``nn/attention.py _write_kv_paged`` + ``_attend_pages_rows``;
``nn/latent_attention.py _attend_rows`` over ``_gather_pages``) gather
every slot of every lane's table into a new array first, scratch slots and
all, and read that back: 35 and 9 times what the lanes hold at two cells'
loads (PERF.md, PR 44), 13 times at a third's, twice over (PR 49).

  grid = (lanes,), one lane a step, in order ("arbitrary": the page
  buffers and the slot that is being filled carry over from lane to lane)
  scalar prefetch (SMEM): ``pos`` (lanes,), ``tables`` flattened
  per lane: cdiv(pos + 1, block) rounds; a round's pages come by one
  async copy each into one of two VMEM buffers, only the pages that hold a
  key at or before ``pos``; while a round is scored the next round's pages
  (the next LANE's first, behind a lane's last round) are already on
  their way; scores in float32, keys past ``pos`` masked, running maximum,
  sum and float32 accumulator, ``p`` cast to V's dtype before P.V

One walk, two forms of pool entry, told apart by the entry's shape when the
program is traced:

* **a K and V pair** (``paged_attention``; full attention, H_kv * D
  columns): q meets K and V as the XLA rows form does, spread onto a block
  diagonal (H, H_kv * D), so both products run over whole rows of a page as
  stored and no 64-wide head is ever sliced out of a 128-lane tile; each
  head keeps its own D columns of the (H, H_kv * D) result. Rounds of
  ``BLOCK_TOKENS`` keys, a buffer pair a leaf.
* **ONE leaf whose rows are key and value at once** (``
  paged_latent_attention``; latent attention's absorbed step): the query is
  already whole rows of the leaf and there is no kv-head axis, so no block
  diagonal surrounds the call; a page is copied ONCE and both products read
  that copy. A page is a small copy here (20 KB at 640 bfloat16 columns
  against 41 and 123 KB), so what a round costs beside its copies weighs
  more, and a round is as wide as a VMEM budget allows
  (``row_block_pages``); its live pages come page by page in a loop.

The same keys, the same dtypes at the same places as the rows forms; only
the order of the float32 sums differs.

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


#: what the two page buffers of a ONE-leaf round may take of VMEM (a pair's
#: round stays ``BLOCK_TOKENS``)
ROUND_BUFFER_BYTES = 4 << 20
#: ... and the most keys such a round holds (2048 read slower at 4 and at 13
#: live lanes of 32: PERF.md, PR 49)
ROUND_TOKENS_MAX = 1024


def row_block_pages(leaf, table_len: int) -> int:
    """Pages a round holds where the pool's entry is ONE leaf (max_pages,
    page_size, C): as many keys as two buffers of ``ROUND_BUFFER_BYTES``
    take, a power of two times ``BLOCK_TOKENS`` and at most
    ``ROUND_TOKENS_MAX``; whole pages, no more than a table has."""
    _, page_size, cols = leaf.shape
    fit = ROUND_BUFFER_BYTES // (2 * cols * jnp.dtype(leaf.dtype).itemsize)
    tokens = BLOCK_TOKENS
    while 2 * tokens <= min(fit, ROUND_TOKENS_MAX):
        tokens *= 2
    return max(1, min(table_len, tokens // page_size))


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


def _kernel(pos_ref, tab_ref, q_ref, *refs, table_len: int, page_size: int,
            pages: int, scale: float, one_leaf: bool):
    """``refs``: the leaves in HBM, the output block, a pair of page
    buffers a leaf, then the semaphores (a leaf's copies, a buffer), the
    buffer the next lane starts in, and the running maximum, sum and
    accumulator. ``one_leaf``: the values are the keys' rows (a latent
    layer's pool), so a page is copied once and both products read it."""
    if one_leaf:
        v_hbm, o_ref, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref = refs
        k_hbm, k_buf = v_hbm, v_buf
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, m_ref, l_ref,
         acc_ref) = refs
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    width = pages * page_size

    def each_page(lane, rnd, slot, act):
        # ``act`` on the copies of round ``rnd`` of ``lane`` into buffer
        # ``slot``: the pages that hold a key at or before the lane's pos
        left = pos_ref[lane] + 1 - rnd * width

        def page_copies(i, rows):
            page = tab_ref[lane * table_len + rnd * pages + i]
            act(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, rows], sems.at[1, slot]))
            if one_leaf:
                return

            # a lane's only key needs no score (``lone_key``)
            @pl.when(pos_ref[lane] > 0)
            def _():
                act(pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[slot, rows], sems.at[0, slot]))

        if one_leaf:
            # a loop over the live pages, not unrolled: a round is up to 64
            # pages here, and every copy traced is set-up (PERF.md, PR 49)
            live = jnp.clip((left + page_size - 1) // page_size, 0, pages)
            jax.lax.fori_loop(0, live, lambda i, _: page_copies(i, pl.ds(
                pl.multiple_of(i * page_size, page_size), page_size)), None)
        else:
            for i in range(pages):
                pl.when(i * page_size < left)(functools.partial(
                    page_copies, i, pl.ds(i * page_size, page_size)))

    start = lambda copy: copy.start()
    wait = lambda copy: copy.wait()

    @pl.when(b == 0)
    def _():
        # rows of a buffer no copy of this call has filled are masked
        # keys: their p is 0, and 0 times what lies there must be 0
        if not one_leaf:
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


def _walk(q, leaves, tables, pos, *, pages: int, scale: float, interpret,
          one_leaf: bool):
    """The kernel over ``q`` (B, H', C), H' whole sublane tiles of rows and
    C a row of the leaves, ``pages`` pages a round: the K and V pair, or
    (``one_leaf``) the one leaf whose rows are both; (B, H', C) in the
    values' dtype."""
    lanes, heads, cols = q.shape
    page_size = leaves[0].shape[1]
    table_len = tables.shape[1]
    width = pages * page_size
    lane_block = pl.BlockSpec((1, heads, cols), lambda b, *_: (b, 0, 0))
    kernel = functools.partial(
        _kernel, table_len=table_len, page_size=page_size, pages=pages,
        scale=scale, one_leaf=one_leaf)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes,),
            in_specs=[lane_block, *(pl.BlockSpec(memory_space=pl.ANY)
                                    for _ in leaves)],
            out_specs=lane_block,
            scratch_shapes=[
                *(pltpu.VMEM((2, width, cols), leaf.dtype)
                  for leaf in leaves),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, cols), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, leaves[-1].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_latent_attention" if one_leaf else "paged_attention",
    )(pos.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      q, *leaves)


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _call(q_bd, k_pages, v_pages, tables, pos, head_dim, interpret):
    """The pair's form over a block-diagonal ``q_bd`` (B, H', H_kv * D).
    Jitted on its own so that a model's layers share ONE trace and one
    Mosaic compile of it: traced a layer, 36 layers' cold set-up read
    180 s against 133 (PERF.md, PR 44)."""
    return _walk(q_bd, (k_pages, v_pages), tables, pos,
                 pages=block_pages(k_pages.shape[1], tables.shape[1]),
                 scale=1.0 / math.sqrt(head_dim), interpret=interpret,
                 one_leaf=False)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _call_rows(q, pages, tables, pos, scale, interpret):
    """The one-leaf form over whole-row queries ``q`` (B, H', C); jitted on
    its own as :func:`_call` is."""
    return _walk(q, (pages,), tables, pos,
                 pages=row_block_pages(pages, tables.shape[1]), scale=scale,
                 interpret=interpret, one_leaf=True)


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


def paged_latent_attention(q, pages, tables, pos, scale: float,
                           interpret: Optional[bool] = None):
    """One query token a lane over the pages its block table names, where
    a cached row is key and value at once (``nn/latent_attention.py``'s
    absorbed decode step): ``q`` (B, H, C) whole rows of the pool's ONE
    leaf ``pages`` (max_pages, page_size, C), ``tables`` and ``pos`` as
    :func:`paged_attention`'s. ``softmax_i(scale * q . row_i) @ rows`` over
    the keys ``i <= pos[b]``, (B, H, C) in the leaf's dtype.

    No block diagonal surrounds it: the heads have no axis in the leaf. A
    round's pages are copied once and both products read that copy."""
    h = q.shape[1]
    if interpret is None:
        interpret = default_interpret()
    # whole sublane tiles of query rows, as above (the served 32 heads are)
    rows = 32 // jnp.dtype(q.dtype).itemsize
    q = jnp.pad(q, ((0, 0), (0, -h % rows), (0, 0)))
    return _call_rows(q, pages, tables, pos, float(scale), interpret)[:, :h]
