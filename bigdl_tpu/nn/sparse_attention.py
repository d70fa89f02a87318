"""Block-sparse attention that chooses what it reads through a cache of
compressed keys (the MiniCPM4 family's trainable sparse attention, "InfLLM
v2": Xiao et al., arXiv:2506.07900 section 2.2, inference form).

Grouped-query softmax attention whose keys are cut into BLOCKS of
``block_size`` tokens. Beside K and V the layer keeps one COMPRESSED KEY a
``kernel_stride`` tokens: the mean of the ``kernel_size`` keys of a span
(no parameters). A query at position ``t`` of KV group ``g``:

1. scores every span that ends at or before ``t``: per head a softmax of
   ``q . C_j / sqrt(d)`` over the visible spans, summed over the group's
   heads;
2. gives block ``b`` the highest score of the spans that overlap it;
3. always takes the first ``init_blocks`` blocks and the blocks that hold
   the last ``window_size`` tokens (its own among them), and of the rest
   the highest-scored until ``topk`` blocks are taken in all (ties go to
   the earlier block);
4. attends, causal softmax at ``1 / sqrt(d)``, to the tokens ``s <= t`` of
   the blocks taken: every head of the group to the same blocks.

A query at ``t < dense_len`` attends to every ``s <= t``. The rule goes by
the query's POSITION (not by the length of a call), so a sequence
prefilled in chunks and extended token by token computes what one pass
over the whole of it computes.

Served over pages (``page_size`` = ``kernel_stride``): a pool entry is
``{"k": [...], "v": [...], "ck": leaf}``: one K and one V leaf a KV
group, (max_pages, page_size, head_dim), so that a group gathers its own
selection and nothing of the other's; ``ck`` (max_pages, groups *
head_dim) holds ONE compressed key a page, that of the span which ENDS in
the page. A page then holds only what the tokens up to its end determine:
a shared prefix's pages are valid for every sequence that shares them, and
the span that starts in the last shared page and ends in a request's own
page is the request's. The decode step reads the compressed keys of its
lane, picks, and gathers the pages of the blocks it took and no others
(:func:`~bigdl_tpu.nn.attention._gather_pages` given the selected table);
the prefill chunk walks the lane's pages by key blocks under each query
token's own selection as a mask.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.attention import RMSNorm, _gather_pages
from bigdl_tpu.nn.gated_delta import project
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.module import Module, scoped

#: pages of keys the prefill chunk scores at once (a chunk's scores over a
#: whole long lane do not fit: they are formed by key blocks)
KEY_PAGES = 64
_SCRATCH = 0


class BlockSparseAttention(Module):
    """``embed_dim`` -> ``num_heads`` query heads over ``num_kv_heads`` KV
    heads of ``head_dim``; q and k RMS-normed per head (one gain of
    ``head_dim`` each), no rotation; ``out(o * sigmoid(gate(x)))``. No
    biases; float32 out whatever the weights' dtype (:func:`project`)."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, kernel_size: int = 32,
                 kernel_stride: int = 16, block_size: int = 64,
                 topk: int = 64, init_blocks: int = 1,
                 window_size: int = 2048, dense_len: int = 8192,
                 norm_eps: float = 1e-6):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not a multiple of "
                             f"num_kv_heads {num_kv_heads}")
        if kernel_size % kernel_stride or block_size % kernel_stride:
            raise ValueError(
                f"kernel_size {kernel_size} and block_size {block_size} "
                f"must be multiples of kernel_stride {kernel_stride}")
        if init_blocks + -(-window_size // block_size) + 1 > topk:
            raise ValueError(
                f"topk {topk} does not hold the {init_blocks} first blocks "
                f"and a window of {window_size} tokens")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.kernel_size, self.kernel_stride = kernel_size, kernel_stride
        self.block_size, self.topk = block_size, topk
        self.init_blocks, self.window_size = init_blocks, window_size
        self.dense_len = dense_len
        #: pages a span covers, pages a block covers
        self.span_pages = kernel_size // kernel_stride
        self.block_pages = block_size // kernel_stride
        kv = num_kv_heads * head_dim
        self.qkv = Linear(embed_dim, num_heads * head_dim + 2 * kv,
                          with_bias=False)
        self.gate = Linear(embed_dim, num_heads * head_dim, with_bias=False)
        self.out_proj = Linear(num_heads * head_dim, embed_dim,
                               with_bias=False)
        self.q_norm = RMSNorm(head_dim, norm_eps)
        self.k_norm = RMSNorm(head_dim, norm_eps)

    # ------------------------------------------------------------- the rule
    def attended_tokens(self, positions) -> np.ndarray:
        """Tokens a query at each of ``positions`` attends to (host
        arithmetic: what the rule reads, whatever implements it)."""
        t = np.asarray(positions, np.int64)
        taken = np.minimum(self.topk, t // self.block_size + 1)
        sparse = taken * self.block_size - (
            self.block_size - 1 - t % self.block_size)
        return np.where(t < self.dense_len, t + 1, sparse)

    def _max_taken(self, n_blocks: int) -> int:
        return min(n_blocks, max(self.topk,
                                 -(-self.dense_len // self.block_size)))

    def _step_blocks(self, n_blocks: int):
        """Blocks the decode step gathers for every row, and the further
        ones it gathers for a row under ``dense_len`` (which may take
        more than ``topk``)."""
        k = self._max_taken(n_blocks)
        return min(k, self.topk), k - min(k, self.topk)

    def gathered_tokens(self, positions, table_pages: int) -> np.ndarray:
        """Tokens' worth of K and V pages the decode step gathers for a row
        at each of ``positions`` through a table of ``table_pages`` pages
        (host arithmetic of :meth:`forward_step_paged`'s two gathers;
        scratch pages that fill a short row's list count too)."""
        t = np.asarray(positions, np.int64)
        every, short = self._step_blocks(
            -(-int(table_pages) // self.block_pages))
        return (every + np.where(t < self.dense_len, short, 0)) \
            * self.block_size

    def block_keys(self, q, ck, t):
        """What the selection sorts by. ``q`` (B, G, R, T, D) the group's
        query heads, ``ck`` (B, G, P, D) the compressed keys by page (row
        ``p``: the span that ends in page ``p``), ``t`` (B, T) the
        queries' positions; P a multiple of the pages a block covers.
        Returns (B, G, T, P / block_pages) float32: a block's score, +inf
        where the block is always taken (all of them under ``dense_len``),
        -inf where the query does not see it."""
        d, pages = q.shape[-1], ck.shape[2]
        m, bp = self.span_pages, self.block_pages
        nb = pages // bp
        s = jnp.einsum("bgrtd,bgpd->bgrtp", q, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        page = jnp.arange(pages)
        seen = ((page >= m - 1)[None, None]
                & (((page + 1) * self.kernel_stride - 1)[None, None]
                   <= t[:, :, None]))                        # (B, T, P)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
        den = jnp.sum(e, axis=-1, keepdims=True)
        score = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=2)
        # a block's spans end in its own pages and the next m - 1
        padded = jnp.pad(score, ((0, 0),) * 3 + ((0, m - 1),))
        by_block = padded[..., 0::bp][..., :nb]
        for i in range(1, bp + m - 1):
            by_block = jnp.maximum(by_block, padded[..., i::bp][..., :nb])
        blk = jnp.arange(nb)
        own = t // self.block_size                           # (B, T)
        window = jnp.maximum(t - self.window_size + 1, 0) // self.block_size
        always = ((blk < self.init_blocks)[None, None]
                  | (blk[None, None] >= window[..., None])
                  | (t < self.dense_len)[..., None])
        visible = blk[None, None] <= own[..., None]
        return jnp.where(visible[:, None],
                         jnp.where(always[:, None], jnp.inf, by_block),
                         -jnp.inf)

    def take_blocks(self, keys, t):
        """``keys`` (B, G, T, NB) from :meth:`block_keys` -> ``(idx, keep)``
        (B, G, T, K): the blocks taken in order of their key (ties to the
        earlier block) and which of the K are real."""
        k = self._max_taken(keys.shape[-1])
        vals, idx = jax.lax.top_k(keys, k)
        limit = jnp.where(t < self.dense_len, k, min(k, self.topk))
        keep = ((jnp.arange(k)[None, None, None] < limit[:, None, :, None])
                & (vals > -jnp.inf))
        return idx, keep

    def block_mask(self, keys, t):
        """``keys`` (B, G, T, NB) -> (B, G, T, NB) bool: the blocks each
        query takes (what :meth:`take_blocks` lists, as a mask). A block
        is taken when fewer than the query's limit stand before it in the
        order of :meth:`take_blocks` (a higher key, or the same key and an
        earlier block): a count of comparisons, where sorting a chunk's
        keys cost more than its attention (PERF.md, PR 37)."""
        nb = keys.shape[-1]
        k = self._max_taken(nb)
        blk = jnp.arange(nb)
        mine, other = keys[..., :, None], keys[..., None, :]
        before = (other > mine) | ((other == mine)
                                   & (blk[None, :] < blk[:, None]))
        rank = jnp.sum(before, axis=-1, dtype=jnp.int32)
        limit = jnp.where(t < self.dense_len, k, min(k, self.topk))
        return (keys > -jnp.inf) & (rank < limit[:, None, :, None])

    # ---------------------------------------------------------------- pieces
    @scoped("attn/qkv")
    def _qkv(self, x):
        """(..., embed) -> q (..., H, D), k, v (..., G, D) float32, q and k
        normed per head."""
        lead = x.shape[:-1]
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim
        y = project(self.qkv, x)
        q = self.q_norm(y[..., :h * d].reshape(lead + (h, d)))
        k = self.k_norm(y[..., h * d:(h + g) * d].reshape(lead + (g, d)))
        return q, k, y[..., (h + g) * d:].reshape(lead + (g, d))

    def _grouped(self, q):
        """(B, T, H, D) -> (B, G, R, T, D)."""
        b, t, h, d = q.shape
        g = self.num_kv_heads
        return q.reshape(b, t, g, h // g, d).transpose(0, 2, 3, 1, 4)

    @scoped("attn/out")
    def _output(self, o, x):
        """(..., H * D) attention output and the layer's input -> the
        layer's output."""
        return project(self.out_proj, o.astype(jnp.float32)
                       * jax.nn.sigmoid(project(self.gate, x)))

    def _span_means(self, sums):
        """Page sums (..., n + m - 1, D) -> the means of the n spans that
        end in the last n pages."""
        m = self.span_pages
        n = sums.shape[-2] - (m - 1)
        return sum(sums[..., i:i + n, :] for i in range(m)) \
            / float(self.kernel_size)

    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32):
        if page_size != self.kernel_stride:
            raise ValueError(
                f"a page holds one compressed key: page_size {page_size} "
                f"must equal kernel_stride {self.kernel_stride}")
        g, d = self.num_kv_heads, self.head_dim
        leaf = lambda: jnp.zeros((max_pages, page_size, d), dtype)
        return {"k": [leaf() for _ in range(g)],
                "v": [leaf() for _ in range(g)],
                "ck": jnp.zeros((max_pages, g * d), dtype)}

    def _whole_blocks(self, tables):
        """Block tables padded (scratch page) to whole blocks."""
        return jnp.pad(tables, ((0, 0),
                                (0, -tables.shape[1] % self.block_pages)))

    def _compressed(self, ck, tables):
        """The lane's compressed keys through its table: (B, G, P, D).
        Clips as :func:`_gather_pages` does, under its contract on the
        ids (``ck`` holds one row a page: not that function's shape)."""
        b, pages = tables.shape
        return jnp.take(ck, tables, axis=0, mode="clip").reshape(
            b, pages, self.num_kv_heads, self.head_dim).transpose(0, 2, 1, 3)

    # ------------------------------------------------------------ the forms
    def forward_step_paged(self, x_t, pool, tables, pos):
        """One token a row: ``x_t`` (B, embed) at ``pos`` (B,). Writes the
        token's K and V, the compressed key of the page it completes (if
        it does), then selects and attends over the selected pages."""
        b = x_t.shape[0]
        g, d, ps = self.num_kv_heads, self.head_dim, self.kernel_stride
        m, bp = self.span_pages, self.block_pages
        dtype = pool["ck"].dtype
        q, k, v = self._qkv(x_t)
        with jax.named_scope("attn/kv_write"):
            p_now, off = pos // ps, pos % ps
            pg = jnp.take_along_axis(tables, p_now[:, None], axis=1)[:, 0]
            ks = [leaf.at[pg, off].set(k[:, i].astype(dtype))
                  for i, leaf in enumerate(pool["k"])]
            vs = [leaf.at[pg, off].set(v[:, i].astype(dtype))
                  for i, leaf in enumerate(pool["v"])]
        with jax.named_scope("sparse/select"):
            # the span that ends with this token's page, from the pool
            span = jnp.take_along_axis(tables, jnp.maximum(
                p_now[:, None] - (m - 1) + jnp.arange(m)[None], 0), axis=1)
            mean = jnp.concatenate([
                jnp.mean(_gather_pages(leaf, span).astype(jnp.float32), 1)
                for leaf in ks], axis=-1)                    # (B, G * D)
            ends = (off == ps - 1) & (p_now >= m - 1)
            ck = pool["ck"].at[jnp.where(ends, pg, _SCRATCH)].set(
                mean.astype(dtype))
            tables = self._whole_blocks(tables)
            q5 = self._grouped(q[:, None]).astype(dtype)
            keys = self.block_keys(q5, self._compressed(ck, tables),
                                   pos[:, None])
            idx, keep = self.take_blocks(keys, pos[:, None])
            idx, keep = idx[:, :, 0], keep[:, :, 0]          # (B, G, K)
            pages = (idx[..., None] * bp + jnp.arange(bp)).reshape(b, g, -1)
            keep = jnp.repeat(keep, bp, axis=-1)
            taken = jnp.where(keep, jnp.take_along_axis(
                jnp.broadcast_to(tables[:, None], (b, g, tables.shape[1])),
                pages, axis=-1), _SCRATCH)
            at = (pages[..., None] * ps + jnp.arange(ps)).reshape(b, g, -1)
            live = jnp.repeat(keep, ps, axis=-1) & (at <= pos[:, None, None])
        with jax.named_scope("sparse/attend"):
            o = self._attend_selected(q5[:, :, :, 0], ks, vs, taken, live,
                                      pos)
        return self._output(o.reshape(b, -1), x_t), \
            {"k": ks, "v": vs, "ck": ck}

    def _attend_selected(self, q, ks, vs, taken, live, pos):
        """The decode step's attention: ``q`` (B, G, R, D) over the pages
        ``taken`` (B, G, K * block_pages) lists in the order of
        :meth:`take_blocks`, ``live`` (B, G, tokens) the tokens of them that
        count. The first ``topk`` blocks' pages are gathered for every row
        at once: all a row at or over ``dense_len`` takes. A row under it
        may take more; the further pages are gathered for those rows alone,
        one at a time (a loop as long as there are such rows: none, in a
        step whose rows all select), and the two parts joined by their
        maxima and sums. Returns (B, G, R, D) float32."""
        b, g, r, d = q.shape
        ps, dtype = self.kernel_stride, ks[0].dtype
        every, short = self._step_blocks(taken.shape[-1] // self.block_pages)
        cut = every * self.block_pages

        def part(q, tb, ok):
            """(n, G, R, D) over pages (n, G, P) -> maximum, sum, values;
            a group gathers from its own leaves."""
            out = []
            for i in range(g):
                k_sel = _gather_pages(ks[i], tb[:, i])       # (n, N, D)
                v_sel = _gather_pages(vs[i], tb[:, i])
                s = jnp.einsum("brd,bnd->brn", q[:, i], k_sel,
                               preferred_element_type=jnp.float32
                               ) / math.sqrt(d)
                s = jnp.where(ok[:, i, None], s, -jnp.inf)
                top = jnp.max(s, axis=-1)
                p = jnp.exp(s - jnp.where(jnp.isfinite(top), top,
                                          0.0)[..., None])
                out.append((top, jnp.sum(p, axis=-1), jnp.einsum(
                    "brn,bnd->brd", p.astype(dtype), v_sel,
                    preferred_element_type=jnp.float32)))
            return tuple(jnp.stack(x, axis=1) for x in zip(*out))

        top, den, acc = part(q, taken[..., :cut], live[..., :cut * ps])
        if short:
            under = pos < self.dense_len
            order = jnp.argsort(~under)        # the rows under it first

            def one_row(j, carry):
                i = order[j]
                row = lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, 0)
                new = part(row(q), row(taken)[..., cut:],
                           row(live)[..., cut * ps:])
                return tuple(jax.lax.dynamic_update_slice_in_dim(c, n, i, 0)
                             for c, n in zip(carry, new))

            top2, den2, acc2 = jax.lax.fori_loop(
                0, jnp.sum(under), one_row,
                (jnp.full_like(top, -jnp.inf), jnp.zeros_like(den),
                 jnp.zeros_like(acc)))
            both = jnp.maximum(top, top2)
            safe = jnp.where(jnp.isfinite(both), both, 0.0)
            w, w2 = jnp.exp(top - safe), jnp.exp(top2 - safe)
            den = den * w + den2 * w2
            acc = acc * w[..., None] + acc2 * w2[..., None]
        return acc / jnp.where(den > 0, den, 1.0)[..., None]

    def forward_chunk_paged(self, x, pool, tables, pos0):
        """A chunk a row: ``x`` (B, T, embed) whose first token stands at
        ``pos0`` (B,). CALLER CONTRACT beside ``MultiHeadAttention
        .forward_chunk_paged``'s: ``pos0`` and T are whole pages."""
        b, t, _ = x.shape
        ps, m = self.kernel_stride, self.span_pages
        dtype = pool["ck"].dtype
        if t % ps:
            raise ValueError(f"a chunk of {t} tokens is not whole pages "
                             f"of {ps}")
        q, k, v = self._qkv(x)
        with jax.named_scope("attn/kv_write"):
            positions = pos0[:, None] + jnp.arange(t)[None]
            pg = jnp.take_along_axis(tables, positions // ps, axis=1)
            off = positions % ps
            ks = [leaf.at[pg, off].set(k[:, :, i].astype(dtype))
                  for i, leaf in enumerate(pool["k"])]
            vs = [leaf.at[pg, off].set(v[:, :, i].astype(dtype))
                  for i, leaf in enumerate(pool["v"])]
        q5 = self._grouped(q).astype(dtype)
        with jax.named_scope("sparse/select"):
            # the spans that end in this chunk's pages, from the pool
            n = t // ps
            first = pos0 // ps
            span = jnp.take_along_axis(tables, jnp.maximum(
                first[:, None] - (m - 1) + jnp.arange(n + m - 1)[None], 0),
                axis=1)
            sums = jnp.concatenate([
                jnp.take(leaf, span, axis=0, mode="clip")
                .astype(jnp.float32).sum(2)
                for leaf in ks], axis=-1)          # (B, n + m - 1, G * D)
            own = jnp.take_along_axis(
                tables, first[:, None] + jnp.arange(n)[None], axis=1)
            ck = pool["ck"].at[own].set(self._span_means(sums).astype(dtype))
            tables = self._whole_blocks(tables)
            keys = self.block_keys(q5, self._compressed(ck, tables),
                                   positions)
            taken = self.block_mask(keys, positions)        # (B, G, T, NB)
        with jax.named_scope("sparse/attend"):
            o = self._attend_by_key_blocks(q5, ks, vs, tables, positions,
                                           taken)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, -1)
        return self._output(o, x), {"k": ks, "v": vs, "ck": ck}

    def _attend_by_key_blocks(self, q5, ks, vs, tables, positions, taken):
        """Causal softmax of ``q5`` (B, G, R, T, D) over the lane's pages
        under ``taken`` (B, G, T, NB), ``KEY_PAGES`` pages of keys at a
        time with a running maximum and sum, up to the last page any row
        of the dispatch has reached. Returns (B, G, R, T, D) float32."""
        b, g, r, t, d = q5.shape
        ps, bp = self.kernel_stride, self.block_pages
        kp = min(tables.shape[1], -(-KEY_PAGES // bp) * bp)
        tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % kp)))
        taken = jnp.pad(taken, ((0, 0),) * 3
                        + ((0, tables.shape[1] // bp - taken.shape[-1]),))
        width = kp * ps
        rounds = (jnp.max(positions) + width) // width

        def some_keys(i, carry):
            top, den, acc = carry
            tb = jax.lax.dynamic_slice_in_dim(tables, i * kp, kp, axis=1)
            k_i = jnp.stack([_gather_pages(leaf, tb) for leaf in ks], 1)
            v_i = jnp.stack([_gather_pages(leaf, tb) for leaf in vs], 1)
            s = jnp.einsum("bgrtd,bgnd->bgrtn", q5, k_i,
                           preferred_element_type=jnp.float32) / math.sqrt(d)
            at = i * width + jnp.arange(width)
            ok = (at[None, None] <= positions[:, :, None])[:, None] \
                & jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    taken, i * (kp // bp), kp // bp, axis=3),
                    self.block_size, axis=3)                 # (B, G, T, N)
            s = jnp.where(ok[:, :, None], s, -jnp.inf)
            new = jnp.maximum(top, jnp.max(s, axis=-1))
            safe = jnp.where(jnp.isfinite(new), new, 0.0)
            p = jnp.exp(s - safe[..., None])
            shrink = jnp.exp(top - safe)
            den = den * shrink + jnp.sum(p, axis=-1)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bgrtn,bgnd->bgrtd", p.astype(q5.dtype), v_i,
                preferred_element_type=jnp.float32)
            return new, den, acc

        top, den, acc = jax.lax.fori_loop(0, rounds, some_keys, (
            jnp.full((b, g, r, t), -jnp.inf, jnp.float32),
            jnp.zeros((b, g, r, t), jnp.float32),
            jnp.zeros((b, g, r, t, d), jnp.float32)))
        return acc / jnp.where(den > 0, den, 1.0)[..., None]

    def _whole(self, input):
        """q, k, v of a whole sequence with no cache and the blocks each
        query takes (B, G, T, NB)."""
        b, t, _ = input.shape
        g, d, ps = self.num_kv_heads, self.head_dim, self.kernel_stride
        m = self.span_pages
        q, k, v = self._qkv(input)
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        kp = jnp.pad(k, ((0, 0), (0, -t % self.block_size), (0, 0), (0, 0)))
        sums = kp.reshape(b, -1, ps, g, d).sum(2)            # (B, P, G, D)
        sums = jnp.pad(sums, ((0, 0), (m - 1, 0), (0, 0), (0, 0)))
        ck = self._span_means(jnp.moveaxis(sums, 2, 1))      # (B, G, P, D)
        q5 = self._grouped(q)
        keys = self.block_keys(q5, ck, positions)
        return q5, k, v, self.block_mask(keys, positions)

    def selected_blocks(self, input):
        """The blocks each position of a whole sequence (B, T, embed)
        takes: (B, G, T, NB) bool."""
        return self._whole(input)[3]

    def forward(self, input):
        """A whole sequence with no cache (B, T, embed): the same rule,
        the selection as a mask over dense scores."""
        b, t, _ = input.shape
        q5, k, v, taken = self._whole(input)
        taken = jnp.repeat(taken, self.block_size, axis=-1)[..., :t]
        ok = taken & (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
        s = jnp.einsum("bgrtd,bngd->bgrtn", q5, k) / math.sqrt(self.head_dim)
        p = jax.nn.softmax(jnp.where(ok[:, :, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrtn,bngd->bgrtd", p, v)
        return self._output(o.transpose(0, 3, 1, 2, 4).reshape(b, t, -1),
                            input)
