"""The block-sparse attention layer: its selection is the plain reference's
(``benchmark/reference/minicpm_sala.py``: an argsort over dense scores) at a
size where sequences cross ``dense_len`` and ``topk`` binds; the paged forms
(a chunk by key blocks under each token's mask, a decode step that gathers
the selected pages only) compute what one pass over the whole sequence
computes, also where a sequence starts on pages another filled."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from bigdl_tpu.nn import sparse_attention as sa  # noqa: E402
from bigdl_tpu.nn.sparse_attention import BlockSparseAttention  # noqa: E402
from sala_tiny import SPARSE, built, tiny_config  # noqa: E402

D, H, G, HD = 32, 4, 2, 8
PS = SPARSE["kernel_stride"]


def mixer(seed=0, **sizes):
    rng = np.random.default_rng(seed)
    m = BlockSparseAttention(D, H, G, HD, **dict(SPARSE, **sizes))
    m.evaluate()
    tree = jax.tree.map(
        lambda a: jnp.asarray(0.4 * rng.standard_normal(a.shape), jnp.float32),
        m.params_dict())
    for norm in ("q_norm", "k_norm"):       # peaked attention, random gains
        tree[norm]["~params"]["weight"] = jnp.asarray(
            rng.uniform(1.5, 3.0, (HD,)), jnp.float32)
    m.load_params_dict(tree)
    return m


def jitted(m):
    """The two paged forms compiled (the weights as constants)."""
    return jax.jit(m.forward_chunk_paged), jax.jit(m.forward_step_paged)


def rows(t, seed=1, b=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((b, t, D)),
                       jnp.float32)


def test_the_selection_is_the_references():
    """Layer 0 of a model whose first held layer is sparse: what the
    program's mixer takes at every position of 200 tokens against the
    reference's argsort. Past position 64 six blocks are taken of up to
    thirteen: the first, three of the window, two by score."""
    from benchmark.reference import minicpm_sala as ref

    cfg = tiny_config(first=0)
    model, w = built(cfg, 3)
    ids = np.random.RandomState(0).randint(0, 120, (2, 200))
    taken = []
    ref.forward(w, ids, cfg, taken=taken)
    blk = model.block0
    x = model._embed(jnp.asarray(ids))
    got = np.asarray(blk.mixer.selected_blocks(blk._enter(x)))
    want = np.stack([t[0] for t in taken])          # (rows, G, T, NB)
    assert got.shape == want.shape == (2, 2, 200, 13)
    assert np.array_equal(got, want)
    late = want[:, :, 100:]
    assert (late.sum(-1) == 6).all()                # topk binds
    assert late[..., 0].all()                       # the first block
    assert not np.array_equal(want[:, 0], want[:, 1])   # a group's own choice
    by_score = late.copy()
    by_score[..., 0] = False
    for t in range(100, 200):                       # less the window's
        by_score[:, :, t - 100, (t - 31) // 16:] = False
    assert (by_score.sum(-1) >= 2).all() and by_score.any(axis=(0, 1, 2))[1:].sum() > 3
    # under dense_len everything seen is taken
    for t in (0, 17, 63):
        assert want[:, :, t, :t // 16 + 1].all() and not want[:, :, t, t // 16 + 1:].any()


def test_the_listed_blocks_are_the_mask_and_the_count_is_the_arithmetic():
    m = mixer()
    x = rows(150)
    q5, k, v, taken = m._whole(x)
    taken = np.asarray(taken)
    # block_keys / take_blocks (what the decode step gathers by) list the
    # same blocks the mask holds
    b, t = 2, 150
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    kp = jnp.pad(k, ((0, 0), (0, -t % 16), (0, 0), (0, 0)))
    sums = jnp.pad(kp.reshape(b, -1, PS, G, HD).sum(2),
                   ((0, 0), (1, 0), (0, 0), (0, 0)))
    ck = m._span_means(jnp.moveaxis(sums, 2, 1))
    idx, keep = m.take_blocks(m.block_keys(q5, ck, pos), pos)
    idx, keep = np.asarray(idx), np.asarray(keep)
    listed = np.zeros_like(taken)
    for i in np.ndindex(idx.shape[:3]):
        listed[i][idx[i][keep[i]]] = True
    assert np.array_equal(listed, taken)
    # tokens attended = tokens of the blocks taken at or before the query
    at = np.arange(t)
    seen = np.repeat(taken, 16, axis=-1)[..., :t] & (at[None] <= at[:, None])
    assert np.array_equal(seen.sum(-1)[0, 0], m.attended_tokens(at))
    assert m.attended_tokens(63) == 64 and m.attended_tokens(64) == 65
    assert m.attended_tokens(95) == 96 and m.attended_tokens(96) == 6 * 16 - 15
    assert m.attended_tokens(1000) == 96 - 7


def run_paged(m, x, n_pre, chunk, tables, pool):
    """Rows of ``x`` through the paged forms: chunks of ``chunk`` up to
    ``n_pre``, then decode steps to the end. Returns the outputs."""
    b, t, _ = x.shape
    out = np.zeros((b, t, D), np.float32)
    chunk_fn, step_fn = jitted(m)
    for c in range(0, n_pre, chunk):
        y, pool = chunk_fn(x[:, c:c + chunk], pool, tables,
                                        jnp.full((b,), c, jnp.int32))
        out[:, c:c + chunk] = np.asarray(y)
    for i in range(n_pre, t):
        y, pool = step_fn(x[:, i], pool, tables,
                                       jnp.full((b,), i, jnp.int32))
        out[:, i] = np.asarray(y)
    return out, pool


@pytest.mark.parametrize("key_pages", [8, 64])
def test_chunks_then_steps_over_pages_equal_one_pass(monkeypatch, key_pages):
    """160 tokens: 7 chunks of 16 (by key blocks of 32 tokens when
    ``KEY_PAGES`` is 8: several rounds with a running maximum), then 48
    decode steps that cross a page end every fourth token, past
    ``dense_len`` and ``topk``."""
    monkeypatch.setattr(sa, "KEY_PAGES", key_pages)
    m = mixer(2)
    x = rows(160, seed=4)
    want = np.asarray(m(x))
    pages = 160 // PS
    tables = jnp.asarray(1 + np.arange(2 * pages).reshape(2, pages), jnp.int32)
    pool = m.init_page_pool(1 + 2 * pages, PS)
    got, pool = run_paged(m, x, 112, 16, tables, pool)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    # the pool's compressed keys are the means of the spans that end in
    # each page (page p: tokens 4 p - 4 .. 4 p + 3)
    _, k, _ = m._qkv(x)
    ck = np.asarray(pool["ck"])[np.asarray(tables)]          # (B, P, G * D)
    for p in (1, 7, 28, 39):
        mean = np.asarray(k[:, PS * (p - 1):PS * (p + 1)]).mean(1).reshape(2, -1)
        assert np.abs(ck[:, p] - mean).max() < 1e-5


def test_a_step_gathers_further_pages_only_for_its_rows_under_dense_len():
    """``dense_len`` 128 is 8 blocks where ``topk`` is 6: a row under it may
    take 2 more than the step gathers for every row. Two rows in one step,
    one at positions 140.. (selecting) and one at 88.. that crosses 128 on
    the way: each computes what one pass over its sequence computes, and
    the count of what the step gathers follows the rows' positions."""
    m = mixer(5, dense_len=128)
    assert m._step_blocks(13) == (6, 2)
    x = rows(200, seed=8)
    want = np.asarray(m(x))
    pages = 200 // PS
    tables = jnp.asarray(1 + np.arange(2 * pages).reshape(2, pages), jnp.int32)
    pool = m.init_page_pool(1 + 2 * pages, PS)
    _, pool = run_paged(m, x, 140, 20, tables, pool)
    step_fn = jitted(m)[1]
    for j in range(60):
        at = np.asarray([140 + j, 88 + j])
        y, pool = step_fn(x[np.arange(2), at], pool, tables,
                          jnp.asarray(at, jnp.int32))
        assert np.abs(np.asarray(y) - want[np.arange(2), at]).max() \
            < 2e-5 * np.abs(want).max(), j
    assert m.gathered_tokens([140, 90, 127, 128], pages).tolist() == [
        96, 128, 128, 96]
    # tables of 6 blocks or fewer: nothing further to gather
    assert mixer().gathered_tokens([10, 200], 24).tolist() == [96, 96]
    assert mixer()._step_blocks(13) == (6, 0)


def test_a_sequence_that_starts_on_anothers_pages_reads_their_compressed_keys():
    """Row B shares row A's first 36 tokens (9 pages: not a multiple of a
    span's 8 tokens, so the span of tokens 32..39 straddles the boundary):
    it prefills from position 36 through a table whose first 9 pages are
    A's, and computes what one pass over its own 140 tokens computes. A's
    pages are read, not written."""
    m = mixer(3)
    a = rows(144, seed=5, b=1)
    bx = jnp.concatenate([a[:, :36], rows(108, seed=6, b=1)], axis=1)
    pages = 144 // PS
    pool = m.init_page_pool(1 + 2 * pages, PS)
    t_a = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
    chunk_fn, step_fn = jitted(m)
    for c in range(0, 144, 16):
        _, pool = chunk_fn(a[:, c:c + 16], pool, t_a,
                                        jnp.full((1,), c, jnp.int32))
    before = jax.tree.map(lambda l: np.asarray(l)[1:10], pool)
    t_b = jnp.concatenate([t_a[:, :9], 1 + pages + jnp.arange(pages - 9)[None]],
                          axis=1).astype(jnp.int32)
    want = np.asarray(m(bx))
    got = np.zeros_like(want)
    # 36 is whole pages, not a whole chunk of 16: chunks of 4 up to 48
    for c in list(range(36, 48, 4)):
        y, pool = chunk_fn(bx[:, c:c + 4], pool, t_b,
                                        jnp.full((1,), c, jnp.int32))
        got[:, c:c + 4] = np.asarray(y)
    for c in range(48, 128, 16):
        y, pool = chunk_fn(bx[:, c:c + 16], pool, t_b,
                                        jnp.full((1,), c, jnp.int32))
        got[:, c:c + 16] = np.asarray(y)
    for i in range(128, 144):
        y, pool = step_fn(bx[:, i], pool, t_b,
                                       jnp.full((1,), i, jnp.int32))
        got[:, i] = np.asarray(y)
    assert np.abs(got[:, 36:] - want[:, 36:]).max() < 2e-5 * np.abs(want).max()
    after = jax.tree.map(lambda l: np.asarray(l)[1:10], pool)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(before), jax.tree.leaves(after)))


def test_a_padded_last_chunk_is_repaired_by_the_steps_that_fill_its_pages():
    """A prompt of 70 tokens: its last chunk of 16 holds 6 real tokens and
    10 of padding whose junk K and compressed keys land in the row's own
    pages; the decode steps overwrite them before any query sees them."""
    m = mixer(4)
    x = rows(120, seed=7, b=1)
    want = np.asarray(m(x))
    pages = 120 // PS
    tables = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
    pool = m.init_page_pool(1 + pages, PS)
    junk = jnp.concatenate([x[:, :70], 9.0 * jnp.ones((1, 10, D))], axis=1)
    got = np.zeros_like(want)
    chunk_fn, step_fn = jitted(m)
    for c in range(0, 80, 16):
        y, pool = chunk_fn(junk[:, c:c + 16], pool, tables,
                                        jnp.full((1,), c, jnp.int32))
        got[:, c:c + 16] = np.asarray(y)
    for i in range(70, 120):
        y, pool = step_fn(x[:, i], pool, tables,
                                       jnp.full((1,), i, jnp.int32))
        got[:, i] = np.asarray(y)
    assert np.abs(got - want)[:, :70].max() < 2e-5 * np.abs(want).max()
    assert np.abs(got - want)[:, 70:].max() < 2e-5 * np.abs(want).max()


def test_what_the_layer_refuses():
    m = mixer()
    with pytest.raises(ValueError, match="kernel_stride"):
        m.init_page_pool(9, 8)
    with pytest.raises(ValueError, match="topk"):
        BlockSparseAttention(D, H, G, HD, **dict(SPARSE, topk=3))
    with pytest.raises(ValueError, match="multiples"):
        BlockSparseAttention(D, H, G, HD, **dict(SPARSE, block_size=18))
    with pytest.raises(ValueError, match="whole pages"):
        m.forward_chunk_paged(rows(6), m.init_page_pool(9, PS),
                              jnp.ones((2, 4), jnp.int32),
                              jnp.zeros((2,), jnp.int32))
    pool = m.init_page_pool(9, PS, jnp.bfloat16)
    assert [l.shape for l in pool["k"]] == [(9, PS, HD)] * G
    assert pool["ck"].shape == (9, G * HD) and pool["ck"].dtype == jnp.bfloat16
