"""The decode step's paged-attention kernel (``ops/paged_attention.py``)
against the gathered forms it replaces on a TPU (the pair's
``_write_kv_paged(rows=True)`` + ``_attend_pages_rows``; the one leaf's
``_gather_pages`` + ``nn/latent_attention.py _attend_rows``), in the TPU
interpreter on the CPU, where memory no copy has filled reads as NaN.

One random pool a case at the served geometries cut down in pages (GPT-2
Large's 20 heads of 64 = 1280 columns, Olmo's 30 heads of 128 = 3840, a
grouped 8 over 2, and JoyAI-LLM-Flash's ONE leaf of 640-wide latent rows
under 32 whole-row queries), in bfloat16 and float32; eight lanes, each at a
position that is an edge of something: an idle lane on the scratch page
first and last, one and two keys, a page's last and next first key, a round's
last and next first key (a round is 128 keys for a pair and follows the
leaf's width for the one leaf), the table's last key. The live pages are
scattered over the pool, the two longest lanes share their first pages, and
one page no lane holds is NaN. Then the engine's side: which form it takes,
the tokens it serves through the kernel, and the host arithmetic of what a
step reads.
"""

import functools
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn import attention as A
from bigdl_tpu.nn import latent_attention as L
from bigdl_tpu.observability import trace
from bigdl_tpu.ops.paged_attention import (
    BLOCK_TOKENS, ROUND_BUFFER_BYTES, ROUND_TOKENS_MAX, block_pages,
    paged_attention, paged_latent_attention, row_block_pages, supported)

PS = 16                    # tokens a page, as served
#: name -> (query heads, kv heads, a head's columns); no kv heads: ONE leaf
#: whose rows are keys and values at once, under whole-row queries
GEOMETRY = {"gpt2-large": (20, 20, 64), "olmo-hybrid": (30, 30, 128),
            "gqa-8-over-2": (8, 2, 128), "joyai-latent": (32, None, 640)}
LANES = ["idle", "two-keys", "page-end", "page-start", "round-end",
         "round-start", "table-end", "idle-last"]
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}
LATENT_SCALE = 192 ** -0.5


def _round_pages(geometry, dtype):
    """Pages a round of the case's kernel holds (of a table long enough)."""
    _, kv_heads, d = GEOMETRY[geometry]
    if kv_heads is None:
        return row_block_pages(
            jax.ShapeDtypeStruct((1, PS, d), jnp.dtype(dtype)), 1 << 20)
    return block_pages(PS, 1 << 20)


def _positions(geometry, dtype):
    """(each lane's last live position, pages a table): a table is one
    whole round and two pages of the next."""
    pages = _round_pages(geometry, dtype)
    table = pages + 2
    at = {"idle": 0, "two-keys": 1, "page-end": PS - 1, "page-start": PS,
          "round-end": pages * PS - 1, "round-start": pages * PS,
          "table-end": table * PS - 1, "idle-last": 0}
    return np.asarray([at[name] for name in LANES], np.int32), table


@functools.lru_cache(maxsize=None)
def _case(geometry, dtype):
    """(q, the pool's leaves, tables, pos, the step's own token a leaf): the
    pool with random history in every page, the scratch page too, but the
    last page, which no lane holds and is NaN."""
    heads, kv_heads, d = GEOMETRY[geometry]
    rng = np.random.default_rng(sorted(GEOMETRY).index(geometry))
    pos, table = _positions(geometry, dtype)
    lanes = len(LANES)
    max_pages = 2 + sum(int(p) // PS + 1 for p in pos if p)
    dt = jnp.dtype(dtype)
    rand = lambda *s: jnp.asarray(rng.standard_normal(s), dt)
    tables = np.zeros((lanes, table), np.int32)
    # the round-end lane's pages follow one another in the pool (a prompt's
    # pages, handed out together); every other live page is scattered
    held = pos[LANES.index("round-end")] // PS + 1
    tables[LANES.index("round-end"), :held] = 1 + np.arange(held)
    free = iter(rng.permutation(np.arange(1 + held, max_pages - 1)))
    for lane, p in enumerate(pos):
        if p and not tables[lane, 0]:      # the idle lanes stay on scratch
            for i in range(p // PS + 1):
                tables[lane, i] = next(free)
    # lanes share pages, as requests over one prefix do: the round-start
    # lane's second eight with the lane before it, the table-end lane's
    # first seven with the round-start lane
    if held >= 16:
        tables[5, 8:16] = tables[4, 8:16]
    tables[6, :7] = tables[5, :7]
    cols = d if kv_heads is None else kv_heads * d
    leaves = tuple(rand(max_pages, PS, cols).at[-1].set(jnp.nan)
                   for _ in range(1 if kv_heads is None else 2))
    # both idle lanes write the scratch page's first row: the same token
    same = lambda a: a.at[-1].set(a[0])
    if kv_heads is None:
        new = (same(rand(lanes, 1, cols)),)
    else:
        new = tuple(same(rand(lanes, kv_heads, 1, d)) for _ in range(2))
    return rand(lanes, heads, d), leaves, tables, pos, new


@functools.lru_cache(maxsize=None)
def _both(geometry, dtype, repoint=False):
    """(kernel's, gathered form's) outputs (lanes, H, D) as float32, behind
    the same write. ``repoint``: the kernel's tables name the NaN page in
    every slot past a lane's last live page, in place of scratch."""
    q, leaves, tables, pos, new = _case(geometry, dtype)
    kernel_tables = tables.copy()
    if repoint:
        for lane, p in enumerate(pos):
            kernel_tables[lane, p // PS + 1:] = len(leaves[0]) - 1
    tables, kernel_tables, pos = (jnp.asarray(a) for a in (
        tables, kernel_tables, pos))
    if len(leaves) == 1:
        leaf = L.LatentAttention._write(leaves[0], new[0], tables,
                                        pos[:, None])
        want = L._attend_rows(q, A._gather_pages(leaf, tables), pos,
                              LATENT_SCALE).astype(leaf.dtype)
        got = paged_latent_attention(q, leaf, kernel_tables, pos,
                                     LATENT_SCALE)
    else:
        pool, k_rows, v_rows = A._write_kv_paged(leaves, *new, tables, pos,
                                                 rows=True)
        want = A._attend_pages_rows(q, k_rows, v_rows, pos)
        got = paged_attention(q, *pool, kernel_tables, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


CASES = [(g, d) for g in GEOMETRY for d in ("bfloat16", "float32")]


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("geometry,dtype", CASES)
def test_kernel_attends_what_the_gathered_form_attends(geometry, dtype, lane):
    """Each lane's output is the rows form's within what re-ordering the
    float32 sums costs, and no NaN: nothing the kernel multiplies is memory
    it has not filled."""
    got, want = _both(geometry, dtype)
    i = LANES.index(lane)
    assert not np.isnan(got[i]).any()
    np.testing.assert_allclose(got[i], want[i], atol=TOLERANCE[dtype],
                               rtol=TOLERANCE[dtype])


@pytest.mark.parametrize("geometry,dtype", CASES)
def test_nothing_past_a_lanes_position_reaches_the_result(geometry, dtype):
    """With every slot past a lane's last live page re-pointed at a page of
    NaN the kernel's outputs are the same to the bit: pages past ``pos`` are
    not fetched (a fetched NaN under a probability of 0 would still be NaN),
    keys past ``pos`` in the last page are masked."""
    got, _ = _both(geometry, dtype)
    moved, want = _both(geometry, dtype, repoint=True)
    np.testing.assert_array_equal(moved, got)
    np.testing.assert_allclose(moved, want, atol=TOLERANCE[dtype],
                               rtol=TOLERANCE[dtype])


def test_a_round_is_whole_pages_and_no_longer_than_a_table():
    assert block_pages(16, 64) == block_pages(16, 256) == 8
    assert block_pages(16, 3) == 3 and block_pages(256, 4) == 1


@pytest.mark.parametrize("cols,dtype,tokens", [
    (640, "bfloat16", 1024), (640, "float32", 512), (1280, "bfloat16", 512),
    (3840, "bfloat16", 256), (3840, "float32", 128), (16384, "float32", 128)])
def test_a_one_leaf_round_follows_the_leafs_width(cols, dtype, tokens):
    """The width of a one-leaf round is a function of the leaf alone: the
    keys whose two buffers fit ``ROUND_BUFFER_BYTES``, a power of two times
    the pair's 128 and at most ``ROUND_TOKENS_MAX``; whole pages, never
    longer than a table. (A pair's round is 128 keys whatever its width.)"""
    leaf = jax.ShapeDtypeStruct((9, PS, cols), jnp.dtype(dtype))
    pages = row_block_pages(leaf, 1024)
    assert pages * PS == tokens
    assert BLOCK_TOKENS <= tokens <= ROUND_TOKENS_MAX
    row = cols * jnp.dtype(dtype).itemsize
    assert 2 * tokens * row <= ROUND_BUFFER_BYTES or tokens == BLOCK_TOKENS
    # twice as wide would not fit, or would pass the cap
    assert 4 * tokens * row > ROUND_BUFFER_BYTES \
        or 2 * tokens > ROUND_TOKENS_MAX
    assert row_block_pages(leaf, 5) == 5
    assert row_block_pages(jax.ShapeDtypeStruct((9, 256, cols), leaf.dtype),
                           1024) == max(1, tokens // 256)


@pytest.mark.parametrize("shape,dtype,ok", [
    ((9, 16, 1280), "bfloat16", True), ((9, 16, 3840), "bfloat16", True),
    ((9, 16, 640), "bfloat16", True), ((9, 16, 576), "bfloat16", False),
    ((9, 8, 256), "float32", True), ((9, 8, 256), "bfloat16", False),
    ((9, 4, 16), "float32", False), ((9, 16, 1280), "int8", False)])
def test_kernel_reads_whole_tiles_of_floats_only(shape, dtype, ok):
    """What the engine asks before it takes the kernel: a page is whole
    tiles (128 columns; 8 rows of 4 bytes, 16 of 2) of floats. int8 codes
    (the quantized pool's first leaf) and a toy model's pages are not."""
    assert supported(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))) is ok


def test_step_read_counts_closed_form():
    """The kernel reads each lane's pages up to its position, an idle lane
    one page; the gathered forms every slot of every table."""
    attn = A.MultiHeadAttention(1280, 20)
    pos = [0] * 29 + [15, 16, 399]
    whole = 32 * 64 * 16
    for form in ("rows", "heads"):
        assert attn.step_read_counts(pos, 16, 64, form) == {
            "kv_read_tokens": whole, "kv_table_tokens": whole}
    assert attn.step_read_counts(pos, 16, 64) == {
        "kv_read_tokens": whole, "kv_table_tokens": whole}
    assert attn.step_read_counts(pos, 16, 64, "kernel") == {
        "kv_read_tokens": (29 + 1 + 2 + 25) * 16, "kv_table_tokens": whole}
    full = attn.step_read_counts([1023], 16, 64, "kernel")
    assert full["kv_read_tokens"] == full["kv_table_tokens"] == 1024


# ------------------------------------------------------------ the engine
def _lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(44)
    lm = TransformerLM(48, embed_dim=128, num_heads=2, num_layers=2,
                       max_len=64, use_rope=True)
    lm.evaluate()
    return lm


def _serve(lm, prompts, new_tokens, on_tpu):
    """(rows served, stats()["paging"], the decode spans) of an engine over
    ``lm`` whose pages are whole float32 tiles (8 x 128); ``on_tpu``: told
    at construction that its backend is a TPU, which is where it decides."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    t_before = time.time_ns()
    with mock.patch.object(jax, "default_backend",
                           (lambda: "tpu") if on_tpu
                           else jax.default_backend):
        eng = ContinuousBatchingEngine(lm, max_slots=3, prefill_chunk=8,
                                       page_size=8)
    with eng:
        handles = [eng.submit(p, new_tokens) for p in prompts]
        rows = [h.result(timeout=300) for h in handles]
        paging = eng.stats()["paging"]
    spans = [s for s in trace.export(names=["serving/decode_dispatch"])
             if s["start_ns"] >= t_before]
    return rows, paging, spans


def test_engine_on_the_cpu_gathers_rows_and_counts_whole_tables():
    """No mesh and no TPU: ``"rows"`` as before, the tokens a lone
    ``generate`` gives, and every decode span reads what the tables hold."""
    lm = _lm()
    prompt = np.random.RandomState(1).randint(0, 48, (11,))
    rows, paging, spans = _serve(lm, [prompt], 9, on_tpu=False)
    assert paging["decode_attention"] == "rows"
    np.testing.assert_array_equal(
        rows[0], np.asarray(lm.generate(jnp.asarray(prompt)[None], 9))[0])
    whole = 3 * paging["table_len"] * 8
    assert len(spans) == 8                 # the first token is the prefill's
    assert all(s["attrs"]["kv_read_tokens"] == whole
               and s["attrs"]["kv_table_tokens"] == whole for s in spans)
    assert paging["decode_kv_read_tokens"] == 8 * whole
    assert paging["decode_kv_table_tokens"] == 8 * whole


def test_engine_told_it_is_on_a_tpu_serves_the_same_tokens_by_the_kernel():
    """The third word: decided where ``"rows"`` / ``"heads"`` is, from the
    mesh, the backend and the pool's leaves. Through the kernel (here the
    interpreter) the engine serves the rows form's tokens, and its decode
    spans carry what the kernel reads: each lane's pages up to its position,
    an idle lane one page, a small share of the tables."""
    lm = _lm()
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 48, (n,)) for n in (11, 25)]
    want, _, _ = _serve(lm, prompts, 12, on_tpu=False)
    rows, paging, spans = _serve(lm, prompts, 12, on_tpu=True)
    assert paging["decode_attention"] == "kernel"
    for got, ref in zip(rows, want):
        np.testing.assert_array_equal(got, ref)
    whole = 3 * paging["table_len"] * 8
    assert spans and all(s["attrs"]["kv_table_tokens"] == whole
                         for s in spans)
    reads = [s["attrs"]["kv_read_tokens"] for s in spans]
    assert all(8 * 3 <= r < whole // 2 for r in reads), reads
    # a dispatch with both requests decoding: one idle lane's page, and the
    # pages up to where the two stand
    both = [s for s in spans if s["attrs"]["rows"] == 2]
    assert both and all(
        s["attrs"]["kv_read_tokens"] % 8 == 0
        and s["attrs"]["kv_read_tokens"] >= 8 + 16 + 32 for s in both)
    assert paging["decode_kv_read_tokens"] == sum(reads)
    assert paging["decode_kv_table_tokens"] == len(spans) * whole


def test_engine_keeps_rows_where_the_kernel_cannot_read_the_pool():
    """Told it stands on a TPU, an engine whose pool the kernel cannot read
    as it lies keeps the gathered form: int8 codes with their sidecars, pages
    narrower than a tile, and a model with no full-attention layer at all
    (its pool's leaves are a selecting layer's own: on the chip the first is
    2-D, which the first form of this decision tripped over)."""
    import os
    import sys

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import ContinuousBatchingEngine

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import hybrid_tiny
    import sala_tiny

    toy = TransformerLM(32, embed_dim=16, num_heads=4, num_layers=1,
                        max_len=32)
    toy.evaluate()
    kw = dict(max_slots=2, prefill_chunk=8, page_size=8)
    lanes = dict(max_slots=2, prefill_chunk=4, prefill_rows=2, page_size=4)
    cases = [(_lm(), dict(kw, kv_dtype="int8")), (toy, kw),
             (hybrid_tiny.built(hybrid_tiny.tiny_config(), 7)[0], lanes),
             (sala_tiny.built(sala_tiny.tiny_config(positions=64), 9)[0],
              lanes)]
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for model, kwargs in cases:
            eng = ContinuousBatchingEngine(model, **kwargs)
            assert eng.stats()["paging"]["decode_attention"] == "rows"
            eng.stop()


def test_ops_package_names_both_kernels():
    import bigdl_tpu.ops as ops

    assert ops.paged_attention is paged_attention
    assert "paged_attention" in ops.__doc__ and "flash" in ops.__doc__
