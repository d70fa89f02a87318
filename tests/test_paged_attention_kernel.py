"""The decode step's paged-attention kernel (``ops/paged_attention.py``)
against the gathered form it replaces on a TPU (``_write_kv_paged(rows=True)``
+ ``_attend_pages_rows``), in the TPU interpreter on the CPU, where memory no
copy has filled reads as NaN.

One random pool a case at the served geometries cut down in pages (GPT-2
Large's 20 heads of 64 = 1280 columns, Olmo's 30 heads of 128 = 3840, and a
grouped 8 over 2), in bfloat16 and float32; seven lanes, each at a position
that is an edge of something: an idle lane on the scratch page, one and two
keys, a page's last and next first key, a round's last and next first key, the
table's last key. The live pages are scattered over the pool and the two
longest lanes share their first pages. Then the engine's side: which form it
takes, the tokens it serves through the kernel, and the host arithmetic of what
a step reads.
"""

import functools
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn import attention as A
from bigdl_tpu.observability import trace
from bigdl_tpu.ops.paged_attention import (BLOCK_TOKENS, block_pages,
                                           paged_attention, supported)

PS = 16                    # tokens a page, as served
TABLE = 10                 # pages a table: one whole round of 8 and a part
GEOMETRY = {"gpt2-large": (20, 20, 64), "olmo-hybrid": (30, 30, 128),
            "gqa-8-over-2": (8, 2, 128)}
#: name -> the lane's last live position
LANES = {"idle": 0, "two-keys": 1, "page-end": PS - 1, "page-start": PS,
         "round-end": BLOCK_TOKENS - 1, "round-start": BLOCK_TOKENS,
         "table-end": TABLE * PS - 1}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


@functools.lru_cache(maxsize=None)
def _case(geometry, dtype):
    """(q, k_pages, v_pages, tables, pos, k_t, v_t): the pool with random
    history in every page, the scratch page too, and the step's own token."""
    heads, kv_heads, d = GEOMETRY[geometry]
    rng = np.random.default_rng(sorted(GEOMETRY).index(geometry))
    lanes = len(LANES)
    max_pages = 1 + lanes * TABLE
    dt = jnp.dtype(dtype)
    rand = lambda *s: jnp.asarray(rng.standard_normal(s), dt)
    pos = np.asarray(list(LANES.values()), np.int32)
    tables = np.zeros((lanes, TABLE), np.int32)
    free = iter(rng.permutation(np.arange(1, max_pages)))
    for lane, p in enumerate(pos):
        if p:                              # the idle lane stays on scratch
            for i in range(p // PS + 1):
                tables[lane, i] = next(free)
    # the table-end lane shares the round-start lane's first seven pages
    tables[6, :7] = tables[5, :7]
    return (rand(lanes, heads, d), rand(max_pages, PS, kv_heads * d),
            rand(max_pages, PS, kv_heads * d), jnp.asarray(tables),
            jnp.asarray(pos), rand(lanes, kv_heads, 1, d),
            rand(lanes, kv_heads, 1, d))


@functools.lru_cache(maxsize=None)
def _both(geometry, dtype, repoint=False):
    """(kernel's, gathered form's) outputs (lanes, H, D) as float32, behind
    the same write. ``repoint``: every slot past a lane's last live page
    names another lane's page (the next lane's first) in place of scratch."""
    q, k, v, tables, pos, k_t, v_t = _case(geometry, dtype)
    if repoint:
        t = np.asarray(tables).copy()
        for lane, p in enumerate(np.asarray(pos)):
            t[lane, p // PS + 1:] = t[(lane + 1) % len(t), 0] or t[1, 0]
        tables = jnp.asarray(t)
    pool, k_rows, v_rows = A._write_kv_paged((k, v), k_t, v_t, tables, pos,
                                             rows=True)
    want = A._attend_pages_rows(q, k_rows, v_rows, pos)
    got = paged_attention(q, *pool, tables, pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


CASES = [(g, d) for g in GEOMETRY for d in ("bfloat16", "float32")]


@pytest.mark.parametrize("lane", list(LANES))
@pytest.mark.parametrize("geometry,dtype", CASES)
def test_kernel_attends_what_the_gathered_form_attends(geometry, dtype, lane):
    """Each lane's output is the rows form's within what re-ordering the
    float32 sums costs, and no NaN: nothing the kernel multiplies is memory
    it has not filled."""
    got, want = _both(geometry, dtype)
    i = list(LANES).index(lane)
    assert not np.isnan(got[i]).any()
    np.testing.assert_allclose(got[i], want[i], atol=TOLERANCE[dtype],
                               rtol=TOLERANCE[dtype])


@pytest.mark.parametrize("geometry,dtype", CASES)
def test_nothing_past_a_lanes_position_reaches_the_result(geometry, dtype):
    """With every slot past a lane's last live page re-pointed at another
    lane's page the kernel's outputs are the same to the bit: pages past
    ``pos`` are not fetched, keys past ``pos`` in the last page are masked."""
    got, _ = _both(geometry, dtype)
    moved, want = _both(geometry, dtype, repoint=True)
    np.testing.assert_array_equal(moved, got)
    np.testing.assert_allclose(moved, want, atol=TOLERANCE[dtype],
                               rtol=TOLERANCE[dtype])


def test_a_round_is_whole_pages_and_no_longer_than_a_table():
    assert block_pages(16, 64) == block_pages(16, 256) == 8
    assert block_pages(16, 3) == 3 and block_pages(256, 4) == 1


@pytest.mark.parametrize("shape,dtype,ok", [
    ((9, 16, 1280), "bfloat16", True), ((9, 16, 3840), "bfloat16", True),
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
