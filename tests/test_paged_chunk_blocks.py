"""The prefill chunk's paged attention by key blocks
(``nn/attention.py _attend_key_blocks``): a fixed number of pages a round
with a running maximum and sum, up to the furthest position any row of the
dispatch has reached.

Each case writes a chunk into a random pool through ``_scatter_kv_paged``,
attends it by key blocks, and compares with ``dot_product_attention`` over
the same K and V (the rows' pages gathered whole on the host, an int8 pool's
as they dequantize), under the same position mask. The round is made narrower
than the table (``KEY_BLOCK_TOKENS`` is a shape rule: these tables are shorter
than one round of it), so the loop, its last partial round and the table's
padding all run. Then the engine's host arithmetic of the same trip count:
the ``serving/prefill_dispatch`` span's ``kv_read_tokens`` /
``kv_table_tokens`` and their sums in ``stats()["paging"]``."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.nn import attention as A
from bigdl_tpu.observability import trace

PS = 4            # tokens a page
ROUND = 3         # pages a round: 12 keys, no divisor of the tables below


def _narrow_rounds(monkeypatch, pages=ROUND):
    monkeypatch.setattr(A, "KEY_BLOCK_TOKENS", pages * PS)


def _case(heads, kv_heads, d, int8, pos0, t, table_len=10):
    """(q, pool, tables, positions, k_new, v_new): a pool of random history
    (int8: random codes and scales), each live row's table a random draw of
    distinct pages, an idle row (``pos0`` None) on the scratch table at
    position 0."""
    rng = np.random.default_rng(hash((heads, kv_heads, d, int8, t)) % 2**31)
    b = len(pos0)
    max_pages = 1 + b * table_len
    width = kv_heads * d
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    if int8:
        codes = lambda: jnp.asarray(rng.integers(
            -127, 128, (max_pages, PS, width)), jnp.int8)
        scales = lambda: jnp.asarray(rng.uniform(
            0.002, 0.02, (max_pages, PS, kv_heads)), jnp.float32)
        pool = (codes(), codes(), scales(), scales())
    else:
        pool = (f32(max_pages, PS, width), f32(max_pages, PS, width))
    tables = np.zeros((b, table_len), np.int32)
    free = rng.permutation(np.arange(1, max_pages))
    for r, p in enumerate(pos0):
        if p is not None:
            tables[r] = free[r * table_len:(r + 1) * table_len]
    start = np.asarray([p or 0 for p in pos0], np.int32)
    positions = jnp.asarray(start[:, None] + np.arange(t)[None])
    return (f32(b, heads, t, d), pool, jnp.asarray(tables), positions,
            f32(b, kv_heads, t, d), f32(b, kv_heads, t, d))


def _dense_reference(q, pool, tables, positions):
    """``dot_product_attention`` over each row's whole table, gathered on
    the host from the pool the chunk was scattered into."""
    b, h, t, d = q.shape
    h_kv = pool[0].shape[2] // d

    def rows(leaf, last):
        got = np.asarray(leaf)[np.asarray(tables)]   # (B, table, PS, ...)
        return got.reshape(b, -1, h_kv, last)

    if len(pool) == 2:
        k, v = rows(pool[0], d), rows(pool[1], d)
    else:
        k = np.asarray(A.dequantize_kv(rows(pool[0], d), rows(pool[2], 1)))
        v = np.asarray(A.dequantize_kv(rows(pool[1], d), rows(pool[3], 1)))
    rep = h // h_kv
    k = jnp.asarray(np.repeat(k.transpose(0, 2, 1, 3), rep, axis=1))
    v = jnp.asarray(np.repeat(v.transpose(0, 2, 1, 3), rep, axis=1))
    mask = (jnp.arange(k.shape[2])[None, None, :]
            <= positions[:, :, None])[:, None]
    return A.dot_product_attention(q, k, v, mask=mask)


CASES = {
    # layer kinds: MHA and GQA at both head sizes, float and int8 pools
    "mha-d64": dict(heads=4, kv_heads=4, d=64, pos0=[8, 20], t=8),
    "gqa-d64": dict(heads=4, kv_heads=2, d=64, pos0=[8, 20], t=8),
    "mha-d128": dict(heads=2, kv_heads=2, d=128, pos0=[8, 20], t=8),
    "gqa-d128": dict(heads=4, kv_heads=1, d=128, pos0=[8, 20], t=8),
    "mha-int8": dict(heads=4, kv_heads=4, d=64, int8=True, pos0=[8, 20],
                     t=8),
    "gqa-int8": dict(heads=4, kv_heads=2, d=64, int8=True, pos0=[8, 20],
                     t=8),
    # where the rows stand: the table's first chunk, the middle of a round,
    # the table's last chunk (the round past it is the table's padding)
    "pos0-zero": dict(heads=4, kv_heads=2, d=64, pos0=[0, 0], t=8),
    "pos0-mid-round": dict(heads=4, kv_heads=2, d=64, pos0=[16, 4], t=8),
    "pos0-last-chunk": dict(heads=4, kv_heads=2, d=64, pos0=[32, 0], t=8),
    # 13 + 8 = 21 live keys: the second round of 12 ends past them
    "live-ends-inside-a-round": dict(heads=4, kv_heads=2, d=64,
                                     pos0=[13, 13], t=8),
    "idle-row-beside-a-live-one": dict(heads=4, kv_heads=2, d=64,
                                       pos0=[24, None], t=8),
    "chunk-no-multiple-of-the-round": dict(heads=4, kv_heads=2, d=64,
                                           pos0=[12, 3], t=7),
    "one-round-is-the-dense-form": dict(heads=4, kv_heads=2, d=64,
                                        pos0=[8, 20], t=8, pages=10),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_key_blocks_match_dense_attention(name, monkeypatch):
    c = dict(CASES[name])
    _narrow_rounds(monkeypatch, c.pop("pages", ROUND))
    int8 = c.pop("int8", False)
    q, pool, tables, positions, k_new, v_new = _case(int8=int8, **c)
    live = [r for r, p in enumerate(c["pos0"]) if p is not None]

    @jax.jit
    def chunk(q, pool, tables, positions, k_new, v_new):
        pool = A._scatter_kv_paged(pool, k_new, v_new, tables, positions)
        return A._attend_key_blocks(q, pool, tables, positions), pool

    out, written = chunk(q, pool, tables, positions, k_new, v_new)
    assert out.dtype == jnp.float32 and out.shape == q.shape
    want = _dense_reference(q, written, tables, positions)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    # the chunk's own keys went through the scatter: what row 0 wrote
    # stands in its pages (int8: as codes)
    r = live[0]
    page = int(tables[r, int(positions[r, 0]) // PS])
    stored = np.asarray(written[0])[page, int(positions[r, 0]) % PS]
    assert not np.array_equal(stored, np.asarray(pool[0])[
        page, int(positions[r, 0]) % PS])


def test_the_loop_stops_at_the_furthest_row(monkeypatch):
    """Pages past the dispatch's furthest position are never read: filling
    them with NaN changes nothing, and the trip count in the program is a
    traced value (one compiled program for every ``pos0``)."""
    _narrow_rounds(monkeypatch)
    q, pool, tables, positions, _, _ = _case(4, 2, 64, False, [9, 2], 8,
                                             table_len=10)
    fn = jax.jit(A._attend_key_blocks)
    want = fn(q, pool, tables, positions)
    reach = -(-(9 + 8) // (ROUND * PS)) * ROUND       # pages two rounds read
    behind = np.asarray(tables)[:, reach:].ravel()
    poisoned = tuple(leaf.at[behind].set(jnp.nan) for leaf in pool)
    np.testing.assert_array_equal(np.asarray(fn(q, poisoned, tables,
                                                positions)),
                                  np.asarray(want))
    assert np.isnan(np.asarray(fn(q, poisoned, tables,
                                  positions + 16))).any()
    assert fn._cache_size() == 1
    assert "while" in fn.lower(q, pool, tables, positions).as_text()


def test_heads_sharded_pool_needs_no_collective(monkeypatch):
    """The tensor-parallel engine's form, on four of the CPU's devices:
    heads are a batch dimension of both products, so with q and the pool
    sharded by heads the partitioned program holds no collective and gives
    the unsharded result."""
    _narrow_rounds(monkeypatch)
    q, pool, tables, positions, _, _ = _case(8, 4, 64, False, [16, 5], 8)
    want = jax.jit(A._attend_key_blocks)(q, pool, tables, positions)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("model",))
    put = lambda a, *spec: jax.device_put(a, NamedSharding(mesh, P(*spec)))
    args = (put(q, None, "model"),
            tuple(put(leaf, None, None, "model") for leaf in pool),
            put(tables), put(positions))
    fn = jax.jit(A._attend_key_blocks,
                 out_shardings=NamedSharding(mesh, P(None, "model")))
    text = fn.lower(*args).compile().as_text()
    for op in ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter"):
        assert op not in text, op
    np.testing.assert_allclose(np.asarray(fn(*args)), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_engine_counts_what_the_chunk_reads(monkeypatch):
    """A short prompt in a long table: every prefill span carries
    ``kv_read_tokens <= kv_table_tokens``, ``stats()["paging"]`` holds their
    sums, the read share is what the rounds' arithmetic gives, and the
    tokens are a lone ``generate``'s."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import ContinuousBatchingEngine
    from bigdl_tpu.utils import random as rnd

    _narrow_rounds(monkeypatch, pages=4)        # rounds of 16 keys
    rnd.set_seed(38)
    lm = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                       num_layers=2, max_len=128, use_rope=True)
    lm.evaluate()
    prompt = np.random.RandomState(0).randint(0, 32, (21,))
    t_before = time.time_ns()
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=8,
                                  page_size=PS) as eng:
        row = eng.submit(prompt, 6).result(timeout=120)
        paging = eng.stats()["paging"]
    np.testing.assert_array_equal(
        row, np.asarray(lm.generate(jnp.asarray(prompt)[None], 6))[0])
    spans = [s for s in trace.export(names=["serving/prefill_dispatch"])
             if s["start_ns"] >= t_before]
    assert len(spans) == 3                          # 21 tokens, chunks of 8
    table = paging["table_len"] * PS
    assert table == 128
    # one row a dispatch; chunks at 0, 8, 16 reach 8, 16, 24 keys: one, one
    # and two rounds of 16
    assert [s["attrs"]["kv_read_tokens"] for s in spans] == [16, 16, 32]
    assert all(s["attrs"]["kv_table_tokens"] == table for s in spans)
    assert all(s["attrs"]["kv_read_tokens"] <= s["attrs"]["kv_table_tokens"]
               for s in spans)
    assert paging["prefill_kv_read_tokens"] == 64
    assert paging["prefill_kv_table_tokens"] == 3 * table


def test_read_counts_follow_rows_width_and_table():
    """``chunk_read_counts``: rounds x width x rows, never more than the
    rows' tables; a table no longer than a round is read whole."""
    attn = A.MultiHeadAttention(3840, 30)
    width = A._key_block_pages(16, 256) * 16
    assert 16 <= width < 4096 and 4096 % width == 0
    got = attn.chunk_read_counts([2048, 0], 256, 16, 256)
    assert got == {"kv_read_tokens": 2 * -(-2304 // width) * width,
                   "kv_table_tokens": 2 * 4096}
    assert attn.chunk_read_counts([3840], 256, 16, 256) == {
        "kv_read_tokens": 4096, "kv_table_tokens": 4096}
    assert A._key_block_pages(16, 2) == 2      # a table of two pages
    short = attn.chunk_read_counts([0], 16, 16, 2)
    assert short["kv_read_tokens"] == short["kv_table_tokens"] == 32
