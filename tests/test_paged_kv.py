"""Paged KV cache (bigdl_tpu/serving/paging.py + the engine over it).

The subsystem contract under test, unit first and then end-to-end:

* ``PagePool`` — refcounted block allocator over ONE persistent device
  tree: all-or-nothing ``alloc``, loud-failure ``share``/``free``,
  LIFO recycling, cumulative flow counters with the invariant
  ``allocated - freed == pages_in_use`` at all times, and the billing
  conservation law: the sum of ``holder_bytes`` over every holder of
  a page is exactly that page's bytes.
* ``BlockTable`` — position ``i`` lives at offset ``i % page_size`` of
  ``pages[i // page_size]``; ``build`` is atomic (a failed fresh
  allocation never touches the shared head's refcounts), ``fork`` is
  pure refcount, ``ensure_writable`` breaks a share with one
  single-page device copy and the ORIGINAL holder's bytes are
  untouched (copy-on-write isolation).
* The engine — greedy decode stays token-identical to the dense
  ``model.generate`` oracle across plain / tiered / speculative
  / quantized / tensor-parallel variants; a prefix hit SHARES pages
  (``shared_total`` moves, ``cow_forks_total`` does not: the
  zero-copy hit leg); the jit-compile gauge is FLAT through page
  alloc / share / free / preemption; a preempt-then-drain cycle leaks
  nothing (every allocated page comes back, the pool ends empty); the
  usage ledger bills ``kv_byte_seconds`` per actually-held page; and
  ``/debug/memory`` attributes both the pool's capacity and its live
  occupancy.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu.observability import memory as obs_memory
from bigdl_tpu.observability.events import FlightRecorder
from bigdl_tpu.serving import ContinuousBatchingEngine
from bigdl_tpu.serving.paging import (
    SCRATCH_PAGE, BlockTable, PagePool,
)
from bigdl_tpu.serving.scheduler import pages_needed

PS = 4          # page_size under test
CHUNK = 4       # prefill_chunk (must be a page multiple)


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(21)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


@pytest.fixture(scope="module")
def lm_tp():
    # 4-way model axis needs num_kv_heads divisible by 4
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(23)
    m = TransformerLM(32, embed_dim=32, num_heads=8, num_kv_heads=4,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


@pytest.fixture(scope="module")
def mesh():
    from bigdl_tpu.parallel import Engine

    return Engine.create_mesh([("model", 4)],
                              devices=jax.devices()[:4])


@pytest.fixture()
def reg():
    r = obs.MetricRegistry()
    prev = obs.set_default_registry(r)
    try:
        yield r
    finally:
        obs.set_default_registry(prev)


@pytest.fixture()
def rec():
    r = FlightRecorder()
    prev = obs.set_default_recorder(r)
    try:
        yield r
    finally:
        obs.set_default_recorder(prev)


def _direct(lm, prompt, n):
    return np.asarray(lm.generate(jnp.asarray(prompt)[None], n))[0]


def _pool(lm, max_pages=6, page_size=PS):
    return PagePool(lm.init_page_pool(max_pages, page_size),
                    page_size)


# ===================================================== PagePool units
def test_pool_alloc_share_free_refcount(lm):
    pool = _pool(lm, max_pages=6)
    assert pool.max_pages == 6 and pool.page_bytes > 0
    assert pool.free_pages == 5          # page 0 reserved for scratch

    pages = pool.alloc(3)
    assert pages is not None and len(set(pages)) == 3
    assert SCRATCH_PAGE not in pages     # scratch is never handed out
    assert pool.pages_in_use == 3 and pool.free_pages == 2
    assert all(pool.refcount(p) == 1 for p in pages)

    # all-or-nothing: asking for more than remains changes NOTHING
    assert pool.alloc(3) is None
    assert pool.free_pages == 2 and pool.allocated == 3

    pool.share(pages[:2])
    assert pool.refcount(pages[0]) == 2 == pool.refcount(pages[1])
    pool.free(pages)                     # drop the original reference
    assert pool.refcount(pages[2]) == 0  # last ref gone -> free list
    assert pool.pages_in_use == 2        # the two shared pages remain
    pool.free(pages[:2])
    assert pool.pages_in_use == 0 and pool.free_pages == 5

    # flow counters: allocated - freed == pages_in_use held throughout
    s = pool.stats()
    assert s["allocated_total"] == 3 and s["shared_total"] == 2
    assert s["freed_total"] == 3
    assert s["allocated_total"] - s["freed_total"] == s["pages_in_use"]
    assert s["bytes_in_use"] == 0
    assert s["capacity_bytes"] == 6 * pool.page_bytes

    # double-free and share-of-free fail loudly, not silently
    with pytest.raises(RuntimeError):
        pool.free([pages[0]])
    with pytest.raises(RuntimeError):
        pool.share([pages[0]])


def test_pool_holder_bytes_conservation(lm):
    """The ledger's conservation law: each page bills its bytes split
    evenly across its CURRENT refcount, so summing ``holder_bytes``
    over every holder reproduces ``bytes_in_use`` exactly."""
    pool = _pool(lm, max_pages=8)
    t1 = BlockTable.build(pool, (), 3)
    t2 = t1.fork()                              # 3 pages shared 2 ways
    t3 = BlockTable.build(pool, t1.pages[:1], 2)  # 1 shared 3 ways + 2
    holders = [t1, t2, t3]
    total = sum(pool.holder_bytes(t.pages) for t in holders)
    assert total == pytest.approx(pool.bytes_in_use, abs=1e-6)
    # still conserved after an asymmetric release
    t2.free()
    total = sum(pool.holder_bytes(t.pages) for t in (t1, t3))
    assert total == pytest.approx(pool.bytes_in_use, abs=1e-6)
    t1.free()
    t3.free()
    assert pool.bytes_in_use == 0


# =================================================== BlockTable units
def test_block_table_build_atomic_fork_views(lm):
    pool = _pool(lm, max_pages=6)
    head = pool.alloc(2)
    # atomic build: fresh allocation fails -> None, and the would-be
    # shared head's refcounts were never bumped
    assert BlockTable.build(pool, head, 4) is None
    assert all(pool.refcount(p) == 1 for p in head)

    t = BlockTable.build(pool, head, 2)
    assert t is not None and len(t) == 4
    assert all(pool.refcount(p) == 2 for p in head)

    # covering / as_array: scratch-padded fixed dispatch shape
    assert t.covering(5) == tuple(t.pages[:2])
    assert t.covering(8) == tuple(t.pages[:2])
    assert t.covering(9) == tuple(t.pages[:3])
    arr = t.as_array(12)
    assert arr.shape == (12,) and arr.dtype == np.int32
    np.testing.assert_array_equal(arr[:4], t.pages)
    assert (arr[4:] == SCRATCH_PAGE).all()

    fork = t.fork()
    assert fork.pages == t.pages
    assert all(pool.refcount(p) >= 2 for p in t.pages)
    fork.free()
    t.free()
    pool.free(head)
    assert pool.pages_in_use == 0


def test_cow_fork_isolation_unit():
    """ensure_writable breaks a share with one page copy and the
    original holder's device bytes are untouched."""
    buffers = {"k": jnp.zeros((6, PS, 2), jnp.float32)}
    pool = PagePool(buffers, PS)

    def write(page, val):
        buffers["k"] = buffers["k"].at[page].set(val)

    def copy_page(dst, src):
        buffers["k"] = buffers["k"].at[dst].set(buffers["k"][src])

    t1 = BlockTable.build(pool, (), 2)
    write(t1.pages[1], 7.0)
    t2 = t1.fork()

    # sole-owner pages skip the copy entirely
    t1_private = BlockTable.build(pool, (), 1)
    assert t1_private.ensure_writable(0, copy_page) is False
    assert pool.cow_forks == 0

    src = t2.pages[1]
    assert t2.ensure_writable(1, copy_page) is True
    dst = t2.pages[1]
    assert dst != src and pool.cow_forks == 1
    assert pool.refcount(src) == 1 and pool.refcount(dst) == 1
    np.testing.assert_array_equal(np.asarray(buffers["k"][dst]),
                                  np.asarray(buffers["k"][src]))
    write(dst, 9.0)                      # the fork diverges...
    assert float(buffers["k"][t1.pages[1]][0, 0]) == 7.0  # ...alone
    assert float(buffers["k"][dst][0, 0]) == 9.0
    for t in (t1, t2, t1_private):
        t.free()
    assert pool.pages_in_use == 0


def test_cow_copy_page_kernel_copies_every_leaf(lm):
    """The engine's jitted single-page copy (BlockTable's callback)
    moves EVERY layer's K and V for the page, verified leaf by leaf
    against the source page after a real decode has filled it."""
    p = np.asarray([5, 2, 7, 1, 3], np.int32)
    with ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=CHUNK,
                                  page_size=PS, max_pages=15,
                                  prefix_cache_rows=0,
                                  service_name="cow_kernel") as eng:
        eng.submit(p, 6).result(timeout=60)
        # LIFO free list: the request's just-freed pages (holding real
        # KV) are re-issued first, so this table's page is non-trivial
        t = BlockTable.build(eng._pages, (), 1)
        t2 = t.fork()
        src = t2.pages[0]
        assert t2.ensure_writable(0, eng._copy_page) is True
        dst = t2.pages[0]
        for leaf in jax.tree_util.tree_leaves(eng._kv_pool):
            src_page = np.asarray(leaf[src])
            assert np.abs(src_page).sum() > 0   # decode really wrote it
            np.testing.assert_array_equal(np.asarray(leaf[dst]),
                                          src_page)
        t.free()
        t2.free()


# ============================================ engine: greedy parity
def _parity_run(lm, reqs, **engine_kw):
    """Mixed-length concurrent load through a 2-slot paged engine:
    every reply must match the lone-generate oracle, the jit gauge
    must be flat after warmup, and the pool must drain to empty."""
    rows = [None] * len(reqs)
    errs = []
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                  page_size=PS, **engine_kw) as eng:
        # warm both phases so later admissions cannot mint programs
        eng.submit(np.asarray(reqs[0][0]), 2).result(timeout=120)
        jit_warm = eng.stats()["jit_compiles"]

        def worker(i, p, n):
            try:
                rows[i] = eng.submit(p, n).result(timeout=120)
            except Exception as e:       # pragma: no cover - surfaced
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i, p, n))
                   for i, (p, n) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        st = eng.stats()
        assert st["jit_compiles"] == jit_warm, \
            "page alloc/share/free must not mint new programs"
        pg = st["paging"]
        assert pg["page_size"] == PS
        assert pg["pool"]["allocated_total"] > 0
        assert 0.0 <= pg["fragmentation"] <= 1.0
    # drained + stopped: every reference dropped, nothing leaked
    pool = eng._pages.stats()
    assert pool["pages_in_use"] == 0 and pool["bytes_in_use"] == 0
    assert pool["allocated_total"] == pool["freed_total"]
    for (p, n), row in zip(reqs, rows):
        np.testing.assert_array_equal(row, _direct(lm, p, n))
    return eng


def _mixed_reqs(seed=0, vocab=32):
    r = np.random.RandomState(seed)
    lens = [(5, 6), (9, 4), (3, 9), (13, 5), (7, 7), (4, 11)]
    return [(r.randint(0, vocab, (t0,)), n) for t0, n in lens]


def test_paged_parity_plain(lm):
    _parity_run(lm, _mixed_reqs(0), prefix_cache_rows=0,
                service_name="paged_plain")


def test_paged_parity_prefix(lm):
    _parity_run(lm, _mixed_reqs(1), prefix_cache_rows=4,
                service_name="paged_prefix")


def test_paged_parity_tiered(lm):
    _parity_run(lm, _mixed_reqs(2), prefix_cache_rows=4,
                prefix_host_rows=4, service_name="paged_tiered")


@pytest.mark.slow
def test_paged_parity_speculative(lm):
    from bigdl_tpu.nn.quantized import Quantizer

    _parity_run(lm, _mixed_reqs(3), prefix_cache_rows=0,
                draft=Quantizer.quantize(lm), spec_gamma=3,
                service_name="paged_spec")


@pytest.mark.slow
def test_paged_parity_quantized_kv(lm):
    """int8 KV pages with per-page scale sidecars: greedy tokens stay
    identical to the f32 oracle at this model scale."""
    _parity_run(lm, _mixed_reqs(4), prefix_cache_rows=0,
                kv_dtype="int8", service_name="paged_int8")


@pytest.mark.slow
def test_paged_parity_tensor_parallel(lm_tp, mesh):
    _parity_run(lm_tp, _mixed_reqs(5), prefix_cache_rows=0,
                mesh=mesh, service_name="paged_tp")


# ==================================== engine: zero-copy prefix sharing
def test_prefix_hit_shares_pages_zero_copy(lm, reg):
    """The tentpole acceptance: a prefix hit bumps refcounts
    (``shared_total``) and copies NOTHING — no row staging, no COW
    (chunk alignment keeps writes off shared pages) — while the reply
    stays token-identical and the registry counters agree."""
    r = np.random.RandomState(7)
    tpl = r.randint(0, 32, (8,))
    pa = np.concatenate([tpl, r.randint(0, 32, (3,))])
    pb = np.concatenate([tpl, r.randint(0, 32, (4,))])
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                  page_size=PS, prefix_cache_rows=4,
                                  service_name="paged_hit") as eng:
        ha = eng.submit(pa, 5)
        np.testing.assert_array_equal(ha.result(timeout=60),
                                      _direct(lm, pa, 5))
        assert ha.prefix_tokens == 0
        jit_before_hit = eng.stats()["jit_compiles"]
        shared_before = eng._pages.stats()["shared_total"]

        hb = eng.submit(pb, 5)
        np.testing.assert_array_equal(hb.result(timeout=60),
                                      _direct(lm, pb, 5))
        assert hb.prefix_tokens == 8
        st = eng.stats()
        assert st["prefix_cache"]["hits"] == 1
        pool = st["paging"]["pool"]
        assert pool["shared_total"] > shared_before   # pages re-referenced
        assert pool["cow_forks_total"] == 0           # nothing copied
        assert st["jit_compiles"] == jit_before_hit   # no new programs
    m = reg.get("bigdl_serving_page_shared_total")
    assert m is not None
    assert sum(c.get() for _, c in m.children()) > 0
    cow = reg.get("bigdl_serving_page_cow_forks_total")
    assert sum(c.get() for _, c in cow.children()) == 0


# ================================== engine: preemption drains cleanly
_VICTIM = np.asarray([7, 3, 1, 4, 1, 5], np.int32)
_URGENT = np.asarray([2, 6, 2, 6], np.int32)


def test_paged_preemption_no_leak_jit_flat(lm, reg, rec):
    """One slot, a low-class decode provably in it, a high-class
    arrival forcing preemption: both outputs match the oracle, the
    jit gauge never moves, the donated prefix pages are refcount
    moves, and after stop every allocated page has been freed —
    the refcount-leak check the ISSUE names."""
    with ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=CHUNK,
                                  page_size=PS, preempt_slack_s=0.002,
                                  prefix_cache_rows=4,
                                  service_name="paged_preempt") as eng:
        eng.submit(_VICTIM, 2, priority="low").result(timeout=60)
        eng.submit(_URGENT, 2, priority="high").result(timeout=60)
        jit_warm = eng.stats()["jit_compiles"]

        h_low = eng.submit(_VICTIM, 40, priority="low", tenant="batch")
        next(h_low.tokens())             # provably decoding in-slot
        h_high = eng.submit(_URGENT, 4, priority="high",
                            tenant="interactive")
        np.testing.assert_array_equal(h_high.result(timeout=120),
                                      _direct(lm, _URGENT, 4))
        np.testing.assert_array_equal(h_low.result(timeout=120),
                                      _direct(lm, _VICTIM, 40))
        assert h_low.preempted >= 1
        st = eng.stats()
        assert st["jit_compiles"] == jit_warm, \
            "preemption must not mint new programs in paged mode"
        # the victim's usage record billed paged KV residency
        assert h_low.usage()["kv_byte_seconds"] > 0
    pool = eng._pages.stats()
    assert pool["pages_in_use"] == 0, \
        f"page leak after preempt+drain: {pool}"
    assert pool["allocated_total"] == pool["freed_total"]
    g = reg.get("bigdl_serving_page_pool_pages_in_use")
    assert sum(c.get() for _, c in g.children()) == 0


# ===================================== what the clipping take rests on
def _watch_tables(eng, monkeypatch):
    """Record every table the engine hands to a dispatch (the step's
    ``_slot_tables``, the chunk's ``_adm_tables``, target and draft) and
    every ``BlockTable.as_array``; returns ``(seen, faults)``: (builder,
    draft, the table as a host array) a dispatch, and what broke
    ``nn.attention._gather_pages``' contract: an id outside
    ``[0, max_pages)``, or anything but ``SCRATCH_PAGE`` where a row
    holds nothing."""
    seen, faults = [], []
    as_array = BlockTable.as_array

    def padded(tbl, table_len):
        out = as_array(tbl, table_len)
        held = len(tbl.pages)
        if not ((out[:held] >= 1) & (out[:held] < tbl.pool.max_pages)).all():
            faults.append(("as_array: a held page's id", out.tolist()))
        if not (out[held:] == SCRATCH_PAGE).all():
            faults.append(("as_array: padding", out.tolist()))
        return out

    monkeypatch.setattr(BlockTable, "as_array", padded)

    def watched(name, holders):
        build = getattr(eng, name)

        def tables(draft=False):
            held = holders(draft)        # row -> pages held, before the build
            out = build(draft=draft)
            t = np.asarray(out)
            pool = eng._d_pages if draft else eng._pages
            if not ((t >= 0) & (t < pool.max_pages)).all():
                faults.append((name, "an id out of range", t.tolist()))
            for row in range(t.shape[0]):
                n = held.get(row, 0)
                if not (t[row, n:] == SCRATCH_PAGE).all() \
                        or (t[row, :n] == SCRATCH_PAGE).any():
                    faults.append((name, draft, row, n, t[row].tolist()))
            seen.append((name, draft, t))
            return out
        monkeypatch.setattr(eng, name, tables)

    def slots(draft):
        tables = eng._d_tables if draft else eng._tables
        return {sid: len(tbl.pages) for sid, tbl in enumerate(tables)
                if tbl is not None}

    def admissions(draft):
        return {a.row: len(tbl.pages) for a in eng._adms
                for tbl in [a.d_table if draft else a.table]
                if tbl is not None}

    watched("_slot_tables", slots)
    watched("_adm_tables", admissions)
    return seen, faults


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
def test_every_dispatched_table_holds_valid_ids_and_scratch(
        lm, monkeypatch, draft):
    """A take through a block table clips (``_gather_pages``): it rests
    on every id a dispatch is handed lying in ``[0, max_pages)``, with
    ``SCRATCH_PAGE`` wherever a row holds nothing. Held here over a run
    with cold admissions, prefix hits, finished requests' frees and a
    preemption (with a speculative draft's tables too); and on those
    tables the clipping gather equals plain indexing to the bit."""
    from bigdl_tpu.nn.attention import _gather_pages
    from bigdl_tpu.nn.quantized import Quantizer

    kw = dict(draft=Quantizer.quantize(lm), spec_gamma=3) if draft else {}
    template = np.asarray([5, 9, 2, 7, 1, 8, 3, 6], np.int32)
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                  prefill_rows=2, page_size=PS,
                                  preempt_slack_s=0.002,
                                  prefix_cache_rows=4,
                                  service_name=f"paged_ids_{draft}",
                                  **kw) as eng:
        seen, faults = _watch_tables(eng, monkeypatch)
        eng.submit(np.append(template, [4, 4]), 3).result(timeout=120)
        hit = eng.submit(np.append(template, [11, 12, 13]), 5)
        hit.result(timeout=120)
        assert hit.prefix_tokens == len(template)
        # both slots decoding, then a high-class arrival: one is preempted
        low = [eng.submit(_VICTIM + i, 30, priority="low") for i in (0, 1)]
        for h in low:
            next(h.tokens())
        eng.submit(_URGENT, 4, priority="high").result(timeout=120)
        for h in low:
            h.result(timeout=120)
        assert sum(h.preempted for h in low) >= 1
        max_pages, _, hd = jax.tree.leaves(eng._kv_pool)[0].shape
        assert max_pages == eng._pages.max_pages
        assert eng._pages.stats()["freed_total"] > 0
    assert not faults, faults[:3]
    assert {(name, d) for name, d, _ in seen} == {
        (name, d) for name in ("_slot_tables", "_adm_tables")
        for d in (False, True)[:1 + draft]}
    seen = [t for _, _, t in seen]
    assert {t.shape for t in seen} == {(2, eng._table_len)}
    assert any((t != SCRATCH_PAGE).any() for t in seen)
    assert any((t == SCRATCH_PAGE).all(axis=1).any() for t in seen)

    # on every distinct table of the run: the clipped gather IS the index
    tables = np.unique(np.concatenate(seen), axis=0)
    leaf = jnp.asarray(np.random.RandomState(5).standard_normal(
        (max_pages, PS, hd)), jnp.bfloat16)
    want = np.asarray(leaf)[tables].reshape(len(tables), -1, hd)
    got = np.asarray(_gather_pages(leaf, jnp.asarray(tables)))
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
    h_kv = lm.num_kv_heads
    heads = np.asarray(_gather_pages(leaf, jnp.asarray(tables), h_kv))
    assert heads.tobytes() == want.tobytes() \
        and heads.shape == want.shape[:2] + (h_kv, hd // h_kv)


# ================================================ engine: usage ledger
def test_usage_ledger_bills_held_pages(lm):
    """kv_byte_seconds accrues per actually-held page, pro-rata per
    reference: every finished request is billed > 0, and the tenant
    total is bounded by pool capacity x wall time (conservation —
    shared pages are billed once, split across holders)."""
    r = np.random.RandomState(11)
    reqs = [(r.randint(0, 32, (6,)), 8, "tenant-a"),
            (r.randint(0, 32, (9,)), 8, "tenant-b"),
            (r.randint(0, 32, (4,)), 10, "tenant-a")]
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                  page_size=PS, prefix_cache_rows=0,
                                  service_name="paged_ledger") as eng:
        t_start = time.monotonic()
        handles = [eng.submit(p, n, tenant=t) for p, n, t in reqs]
        rows = [h.result(timeout=120) for h in handles]
        wall = time.monotonic() - t_start
        for (p, n, _), row in zip(reqs, rows):
            np.testing.assert_array_equal(row, _direct(lm, p, n))
        billed = [h.usage()["kv_byte_seconds"] for h in handles]
        assert all(b > 0 for b in billed), billed
        cap = eng._pages.capacity_bytes
        assert sum(billed) <= cap * wall * 1.5
        tenants = eng.stats()["usage"]["tenants"]
        assert set(tenants) >= {"tenant-a", "tenant-b"}


# ===================================== engine: validation + /debug
def test_paged_ctor_and_submit_validation(lm):
    with pytest.raises(ValueError, match="multiple of"):
        ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=6,
                                 page_size=4)
    with pytest.raises(ValueError, match="cannot hold one"):
        ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=CHUNK,
                                 page_size=PS, max_pages=4)
    assert pages_needed(9, PS) == 3 and pages_needed(8, PS) == 2


def test_pool_pressure_blocks_admission_not_correctness(lm, rec):
    """A pool sized for ONE full-length reservation under a 2-slot
    engine: the second long request cannot admit until the first
    frees its pages — the engine requeues it (``request/page_wait``
    in the flight recorder) instead of deadlocking or OOMing, and
    both replies stay token-identical."""
    r = np.random.RandomState(13)
    pa, pb = r.randint(0, 32, (8,)), r.randint(0, 32, (9,))
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                  page_size=PS, max_pages=13,
                                  prefix_cache_rows=0,
                                  service_name="paged_pressure") as eng:
        ha = eng.submit(pa, 30)          # reserves 10 of 12 pages
        next(ha.tokens())                # provably holding them
        hb = eng.submit(pb, 30)          # needs 10: must wait
        np.testing.assert_array_equal(ha.result(timeout=120),
                                      _direct(lm, pa, 30))
        np.testing.assert_array_equal(hb.result(timeout=120),
                                      _direct(lm, pb, 30))
    assert eng._pages.pages_in_use == 0
    waits = [e for e in rec.tail() if e.kind == "request/page_wait"]
    assert waits, "pressure never surfaced as a page_wait event"
    assert waits[0].attrs["free_pages"] < waits[0].attrs["needed_pages"]


@pytest.mark.parametrize("chunk, page_size, derived",
                         [(8, None, 8), (16, None, 16), (128, None, 16),
                          (8, 3, None)])
def test_page_size_derived_from_prefill_chunk(chunk, page_size, derived):
    """An engine built with no ``page_size`` serves through a page pool
    of ``gcd(prefill_chunk, 16)``-token pages, token for token what
    ``model.generate`` gives; a ``page_size`` that does not divide the
    chunk is still refused."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(29)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=256, use_rope=True)
    m.evaluate()
    kw = dict(max_slots=1, prefill_chunk=chunk, page_size=page_size,
              service_name=f"paged_derived_{chunk}_{page_size}")
    if derived is None:
        with pytest.raises(ValueError, match="multiple of"):
            ContinuousBatchingEngine(m, **kw)
        return
    p = np.random.RandomState(chunk).randint(0, 32, (11,))
    with ContinuousBatchingEngine(m, **kw) as eng:
        row = eng.submit(p, 6).result(timeout=120)
        pg = eng.stats()["paging"]
    assert pg["page_size"] == derived
    assert pg["pool"]["allocated_total"] == pages_needed(17, derived)
    np.testing.assert_array_equal(row, _direct(m, p, 6))


def test_debug_memory_attributes_pool_and_occupancy(lm):
    """/debug/memory answers both "how big is the pool" (capacity of
    the persistent device tree) and "how full" (live refcounted
    bytes), keyed by service name."""
    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    with ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=CHUNK,
                                  page_size=PS, max_pages=15,
                                  prefix_cache_rows=0,
                                  service_name="paged_dbg") as eng:
        sizes = obs_memory.pool_sizes()
        cap_key = "serving/paged_dbg/kv_page_pool"
        live_key = "serving/paged_dbg/kv_pages_in_use"
        assert cap_key in sizes and live_key in sizes
        assert sizes[cap_key] >= eng._pages.capacity_bytes
        assert sizes[live_key] == 0          # idle: nothing held
        h = eng.submit(p, 30)
        next(h.tokens())                     # provably holding pages
        mid = obs_memory.pool_sizes()[live_key]
        assert mid > 0
        assert mid == eng._pages.bytes_in_use
        h.result(timeout=120)
    assert eng._pages.bytes_in_use == 0


# ================================== the pool's layout (PR 27)
# A pool leaf is (max_pages, page_size, H_kv * D): page and offset, the
# dimensions the KV write indexes, lead, so the write is a scatter of
# whole rows that updates the donated leaf in place. These tests hold
# the model-level programs the engine jits (``decode_step_paged`` /
# ``prefill_chunk_at_paged`` with the pool donated) to that: their
# compiled form re-lays no leaf, and their logits are the dense cache's
# on the same tokens (same values attended, same greedy token; not
# bit-equal: XLA's CPU dot sums a score's head_dim products in another
# order when K arrives token-major, 5e-7 of a score apart).

LAYOUT_PAGES = 37       # a leaf's element count no other array shares


def _bound(lm, fn):
    """``fn`` of the model's methods as a pure function of its params
    (what the engine jits)."""
    from bigdl_tpu.nn.module import bind

    def run(p, bufs, *args):
        with bind(lm, p, bufs, False, None):
            return fn(*args)

    return run


def _programs(lm, **jit_kw):
    """(step, chunk, dense_step, dense_chunk), each jitted with its KV
    tree donated as the engine donates it."""
    step = jax.jit(_bound(lm, lm.decode_step_paged),
                   donate_argnums=(4,), **jit_kw)
    chunk = jax.jit(_bound(lm, lm.prefill_chunk_at_paged),
                    donate_argnums=(3,), **jit_kw)
    d_step = jax.jit(_bound(lm, lm.decode_step), donate_argnums=(4,))
    d_chunk = jax.jit(_bound(lm, lm.prefill_chunk_at), donate_argnums=(3,))
    return step, chunk, d_step, d_chunk


def _state(lm):
    return (jax.tree.map(jnp.asarray, lm.params_dict()),
            jax.tree.map(jnp.asarray, lm.buffers_dict()))


def _relaid_leaves(hlo, pool):
    """``copy`` / ``transpose`` instructions of ``hlo`` (fused ones
    too) whose result holds as many elements as a leaf of ``pool``."""
    import math
    import re

    sizes = {int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(pool)}
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?\S+\s*=\s*\w+\[([\d,]+)\]\S*\s+"
                     r"(copy|transpose)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) in sizes:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["float", "int8"])
def test_paged_programs_write_pool_in_place(lm, kv_dtype):
    """The optimized step and chunk hold no copy or transpose of a
    whole pool leaf (the (k, v) and the int8 4-tuple form). A float32
    pool, so the CPU's bf16-to-f32 widening of scatters is not taken
    for one. With heads between page and offset (the layout before
    PR 27) both programs transposed every leaf around its scatter."""
    params, bufs = _state(lm)
    pool = lm.init_page_pool(LAYOUT_PAGES, PS, kv_dtype=kv_dtype)
    tlen = lm.max_len // PS
    step, chunk, _, _ = _programs(lm)
    i32 = jnp.int32
    b, rows = 3, 2
    compiled = {
        "step": step.lower(params, bufs, jnp.zeros((b,), i32),
                           jnp.zeros((b,), i32), pool,
                           jnp.zeros((b, tlen), i32)).compile(),
        "chunk": chunk.lower(params, bufs, jnp.zeros((rows, CHUNK), i32),
                             pool, jnp.zeros((rows, tlen), i32),
                             jnp.zeros((rows,), i32),
                             jnp.zeros((rows,), i32)).compile(),
    }
    for name, c in compiled.items():
        hlo = c.as_text()
        assert "scatter" in hlo, name      # the write is in there
        assert _relaid_leaves(hlo, pool) == [], name


def _tables(rows, tlen):
    """Block tables from per-row page lists, scratch-padded."""
    t = np.full((len(rows), tlen), SCRATCH_PAGE, np.int32)
    for i, pages in enumerate(rows):
        t[i, :len(pages)] = pages
    return jnp.asarray(t)


def _copy_page(pool, dst, src):
    # the engine's COW primitive (engine.py copy_page), leaf-blind
    return jax.tree.map(lambda b: b.at[dst].set(b[src]), pool)


def _layout_parity(lm, case, kv_dtype=None, jit_kw=None, state=None,
                   pool_sharding=None):
    """Prefill one chunk and decode three tokens through the page pool
    and through the dense cache; return both logit lists."""
    params, bufs = state or _state(lm)
    step, chunk, d_step, d_chunk = _programs(lm, **(jit_kw or {}))
    tlen = lm.max_len // PS
    r = np.random.RandomState(7)
    live = 2
    lanes = 4 if case == "scratch_collision" else live
    head = r.randint(0, 32, (CHUNK,))              # one whole page
    ids0 = r.randint(0, 32, (lanes, CHUNK)).astype(np.int32)
    if case in ("shared_head", "cow_copy"):
        ids0[:live] = head
    pool = lm.init_page_pool(LAYOUT_PAGES, PS, kv_dtype=kv_dtype,
                             sharding=pool_sharding)
    dense = lm.init_cache(lanes, lm.max_len, kv_dtype=kv_dtype)
    pages = [[1, 2, 3], [4, 5, 6]]
    if case in ("shared_head", "cow_copy"):
        pages[1][0] = 1            # row 1's head IS row 0's page
    # idle lanes: all-scratch tables, position 0 — their junk writes
    # collide on page 0
    tables = _tables(pages + [[]] * (lanes - live), tlen)
    zeros = jnp.zeros((lanes,), jnp.int32)
    last = jnp.full((lanes,), CHUNK - 1, jnp.int32)
    got, want = [], []

    ids = jnp.asarray(ids0)
    if case in ("shared_head", "cow_copy"):
        # row 0 alone writes the shared head page; row 1 rides a
        # scratch table through that dispatch and reads the page after
        lg, pool = chunk(params, bufs, ids, pool,
                         _tables([pages[0], []], tlen), zeros, last)
        got.append(np.asarray(lg)[:1])
    else:
        lg, pool = chunk(params, bufs, ids, pool, tables, zeros, last)
        got.append(np.asarray(lg)[:live])
    lg, dense = d_chunk(params, bufs, ids, dense, zeros, last)
    want.append(np.asarray(lg)[:got[0].shape[0]])
    if case == "cow_copy":
        pool = _copy_page(pool, 7, 1)   # privatize row 1's head
        tables = _tables([pages[0], [7] + pages[1][1:]], tlen)

    tok = jnp.asarray(r.randint(0, 32, (lanes,)).astype(np.int32))
    for i in range(3):
        pos = jnp.where(jnp.arange(lanes) < live, CHUNK + i, 0)
        lg, pool = step(params, bufs, tok, pos.astype(jnp.int32), pool,
                        tables)
        got.append(np.asarray(lg)[:live])
        lg, dense = d_step(params, bufs, tok, pos.astype(jnp.int32),
                           dense)
        want.append(np.asarray(lg)[:live])
        nxt = np.zeros((lanes,), np.int32)     # idle lanes stay idle
        nxt[:live] = want[-1].argmax(-1)
        tok = jnp.asarray(nxt)
    return got, want, pool


@pytest.mark.parametrize("case,kv_dtype", [
    ("plain", None), ("scratch_collision", None), ("shared_head", None),
    ("cow_copy", None), ("plain", "int8"), ("shared_head", "int8"),
], ids=["plain", "scratch_collision", "shared_head", "cow_copy", "int8",
        "int8_shared_head"])
def test_paged_layout_parity_with_dense(lm, case, kv_dtype):
    """Chunk then decode through block tables into the token-major
    pool: the dense cache's logits in float32 and its greedy tokens —
    with idle lanes colliding on the scratch page, a prefix-shared
    head page, a COW ``copy_page``, and int8 codes + scale sidecars."""
    got, want, _ = _layout_parity(lm, case, kv_dtype)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-6)
        np.testing.assert_array_equal(g.argmax(-1), w.argmax(-1))


def test_paged_layout_parity_draft_round(lm):
    """A draft's proposal round (the gamma-step scan over
    ``decode_step_paged``) proposes what the dense scan proposes, with
    the same step logits."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    params, bufs = _state(draft)
    b, gamma, tlen = 2, 3, lm.max_len // PS
    tables = _tables([[1, 2, 3], [4, 5, 6]], tlen)
    tok = jnp.asarray([5, 9], jnp.int32)
    pos = jnp.asarray([0, 0], jnp.int32)
    key, one = jax.random.PRNGKey(0), jnp.float32(1.0)
    toks, qlog, pool = draft._propose_fn_paged(b, gamma, tlen)(
        params, bufs, tok, pos, draft.init_page_pool(LAYOUT_PAGES, PS),
        tables, key, one)
    d_toks, d_qlog, _ = draft._propose_fn(b, gamma)(
        params, bufs, tok, pos, draft.init_cache(b, lm.max_len), key, one)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(d_toks))
    np.testing.assert_allclose(np.asarray(qlog), np.asarray(d_qlog),
                               rtol=0, atol=1e-6)
    # the scan wrote gamma tokens a row, each row into its first page
    leaf = np.asarray(jax.tree.leaves(pool)[0])
    assert np.abs(leaf[1, :gamma]).sum() > 0 and not leaf[1, gamma:].any()


def test_paged_layout_heads_sharded_mesh(lm_tp):
    """On a model mesh of 2 the page pool shards its LAST dimension
    (``kv_page_pool_spec``): each device holds its own heads' slice of
    every row, the programs keep that sharding on the donated pool,
    and the logits match the dense cache's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu.parallel import (
        Engine, kv_page_pool_spec, shard_params, transformer_tp_rules,
    )

    assert kv_page_pool_spec("model") == P(None, None, "model")
    mesh2 = Engine.create_mesh([("model", 2)], devices=jax.devices()[:2])
    kv = lm_tp.kv_page_pool_sharding(mesh2)
    repl = NamedSharding(mesh2, P())
    params, bufs = _state(lm_tp)
    state = (shard_params(params, mesh2, transformer_tp_rules("model")),
             jax.device_put(bufs, repl))
    got, want, pool = _layout_parity(
        lm_tp, "scratch_collision", state=state, pool_sharding=kv,
        jit_kw={"out_shardings": (repl, kv)})
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5)
    attn = lm_tp.block0.attn
    for leaf in jax.tree.leaves(pool):
        assert leaf.sharding.is_equivalent_to(kv, leaf.ndim)
        assert leaf.addressable_shards[0].data.shape == (
            LAYOUT_PAGES, PS, attn.num_kv_heads * attn.head_dim // 2)
    with pytest.raises(ValueError, match="divide evenly"):
        lm_tp.kv_page_pool_sharding(
            Engine.create_mesh([("model", 8)], devices=jax.devices()[:8]))


# ------------------------------------------------ decode attention forms
# One query token a row meets its gathered pages in one of two forms
# (nn/attention.py): "rows" — K and V as the pool stores them, q on a
# block diagonal, the engine without a mesh — and "heads" — the
# per-head view, the mesh engine. Same pages in, same numbers out.

def _step_attention_case(case, dtype):
    """(attn, x_t, pool, tables, pos): 4 lanes over a 4-page table —
    a row at position 0, one at the table's LAST position, one partly
    filled (its tail slots on the scratch page) and an idle lane parked
    on the scratch page — over a pool of random content."""
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.utils import random as rnd

    heads, kv_heads, rotary, kv_dtype = {
        "plain": (4, 4, False, None), "gqa2": (4, 2, False, None),
        "gqa4": (8, 2, False, None), "rotary": (4, 2, True, None),
        "int8": (4, 2, False, "int8")}[case]
    rnd.set_seed(30)
    attn = MultiHeadAttention(8 * heads, heads, num_kv_heads=kv_heads,
                              rotary=rotary)
    attn.evaluate()
    attn.load_params_dict(jax.tree.map(lambda a: a.astype(dtype),
                                       attn.params_dict()))
    tlen, pages = 4, 12
    r = np.random.RandomState(30)
    pool = attn.init_page_pool(pages, PS, dtype=dtype, kv_dtype=kv_dtype)
    if kv_dtype is None:
        pool = tuple(jnp.asarray(r.standard_normal(b.shape), dtype)
                     for b in pool)
    else:
        pool = tuple(
            jnp.asarray(r.randint(-127, 128, b.shape), jnp.int8)
            if b.dtype == jnp.int8
            else jnp.asarray(r.uniform(0.005, 0.05, b.shape), jnp.float32)
            for b in pool)
    tables = _tables([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10], []], tlen)
    pos = jnp.asarray([0, tlen * PS - 1, 6, 0], jnp.int32)
    x_t = jnp.asarray(r.standard_normal((4, 1, 8 * heads)), dtype)
    return attn, x_t, pool, tables, pos


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case",
                         ["plain", "gqa2", "gqa4", "rotary", "int8"])
def test_decode_attention_rows_equals_heads(case, dtype):
    """``forward_step_paged`` under ``decode_attention="rows"`` and
    ``"heads"``: the same pool written, the same output — float32 to
    1e-6, bfloat16 to one bfloat16 step of the output (the products
    with the block diagonal's zeros add nothing; both forms accumulate
    in float32) — for plain heads, GQA with 2 and 4 query heads a kv
    head, rotary positions, and the int8 pool with its sidecars."""
    attn, x_t, pool, tables, pos = _step_attention_case(case, dtype)
    o_rows, p_rows = attn.forward_step_paged(x_t, pool, tables, pos,
                                             decode_attention="rows")
    o_heads, p_heads = attn.forward_step_paged(x_t, pool, tables, pos,
                                               decode_attention="heads")
    for a, b in zip(p_rows, p_heads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ... and the same values handed to the attention, bit for bit: as
    # rows the int8 sidecar is spread over each head's columns by an
    # exact one-hot product, not dequantize_kv's broadcast
    from bigdl_tpu.nn.attention import _write_kv_paged

    k_t = jnp.zeros((4, attn.num_kv_heads, 1, attn.head_dim), dtype)
    views = [_write_kv_paged(pool, k_t, k_t, tables, pos, rows=rows)[1:]
             for rows in (True, False)]
    for as_rows, per_head in zip(*views):
        assert as_rows.ndim == 3 and per_head.ndim == 4
        np.testing.assert_array_equal(
            np.asarray(as_rows.astype(jnp.float32)),
            np.asarray(per_head.astype(jnp.float32)).reshape(
                as_rows.shape))
    assert o_rows.dtype == o_heads.dtype == dtype
    got = np.asarray(o_rows.astype(jnp.float32))
    want = np.asarray(o_heads.astype(jnp.float32))
    assert np.isfinite(want).all() and np.abs(want).max() > 0.01
    # bfloat16 keeps 8 significant bits: one step at the largest output
    atol = 1e-6 if dtype == jnp.float32 else np.abs(want).max() * 2.0 ** -8
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_decode_attention_form_is_named_and_checked(lm, lm_tp, mesh):
    """The engine builds its paged step with the rows form when it has
    no mesh and with the heads form when it has one, and says which in
    ``stats()["paging"]``; an unknown form is refused."""
    eng = ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=CHUNK,
                                   page_size=PS)
    try:
        assert eng.stats()["paging"]["decode_attention"] == "rows"
    finally:
        eng.stop()
    eng = ContinuousBatchingEngine(lm_tp, max_slots=2, prefill_chunk=CHUNK,
                                   page_size=PS, mesh=mesh)
    try:
        assert eng.stats()["paging"]["decode_attention"] == "heads"
    finally:
        eng.stop()
    attn, x_t, pool, tables, pos = _step_attention_case("plain",
                                                        jnp.float32)
    with pytest.raises(ValueError, match="decode_attention"):
        attn.forward_step_paged(x_t, pool, tables, pos,
                                decode_attention="columns")
