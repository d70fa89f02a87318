"""Two kinds of state in one cache manager (``serving/engine.py``,
``serving/paging.py``): a hybrid decoder's recurrent state lives per lane
beside the pages of its full-attention layers, a prefix hit resumes from a
state snapshot, a preempted request from its deepest one. Every request's
served tokens are held to the benchmark's plain reference (float32, so the
served token is the reference's argmax at every position, and its logits
through the paged pass agree)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bigdl_tpu import observability as obs  # noqa: E402
from bigdl_tpu.observability import trace  # noqa: E402
from bigdl_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from bigdl_tpu.serving import engine as engine_mod  # noqa: E402
from bigdl_tpu.serving.paging import (  # noqa: E402
    PagedPrefixIndex, PagePool, SnapshotStore, lane_leaves, page_leaves,
)
from hybrid_tiny import built, tiny_config  # noqa: E402

CONFIG = tiny_config(positions=128)
KW = dict(max_slots=3, prefill_chunk=8, prefill_rows=2, page_size=4)


@pytest.fixture(scope="module")
def lm():
    return built(CONFIG, 7)


@pytest.fixture()
def reg():
    r = obs.MetricRegistry()
    prev = obs.set_default_registry(r)
    try:
        yield r
    finally:
        obs.set_default_registry(prev)


def held_to_reference(w, prompt, served):
    """Every served token is the reference's argmax given what came before,
    by a margin: logits, not only tokens, agree."""
    from benchmark import compare
    from benchmark.reference import olmo_hybrid as ref

    row = np.concatenate([prompt, served]).astype(np.int32)
    logits = ref.forward(w, row[None], CONFIG)[0]
    gaps = compare.served_token_gaps(logits, row, len(prompt))
    assert gaps.max() == 0.0, gaps
    return logits


def test_cold_hit_and_a_lane_waiting_in_admission_match_the_reference(lm, reg):
    model, w = lm
    rng = np.random.RandomState(0)
    doc = rng.randint(0, 120, 40).astype(np.int32)
    ask = lambda n: np.concatenate([doc, rng.randint(0, 120, n)]).astype(np.int32)
    with ContinuousBatchingEngine(model, **KW) as eng:
        state = eng.stats()["paging"]["state"]
        assert state["lanes"] == 3
        assert state["snapshot_capacity"] == \
            engine_mod.SNAPSHOTS_PER_LANE * 3
        # lane bytes over one token's KV bytes, in whole chunks
        lane_bytes = 3 * (3 * 8 * 16 * 4 + 3 * 3 * (2 * 8 + 16) * 4)
        token_bytes = 1 * 2 * 4 * 8 * 4
        assert state["snapshot_bytes"] == lane_bytes
        assert state["snapshot_stride_tokens"] == 8 * -(-lane_bytes // (
            token_bytes * 8)) == 32
        cold = ask(9)
        h = eng.submit(cold, 12)
        served = np.asarray(h.result(timeout=300))[len(cold):]
        assert h.prefix_tokens == 0
        held_to_reference(w, cold, served)
        assert eng.stats()["paging"]["state"]["taken_total"] == 1   # at 32
        # a hit on the document: pages shared up to the snapshot at 32,
        # the state copied back, the rest prefilled
        warm = ask(14)
        trace.reset()
        # ... while a long request decodes beside it: the hit's lane sits
        # in admission (3 chunks) as the decode steps run over ALL lanes
        beside = rng.randint(0, 120, 11).astype(np.int32)
        h_long = eng.submit(beside, 30)
        next(h_long.tokens())
        h = eng.submit(warm, 10)
        served = np.asarray(h.result(timeout=300))[len(warm):]
        assert h.prefix_tokens == 32
        held_to_reference(w, warm, served)
        held_to_reference(w, beside,
                          np.asarray(h_long.result(timeout=300))[11:])
        st = eng.stats()
        assert st["paging"]["state"]["restored_total"] == 1
        assert st["jit_compiles"] == 6   # step, chunk, sample, page copy,
        #                                  restore, snapshot: none on load
        spans = trace.export(names=["serving/state_restore",
                                    "serving/state_snapshot",
                                    "serving/prefill_dispatch"])
        restore = [r for r in spans if r["name"] == "serving/state_restore"]
        assert [r["attrs"] for r in restore] == [
            {"matched_tokens": 40, "resumed_tokens": 32,
             "bytes": lane_bytes}]
        assert reg.get("bigdl_serving_state_restored_total") is not None
    assert eng._snaps.in_use == 0 and eng._pages.pages_in_use == 0


def test_a_preempted_request_resumes_from_its_snapshot_token_identical(lm, reg):
    model, w = lm
    rng = np.random.RandomState(1)
    victim = rng.randint(0, 120, 37).astype(np.int32)
    urgent = rng.randint(0, 120, 6).astype(np.int32)
    with ContinuousBatchingEngine(model, **dict(KW, max_slots=1),
                                  preempt_slack_s=0.002) as eng:
        h_low = eng.submit(victim, 40, priority="low")
        next(h_low.tokens())
        h_high = eng.submit(urgent, 4, priority="high")
        held_to_reference(w, urgent,
                          np.asarray(h_high.result(timeout=300))[6:])
        held_to_reference(w, victim,
                          np.asarray(h_low.result(timeout=300))[37:])
        assert h_low.preempted >= 1
        st = eng.stats()["paging"]["state"]
        assert st["restored_total"] >= 1      # from the snapshot at 32
        assert eng.stats()["jit_compiles"] == 6


def test_a_match_no_snapshot_stands_under_is_prefilled_again(lm, reg):
    """The pages alone would have covered 24 tokens (the stride is 32, so no
    snapshot was ever taken under them): nothing is reused, the shortfall is
    counted, and the answer is still the reference's."""
    model, w = lm
    rng = np.random.RandomState(2)
    head = rng.randint(0, 120, 26).astype(np.int32)
    with ContinuousBatchingEngine(model, **KW) as eng:
        first = np.concatenate([head, rng.randint(0, 120, 3)]).astype(np.int32)
        eng.submit(first, 4).result(timeout=300)
        second = np.concatenate([head, rng.randint(0, 120, 5)]).astype(np.int32)
        trace.reset()
        h = eng.submit(second, 6)
        held_to_reference(w, second, np.asarray(h.result(timeout=300))[31:])
        assert h.prefix_tokens == 0
        st = eng.stats()["paging"]["state"]
        assert st["hits_shortened_total"] == 1
        assert st["shortfall_tokens_total"] == 24
        span, = trace.export(names=["serving/state_restore"])
        assert span["attrs"] == {"matched_tokens": 24, "resumed_tokens": 0,
                                 "bytes": 0}


def test_a_hit_whose_own_entry_the_page_sweep_evicts_keeps_its_snapshot(lm, reg):
    """A pool so tight that the admission which hits the one cached entry
    must reclaim that entry for its fresh pages: the entry's eviction frees
    its snapshots, so the admission's own reference on the one it resumes
    from has to be taken before the sweep (taken after it, the engine's loop
    died on ``share() of free snapshot`` and every request with it)."""
    model, w = lm
    rng = np.random.RandomState(5)
    doc = rng.randint(0, 120, 40).astype(np.int32)
    ask = lambda n: np.concatenate([doc, rng.randint(0, 120, n)]).astype(np.int32)
    # 18 pages to hand out: the first request's entry keeps 13, and the
    # second needs 16 (64 tokens) less the 8 it shares = 8 fresh of 5 free
    with ContinuousBatchingEngine(model, max_len=64, max_pages=19,
                                  **dict(KW, max_slots=1)) as eng:
        eng.submit(ask(9), 4).result(timeout=300)
        assert eng.stats()["paging"]["pool"]["free_pages"] == 5
        assert eng.stats()["paging"]["state"]["snapshots_in_use"] == 1
        warm = ask(14)
        h = eng.submit(warm, 10)
        served = np.asarray(h.result(timeout=120))[len(warm):]
        assert h.prefix_tokens == 32
        held_to_reference(w, warm, served)
        st = eng.stats()
        assert st["prefix_cache"]["evictions"] == 1      # its own entry
        assert st["paging"]["state"]["restored_total"] == 1
        assert st["paging"]["state"]["taken_total"] == 1  # none since
    assert eng._snaps.in_use == 0 and eng._pages.pages_in_use == 0


def test_copy_page_and_the_byte_summaries_read_the_pages_alone(lm, reg):
    model, _ = lm
    with ContinuousBatchingEngine(model, **KW) as eng:
        h = eng.submit(np.arange(20, dtype=np.int32), 3)
        h.result(timeout=300)
        lanes_before = jax.tree.map(np.asarray, lane_leaves(eng._kv_pool))
        pages_before = jax.tree.map(np.asarray, page_leaves(eng._kv_pool))
        eng.stop()
        eng._copy_page(7, 1)
        for a, b in zip(jax.tree.leaves(lanes_before),
                        jax.tree.leaves(lane_leaves(eng._kv_pool))):
            assert np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree.leaves(pages_before),
                        jax.tree.leaves(page_leaves(eng._kv_pool))):
            assert np.array_equal(a[1], np.asarray(b)[7])
        page_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(
            page_leaves(eng._kv_pool))) // eng._pages.max_pages
        assert eng._pages.page_bytes == page_bytes == 4 * 2 * 4 * 8 * 4
        pools = eng.stats()["mesh"]["pools"]
        assert pools["kv_page_pool"]["logical_bytes"] == \
            page_bytes * eng._pages.max_pages
        assert pools["lane_state"]["logical_bytes"] == sum(
            int(l.nbytes) for l in jax.tree.leaves(lanes_before))
        assert pools["state_snapshots"]["logical_bytes"] == \
            6 * eng._snaps.snapshot_bytes


@pytest.mark.parametrize("what", ["draft", "host_tier", "mesh", "kv_dtype"])
def test_what_lane_state_cannot_do_yet_is_refused_at_construction(lm, what):
    model, _ = lm
    kw = dict(KW)
    if what == "draft":
        kw["draft"] = model
    elif what == "host_tier":
        kw["prefix_host_rows"] = 2
    elif what == "mesh":
        from bigdl_tpu.parallel import Engine

        kw["mesh"] = Engine.create_mesh([("model", 2)],
                                        devices=jax.devices()[:2])
    else:
        kw["kv_dtype"] = "int8"
    with pytest.raises(ValueError, match="not served with lane state"):
        ContinuousBatchingEngine(model, **kw)


def test_the_benchmark_adapter_reckons_the_store_the_engine_derives():
    from benchmark.models import olmo_hybrid as adapter

    assert adapter.SNAPSHOTS_PER_LANE == engine_mod.SNAPSHOTS_PER_LANE


# ------------------------------------------------------- host bookkeeping
def index_with_store(pages=40, snaps=6, entries=3):
    pool = PagePool([(np.zeros((pages, 4, 8), np.float32),) * 2], 4)
    store = SnapshotStore(snaps, 1000)
    return pool, store, PagedPrefixIndex(
        pool, max_entries=entries, min_tokens=4, token_bytes=64.0,
        snapshots=store)


def test_a_match_is_as_long_as_the_deepest_snapshot_under_it():
    pool, store, index = index_with_store()
    toks = np.arange(30, dtype=np.int32)
    held = pool.alloc(8)
    s8, s16 = store.take(), store.take()
    assert index.donate_pages(toks, held, [(8, s8), (16, s16)])
    pool.free(held), store.free([s8, s16])          # the donor's own
    assert store.in_use == 2 and store.refcount(s16) == 1
    probe = np.concatenate([toks[:22], [99, 98, 97]]).astype(np.int32)
    m = index.match(probe)
    assert (m.length, m.matched) == (16, 22) and m.entry.resume_at(16) == (16, s16)
    assert index.match(probe)[:2] == (m.entry, 16)
    # the prompt's last position is always computed: a prompt of 16 tokens
    # resumes at 8, not 16
    assert index.match(toks[:16]).length == 8
    # an entry without snapshots matches at length 0 but is still a match
    assert index.match(toks[:6])[1:] == (0, 6)
    # among the entries below a divergence the one that resumes deepest wins
    other = np.concatenate([toks[:20], [70, 71, 72, 73]]).astype(np.int32)
    held2 = pool.alloc(6)
    assert index.donate_pages(other, held2, [])
    pool.free(held2)
    assert index.match(probe).entry is m.entry
    index.drop_all()
    assert store.in_use == 0 and pool.pages_in_use == 0


def test_no_snapshot_or_page_leaks_over_1000_admissions():
    """Donate, hit (inherit the snapshot), take more, evict by the entry cap
    and by ``reclaim_snapshot``: after 1000 rounds and a final drop the store
    and the pool are empty, and a snapshot went with each evicted entry."""
    pool, store, index = index_with_store(pages=400, snaps=8, entries=4)
    rng = np.random.RandomState(0)
    docs = [rng.randint(0, 1000, 16).astype(np.int32) for _ in range(3)]
    skipped = 0
    for i in range(1000):
        prompt = np.concatenate([docs[i % 3], rng.randint(0, 1000, 9)])
        m = index.match(prompt.astype(np.int32))
        snaps = []
        if m.length:
            sid = m.entry.resume_at(m.length)[1]
            store.share([sid]), store.touch(sid)
            snaps.append((m.length, sid))
        held = pool.alloc(7)
        for pos in (8, 16, 24):
            if pos > m.length:
                sid = store.take()
                if sid is None and index.reclaim_snapshot():
                    sid = store.take()
                if sid is None:
                    skipped += 1
                    continue
                snaps.append((pos, sid))
        index.donate_pages(prompt.astype(np.int32), held, snaps)
        pool.free(held)
        store.free([sid for _, sid in snaps])
        assert store.in_use <= 8 and len(index) <= 4
        assert store.in_use == len({sid for e in index._entries
                                    for _, sid in e.snaps})
    assert index.evictions > 900 and store.freed > 900
    index.drop_all()
    assert store.in_use == 0 and pool.pages_in_use == 0
    assert store.stats()["taken_total"] == store.stats()["freed_total"]


def test_reclaim_snapshot_gives_up_what_nothing_resumed_from_for_longest():
    pool, store, index = index_with_store(snaps=3)
    toks = np.arange(40, dtype=np.int32)
    held = pool.alloc(10)
    sids = [store.take() for _ in range(3)]
    index.donate_pages(toks, held, list(zip((8, 16, 24), sids)))
    pool.free(held), store.free(sids)
    store.touch(sids[0])                 # 8 was restored; 16 is the oldest
    assert store.take() is None
    assert index.reclaim_snapshot()
    entry = index.match(toks).entry
    assert [p for p, _ in entry.snaps] == [8, 24]
    # a snapshot a request in flight holds is not given up
    for _, sid in entry.snaps:
        store.share([sid])
    again = store.take()
    assert again is not None and not index.reclaim_snapshot()
    with pytest.raises(ValueError, match="no host tier"):
        PagedPrefixIndex(pool, max_entries=2, host_pages=4, snapshots=store)


def test_a_request_keeps_its_newest_snapshot_and_the_boundary_it_matched(lm, reg):
    """Stride 32. A cold prompt of 100 tokens passes 32, 64, 96 and holds
    only 96 at the end (the store is not filled with states nothing resumes
    from). A second prompt that shares its first 70 tokens resumes nowhere
    (no snapshot at or under 70 is left), is told by its match that prompts
    branch after 64, and keeps 64 beside its newest; a third resumes there."""
    model, w = lm
    rng = np.random.RandomState(5)
    first = rng.randint(0, 120, 100).astype(np.int32)
    fork = lambda n: np.concatenate(
        [first[:70], rng.randint(0, 120, n)]).astype(np.int32)
    with ContinuousBatchingEngine(model, **KW) as eng:
        held_to_reference(w, first, np.asarray(
            eng.submit(first, 3).result(timeout=300))[100:])
        st = eng.stats()["paging"]["state"]
        assert st["taken_total"] == 3 and st["snapshots_in_use"] == 1
        second = fork(30)
        h = eng.submit(second, 3)
        held_to_reference(w, second, np.asarray(h.result(timeout=300))[100:])
        assert h.prefix_tokens == 0
        snaps = sorted(p for e in eng._prefix._entries for p, _ in e.snaps)
        assert snaps == [64, 96, 96]
        third = fork(9)
        h = eng.submit(third, 3)
        held_to_reference(w, third, np.asarray(h.result(timeout=300))[79:])
        assert h.prefix_tokens == 64
        st = eng.stats()["paging"]["state"]
        assert st["hits_shortened_total"] == 1       # the second's
        assert st["restored_total"] == 1


# ---------------------------- a compressed-key cache beside K, V and the lanes
# three lightning layers and two sparse ones (layers 1..5 of the tiny list):
# a lane's 3072 bytes over a token's 288 (K, V and a quarter of a compressed
# key in each sparse layer) is 10.7 tokens: a snapshot every 12, in chunks of
# 4: boundaries that are NOT multiples of a span's 8 tokens
import sala_tiny  # noqa: E402

SALA = sala_tiny.tiny_config(positions=192, layers=5)
SALA_KW = dict(max_slots=3, prefill_chunk=4, prefill_rows=2, page_size=4)


@pytest.fixture(scope="module")
def sala():
    return sala_tiny.built(SALA, 9)


def sala_held_to_reference(w, prompt, served):
    from benchmark import compare
    from benchmark.reference import minicpm_sala as ref

    row = np.concatenate([prompt, served]).astype(np.int32)
    logits = ref.forward(w, row[None], SALA)[0]
    gaps = compare.served_token_gaps(logits, row, len(prompt))
    assert gaps.max() == 0.0, gaps


def test_sala_cold_then_a_hit_whose_boundary_splits_a_compressed_key_span(sala, reg):
    """A cold request of 95 + 40 tokens (past ``dense_len`` 64, where six
    blocks of 16 are taken of up to nine), then one that shares its first 90
    tokens: the pages match up to 88 and the hit resumes at 84, the deepest
    snapshot under the match (a multiple of 12, not of a span's 8: the span
    of tokens 80..87 ends in the request's own first page and is the
    request's to compute, from K of a page it shares). Both are the
    reference's argmax at every token."""
    model, w = sala
    rng = np.random.RandomState(0)
    doc = rng.randint(0, 120, 90).astype(np.int32)
    ask = lambda n: np.concatenate([doc, rng.randint(0, 120, n)]).astype(np.int32)
    with ContinuousBatchingEngine(model, **SALA_KW) as eng:
        state = eng.stats()["paging"]["state"]
        lane_bytes = 3 * 4 * 8 * 8 * 4
        assert state["snapshot_bytes"] == lane_bytes == 3072
        # the pages' bytes count the compressed keys: 2 layers x (K and V of
        # 2 KV heads of 8 a token, and 16 elements a page of 4), float32
        assert eng._pages.page_bytes == 2 * (4 * 2 * 2 * 8 + 2 * 8) * 4
        assert state["snapshot_stride_tokens"] == 12
        cold = ask(5)        # 95 tokens: its newest snapshot stands at 84
        h = eng.submit(cold, 40)
        served = np.asarray(h.result(timeout=600))[len(cold):]
        assert h.prefix_tokens == 0
        sala_held_to_reference(w, cold, served)
        warm = ask(17)
        trace.reset()
        h = eng.submit(warm, 24)
        served = np.asarray(h.result(timeout=600))[len(warm):]
        assert h.prefix_tokens == 84 and 84 % 8 == 4
        sala_held_to_reference(w, warm, served)
        st = eng.stats()
        assert st["paging"]["state"]["restored_total"] == 1
        assert st["jit_compiles"] == 6          # nothing new compiles on a hit
        # what the selection read, from the rows' positions at dispatch
        spans = trace.export(names=["serving/decode_dispatch"])
        assert len(spans) == 23
        first = spans[0]["attrs"]
        at = len(warm)                          # 107: 7 blocks seen, 6 taken
        assert first == {"rows": 1, "cached_tokens": at + 1,
                         "attended_tokens": 96 - (15 - at % 16),
                         "gathered_tokens": 96, "selecting_rows": 1}
        total = lambda name: reg.get(name).labels(
            service=eng.service_name).get()
        assert total("bigdl_serving_selecting_decode_rows_total") >= 23
        assert total("bigdl_serving_selected_attended_tokens_total") < \
            total("bigdl_serving_selected_cached_tokens_total")
    assert eng._snaps.in_use == 0 and eng._pages.pages_in_use == 0


def test_sala_a_request_hits_a_document_its_first_asker_is_still_decoding(sala, reg):
    """With ``donate_at_prefill_end`` the first request for a document
    donates its prompt's pages and its snapshots when its PREFILL ends: one
    that asks for the same 90 tokens
    while the first still decodes resumes at 84 and does not prefill the
    document again. Both are the reference's argmax at every token, and the
    entry the first donates at its end takes its first one's place."""
    model, w = sala
    rng = np.random.RandomState(3)
    doc = rng.randint(0, 120, 90).astype(np.int32)
    ask = lambda n: np.concatenate([doc, rng.randint(0, 120, n)]).astype(np.int32)
    first, second = ask(5), ask(9)
    with ContinuousBatchingEngine(model, **SALA_KW,
                                  donate_at_prefill_end=True) as eng:
        h1 = eng.submit(first, 60)
        next(h1.tokens())
        h2 = eng.submit(second, 12)
        served2 = np.asarray(h2.result(timeout=600))[len(second):]
        assert not h1.done() and h2.prefix_tokens == 84
        sala_held_to_reference(w, second, served2)
        sala_held_to_reference(
            w, first, np.asarray(h1.result(timeout=600))[len(first):])
        prefix = eng.stats()["prefix_cache"]
        assert prefix["entries"] == 2 and prefix["evictions"] == 0
        assert eng.stats()["paging"]["state"]["restored_total"] == 1
    assert eng._snaps.in_use == 0 and eng._pages.pages_in_use == 0


def test_sala_a_preempted_request_resumes_with_its_pages_and_compressed_keys(sala, reg):
    model, w = sala
    rng = np.random.RandomState(1)
    victim = rng.randint(0, 120, 90).astype(np.int32)
    urgent = rng.randint(0, 120, 6).astype(np.int32)
    with ContinuousBatchingEngine(model, **dict(SALA_KW, max_slots=1),
                                  preempt_slack_s=0.002) as eng:
        h_low = eng.submit(victim, 40, priority="low")
        next(h_low.tokens())
        h_high = eng.submit(urgent, 4, priority="high")
        sala_held_to_reference(w, urgent,
                               np.asarray(h_high.result(timeout=600))[6:])
        sala_held_to_reference(w, victim,
                               np.asarray(h_low.result(timeout=600))[90:])
        assert h_low.preempted >= 1
        assert eng.stats()["paging"]["state"]["restored_total"] >= 1
        assert eng.stats()["jit_compiles"] == 6


@pytest.mark.parametrize("what", ["draft", "host_tier", "mesh", "kv_dtype"])
def test_sala_what_lane_state_cannot_do_yet_is_refused_here_too(sala, what):
    model, _ = sala
    kw = {"draft": dict(draft=model),
          "host_tier": dict(prefix_host_rows=2),
          "mesh": dict(mesh=object()),
          "kv_dtype": dict(kv_dtype="int8")}[what]
    with pytest.raises(ValueError, match="lane state"):
        ContinuousBatchingEngine(model, **SALA_KW, **kw)


def test_the_sala_adapter_reckons_the_page_and_the_store_the_engine_derives(sala):
    from benchmark.models import minicpm_sala as adapter

    model, _ = sala
    geometry = adapter.cache_geometry(SALA)
    with ContinuousBatchingEngine(model, **SALA_KW) as eng:
        assert geometry["page_device_bytes"] == eng._pages.page_bytes
        assert adapter.lane_state_bytes(SALA) == eng._snaps.snapshot_bytes
        assert adapter.SNAPSHOTS_PER_LANE == engine_mod.SNAPSHOTS_PER_LANE
    assert geometry["fixed_device_bytes_per_lane"] == int(
        3072 * (1 + 1 / 3 + 2))
