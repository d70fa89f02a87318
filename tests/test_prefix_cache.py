"""Prefix-aware KV reuse + batched multi-row prefill (bigdl_tpu/serving/).

The acceptance contract under test: with the prefix cache WARM (prior
requests donated their KV), every request still gets EXACTLY the tokens
a lone greedy ``model.generate`` call would produce — reuse changes the
WORK, never the tokens — while ``stats()`` shows hits, reused tokens,
and byte occupancy, and the compiled-program gauge stays flat (hit,
miss, donation, and eviction paths all run through construction-warmed
executables). Plus the satellites: the radix-trie match semantics
(exact / partial / truncated), LRU + ref-count eviction under byte
pressure, the ``AdmissionQueue.put`` dead-deadline rejection,
prefix-aware admission ordering with its starvation bound, multi-row
batched prefill parity, and the ``scripts/perf_gate.py`` CI gate."""

import json
import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.serving import (
    AdmissionQueue, ContinuousBatchingEngine, PagedPrefixIndex, PagePool,
    PrefillPolicy, RequestTimedOut,
)
from bigdl_tpu.serving.streams import RequestHandle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(21)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


def _direct(lm, prompt, n, eos=None):
    """The per-request oracle: a lone greedy generate, trimmed at the
    first eos (the engine stops there instead of emitting the padding
    tail)."""
    want = np.asarray(
        lm.generate(jnp.asarray(prompt)[None], n, eos_id=eos))[0]
    if eos is not None:
        gen = want[len(prompt):]
        hits = np.flatnonzero(gen == eos)
        if hits.size:
            want = want[:len(prompt) + hits[0] + 1]
    return want


# --------------------------------------------------------- trie units
PS = 4          # page_size of the index units: an 8-token key is 2 pages


def _index(max_pages, **kw):
    """A prefix index over a small pool of a one-layer (k, v) tree."""
    leaf = np.zeros((max_pages, PS, 8), np.float32)
    pool = PagePool(((leaf, leaf.copy()),), PS)
    return pool, PagedPrefixIndex(pool, **kw)


def _serve(pool, pc, tokens):
    """One request's life on the page surface: reserve its pages
    (reclaiming retained prefixes under pressure), finish, offer them
    to the index, free its own references. None = the pool could not
    hold it; else whether the donation was accepted."""
    n = -(-len(tokens) // PS)
    pages = pool.alloc(n)
    if pages is None:
        pc.reclaim(n)
        pages = pool.alloc(n)
    if pages is None:
        return None
    accepted = pc.donate_pages(tokens, pages) is not None
    pool.free(pages)
    return accepted


def test_radix_trie_match_semantics():
    pool, pc = _index(12, max_entries=4, min_tokens=4)
    t1 = np.arange(1, 9, dtype=np.int32)               # [1..8]
    t2 = np.asarray([1, 2, 3, 4, 9, 9, 9, 9], np.int32)  # splits at 4
    assert _serve(pool, pc, t1)
    assert _serve(pool, pc, t2)
    assert len(pc) == 2

    # exact: the full entry is a prefix of the prompt
    e, m = pc.match(np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 30], np.int32))[:2]
    assert m == 8 and np.array_equal(e.tokens, t1)
    # partial: prompt diverges mid-entry — the shared head still counts
    e, m = pc.match(np.asarray([1, 2, 3, 4, 5, 6, 30, 30], np.int32))[:2]
    assert m == 6 and np.array_equal(e.tokens, t1)
    # truncated: the prompt is SHORTER than every entry — KV causality
    # still makes the shared head valid
    e, m = pc.match(np.asarray([1, 2, 3, 4, 9], np.int32))[:2]
    assert m == 5 and np.array_equal(e.tokens, t2)
    # below the min_tokens floor: no match
    e, m = pc.match(np.asarray([1, 2, 3, 30], np.int32))[:2]
    assert e is None and m == 0
    # total miss
    e, m = pc.match(np.asarray([7, 7, 7, 7, 7], np.int32))[:2]
    assert e is None and m == 0
    # lookup is PURE: nothing above moved the counters
    assert pc.stats()["hits"] == 0 and pc.stats()["misses"] == 0

    # covered donation: a prefix of an existing entry adds nothing
    assert _serve(pool, pc, t1[:6]) is False
    assert len(pc) == 2 and pc.stats()["donations"] == 2

    # donate COPIES the key: a caller mutating its buffer afterwards
    # (e.g. a client reusing one preallocated prompt array) must not
    # rewrite the trie under the entry's retained KV
    buf = np.asarray([5, 5, 5, 5, 5, 5], np.int32)
    assert _serve(pool, pc, buf)
    buf[:] = 9
    e, m = pc.match(np.asarray([5, 5, 5, 5, 5, 5, 1], np.int32))[:2]
    assert m == 6 and np.array_equal(e.tokens, [5] * 6)
    # an entry holds exactly the pages that cover its key
    assert len(e.pages) == 2 and pc.stats()["pages"] == 6


def test_lru_and_refcount_eviction_under_byte_pressure():
    # 6 allocatable pages, 2 entries of 2 pages each: the entry cap
    # binds first, then (below) the pool's bytes do
    pool, pc = _index(7, max_entries=2, min_tokens=4)
    t1 = np.asarray([1] * 8, np.int32)
    t2 = np.asarray([2] * 8, np.int32)
    t3 = np.asarray([3] * 8, np.int32)
    assert _serve(pool, pc, t1) and _serve(pool, pc, t2)
    assert pc.bytes_in_use == 4 * pool.page_bytes == pool.bytes_in_use
    assert pc.capacity_bytes == pool.capacity_bytes
    # touch t1 so t2 is the LRU victim
    e1, _ = pc.match(t1)[:2]
    pc.record_hit(e1, 8)
    assert _serve(pool, pc, t3) and pc.stats()["evictions"] == 1
    assert pc.match(t2).entry is None          # t2 evicted
    assert pc.match(t1).entry is not None      # t1 survived (recently used)

    # ref-count: a PINNED entry is never evicted, even at full budget
    pc.acquire(e1)
    t4 = np.asarray([4] * 8, np.int32)
    e3, _ = pc.match(t3)[:2]
    pc.acquire(e3)
    assert _serve(pool, pc, t4) is False     # both pinned: declined
    pc.release(e3)
    assert _serve(pool, pc, t4)              # t3 evictable now
    assert pc.match(t1).entry is e1            # the pinned entry survived

    # byte pressure: with the pool full, a request's pages come out of
    # the LRU UNPINNED entry — never the pinned one
    live = pool.alloc(2)
    assert pool.free_pages == 0 and not pc.reclaim(4)
    assert pool.free_pages == 2 and pc.match(t4).entry is None
    assert pc.match(t1).entry is e1 and e1.pages
    pool.free(live)
    pc.release(e1)
    with pytest.raises(RuntimeError, match="acquire"):
        pc.release(e1)


def test_policy_and_cache_validation():
    with pytest.raises(ValueError, match="prefill_rows"):
        PrefillPolicy(chunk=4, prefill_rows=0)
    with pytest.raises(ValueError, match="max_entries"):
        _index(4, max_entries=-1)
    with pytest.raises(ValueError, match="min_tokens"):
        _index(4, max_entries=1, min_tokens=0)
    with pytest.raises(ValueError, match="host_pages"):
        _index(4, max_entries=1, host_pages=-1)
    # max_entries=0 is the disabled cache: donations are declined,
    # lookups miss
    pool, pc = _index(4, max_entries=0)
    assert _serve(pool, pc, np.arange(8, dtype=np.int32)) is False
    assert pc.match(np.arange(8, dtype=np.int32))[:2] == (None, 0)
    assert pool.pages_in_use == 0


# ------------------------------------------------- scheduler satellites
def test_put_rejects_dead_deadline_at_wakeup():
    """A request whose deadline expires while BLOCKED on a full queue
    must be rejected with RequestTimedOut at wake-up — not admitted
    with a dead deadline, and not left sleeping out the full put
    timeout."""
    q = AdmissionQueue(capacity=1)
    q.put(RequestHandle(np.asarray([1]), 2))  # fill the queue
    h = RequestHandle(np.asarray([2]), 2, timeout_s=0.05)
    t0 = time.monotonic()
    with pytest.raises(RequestTimedOut, match="full admission queue"):
        q.put(h, block=True, timeout=30.0)
    assert time.monotonic() - t0 < 5.0, \
        "must wake at the DEADLINE, not the 30s put timeout"
    # an already-expired deadline is rejected immediately
    h2 = RequestHandle(np.asarray([3]), 2, timeout_s=0.0)
    time.sleep(0.002)
    with pytest.raises(RequestTimedOut):
        q.put(h2, block=True)


def test_pop_ready_prefix_aware_window_and_starvation_bound():
    q = AdmissionQueue(capacity=8)
    score = lambda h: 10 if h.prompt[0] == 1 else 0  # noqa: E731

    plain = RequestHandle(np.asarray([9, 9]), 2)
    hit1 = RequestHandle(np.asarray([1, 1]), 2)
    q.put(plain)
    q.put(hit1)
    h, dropped = q.pop_ready(scorer=score, window=2)
    assert h is hit1 and not dropped  # cached prefix jumps the queue
    # plain is still queued, in order
    assert q.snapshot() == [plain]

    # starvation bound: after `window` consecutive bypasses the next
    # pop is forced FCFS — the head waits at most window admissions
    hit2 = RequestHandle(np.asarray([1, 2]), 2)
    q.put(hit2)
    assert q.pop_ready(scorer=score, window=2)[0] is hit2  # bypass #2
    hit3 = RequestHandle(np.asarray([1, 3]), 2)
    q.put(hit3)
    assert q.pop_ready(scorer=score, window=2)[0] is plain, \
        "bypass cap reached: the starved head must pop next"
    assert q.pop_ready(scorer=score, window=2)[0] is hit3
    # window=1 (or no scorer) is pure FCFS
    a, b = RequestHandle(np.asarray([9]), 2), RequestHandle(
        np.asarray([1]), 2)
    q.put(a)
    q.put(b)
    assert q.pop_ready(scorer=score, window=1)[0] is a
    assert q.pop_ready()[0] is b


def test_engine_submit_timed_out_while_blocked(lm):
    r = np.random.RandomState(11)
    p = r.randint(0, 32, (4,))
    with ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=4,
                                  queue_capacity=1) as eng:
        h_long = eng.submit(p, 24)
        it = h_long.tokens()
        next(it)                 # admitted: slot busy, queue empty
        eng.submit(p, 4)         # fills the 1-deep queue
        with pytest.raises(RequestTimedOut):
            eng.submit(p, 4, timeout_s=0.05, queue_timeout_s=30.0)
        # the engine keeps serving correctly afterwards
        np.testing.assert_array_equal(h_long.result(timeout=60),
                                      _direct(lm, p, 24))
    assert eng.stats()["timed_out"] >= 1


# ------------------------------------------------ engine: cache reuse
def test_prefix_hit_parity_and_stats(lm):
    """Second request sharing an 8-token template head: token-identical
    to the cold oracle, with the hit visible end-to-end — handle,
    timeline, stats(), and /debug/requests."""
    r = np.random.RandomState(7)
    tpl = r.randint(0, 32, (8,))
    pa = np.concatenate([tpl, r.randint(0, 32, (3,))])
    pb = np.concatenate([tpl, r.randint(0, 32, (4,))])
    with ContinuousBatchingEngine(lm, max_slots=2,
                                  prefill_chunk=4) as eng:
        ha = eng.submit(pa, 5)
        np.testing.assert_array_equal(ha.result(timeout=60),
                                      _direct(lm, pa, 5))
        assert ha.prefix_tokens == 0          # cold cache: a miss
        hb = eng.submit(pb, 5)
        np.testing.assert_array_equal(hb.result(timeout=60),
                                      _direct(lm, pb, 5))
        assert hb.prefix_tokens == 8          # the whole template head
        assert hb.timeline()["prefix_tokens"] == 8
        s = eng.stats()["prefix_cache"]
        assert s["enabled"] and s["hits"] == 1 and s["misses"] == 1
        assert s["hit_rate"] == 0.5
        assert s["reused_tokens"] == 8 and s["reused_fraction"] > 0
        assert s["entries"] >= 1 and s["bytes"] > 0
        assert s["bytes"] <= s["capacity_bytes"]
        dbg = eng.debug_requests()
        assert dbg["prefix_cache"]["hits"] == 1


@pytest.mark.parametrize("asked_by", ["argument", "model", "nobody"])
def test_a_prompt_is_donated_when_its_prefill_ends(lm, asked_by):
    """With ``donate_at_prefill_end`` (the engine's argument, or the
    model's say where the argument is left None) a request that shares a
    template head with one still DECODING is a hit: the first donates its
    prompt's pages when its prefill ends, and the entry it donates at its
    end takes that one's place: one entry a request, no eviction. Asked
    by nobody, a request donates when it ends and the second one prefills
    its whole prompt."""
    import time

    r = np.random.RandomState(12)
    tpl = r.randint(0, 32, (8,))
    pa = np.concatenate([tpl, r.randint(0, 32, (3,))])
    pb = np.concatenate([tpl, r.randint(0, 32, (2,))])
    early = asked_by != "nobody"
    kw = {"donate_at_prefill_end": True} if asked_by == "argument" else {}
    if asked_by == "model":
        lm.donate_at_prefill_end = True
    try:
        with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                      **kw) as eng:
            ha = eng.submit(pa, 30)
            while ha.first_token_at is None:
                time.sleep(0.001)
            during = eng.stats()["prefix_cache"]
            hb = eng.submit(pb, 4)
            np.testing.assert_array_equal(hb.result(timeout=60),
                                          _direct(lm, pb, 4))
            np.testing.assert_array_equal(ha.result(timeout=60),
                                          _direct(lm, pa, 30))
            s = eng.stats()["prefix_cache"]
    finally:
        if asked_by == "model":
            del lm.donate_at_prefill_end
    assert during["entries"] == during["donations"] == int(early)
    assert hb.prefix_tokens == (8 if early else 0)
    assert s["hits"] == int(early) and s["donations"] == (4 if early else 2)
    assert s["entries"] == 2 and s["evictions"] == 0


def test_greedy_parity_shared_prefix_load_vs_cold_engine(lm):
    """The tentpole acceptance: a shared-prefix workload through the
    cached engine (multi-row staging) is token-identical, request for
    request, to the cache-DISABLED engine and to the lone-generate
    oracle."""
    r = np.random.RandomState(8)
    tpls = [r.randint(0, 32, (8,)) for _ in range(2)]
    reqs = []
    for i in range(8):
        tpl = tpls[i % 2]
        reqs.append((np.concatenate([tpl, r.randint(0, 32,
                                                    (1 + i % 4,))]),
                     3 + i % 5))

    def run(**kw):
        rows = []
        with ContinuousBatchingEngine(lm, max_slots=3, prefill_chunk=4,
                                      prefill_rows=2, **kw) as eng:
            handles = [eng.submit(p, n) for p, n in reqs]
            rows = [h.result(timeout=120) for h in handles]
        return rows, eng

    warm_rows, warm_eng = run()
    cold_rows, _ = run(prefix_cache_bytes=0)
    for (p, n), wr, cr in zip(reqs, warm_rows, cold_rows):
        want = _direct(lm, p, n)
        np.testing.assert_array_equal(wr, want)
        np.testing.assert_array_equal(cr, want)
    s = warm_eng.stats()["prefix_cache"]
    assert s["hits"] >= 1 and s["reused_tokens"] >= 8


def test_multiturn_reuse_crosses_decode_kv(lm):
    """Turn 2's prompt embeds turn 1's full prompt+reply: the cached
    head extends past the original prompt into DECODE-produced KV, and
    the greedy output still matches the cold oracle exactly."""
    r = np.random.RandomState(9)
    p1 = r.randint(0, 32, (6,))
    with ContinuousBatchingEngine(lm, max_slots=2,
                                  prefill_chunk=4) as eng:
        row1 = eng.submit(p1, 7).result(timeout=60)   # 13 tokens
        p2 = np.concatenate([row1, r.randint(0, 32, (2,))])
        h2 = eng.submit(p2, 4)
        np.testing.assert_array_equal(h2.result(timeout=60),
                                      _direct(lm, p2, 4))
        # donated key = prompt + generated[:-1] = 12 tokens; chunk-
        # aligned reuse = 12 — strictly more than p1's 6 prompt tokens,
        # so the reused head provably crosses into decode-written KV
        assert h2.prefix_tokens == 12


def test_concurrent_submits_sharing_one_prefix(lm):
    r = np.random.RandomState(10)
    tpl = r.randint(0, 32, (8,))
    warm = np.concatenate([tpl, r.randint(0, 32, (2,))])
    reqs = [(np.concatenate([tpl, r.randint(0, 32, (2 + i % 3,))]),
             3 + i % 4) for i in range(6)]
    rows = [None] * len(reqs)
    errs = []
    with ContinuousBatchingEngine(lm, max_slots=3, prefill_chunk=4,
                                  prefill_rows=2) as eng:
        eng.submit(warm, 2).result(timeout=60)  # donate the template

        def worker(i, p, n):
            try:
                rows[i] = eng.submit(p, n).result(timeout=120)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i, p, n))
                   for i, (p, n) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs, errs
    for (p, n), row in zip(reqs, rows):
        np.testing.assert_array_equal(row, _direct(lm, p, n))
    s = eng.stats()["prefix_cache"]
    assert s["hits"] == len(reqs), \
        "every post-warm submit shares the donated template head"


def test_engine_eviction_under_byte_pressure(lm):
    """Capacity is the page pool's: with 5 allocatable pages the second
    template's admission reclaims the first's retained pages (LRU,
    refs==0) while the entry cap is nowhere near — visible in stats,
    and serving stays correct throughout."""
    r = np.random.RandomState(12)
    t1, t2 = r.randint(0, 32, (8,)), r.randint(0, 32, (8,))
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  max_len=16, max_pages=6) as eng:
        for tpl in (t1, t2):
            p = np.concatenate([tpl, r.randint(0, 32, (2,))])
            np.testing.assert_array_equal(eng.submit(p, 3).result(60),
                                          _direct(lm, p, 3))
        s = eng.stats()["prefix_cache"]
        pool = eng.stats()["paging"]["pool"]
        assert s["entries"] == 1 < s["rows"]
        assert s["evictions"] >= 1
        assert s["bytes"] == s["pages"] * pool["page_bytes"]
        assert s["bytes"] <= s["capacity_bytes"] == pool["capacity_bytes"]


def test_prefix_cache_disabled(lm):
    r = np.random.RandomState(13)
    p = r.randint(0, 32, (8,))
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefix_cache_bytes=0) as eng:
        np.testing.assert_array_equal(eng.submit(p, 4).result(60),
                                      _direct(lm, p, 4))
        h = eng.submit(p, 4)        # identical prompt: still no reuse
        np.testing.assert_array_equal(h.result(60), _direct(lm, p, 4))
        assert h.prefix_tokens == 0
        assert eng.stats()["prefix_cache"] == {"enabled": False}
    assert eng._prefix is None


# --------------------------------------- engine: batched multi-row path
def test_multirow_prefill_parity_and_flat_jit(lm):
    """prefill_rows=3: queued admissions prefill TOGETHER through the
    ragged staging dispatch; every reply stays token-identical and the
    compiled-program count is flat from the first request's warmup
    onward (hit, miss, donation, and batched rounds all reuse the
    construction-warmed executables)."""
    r = np.random.RandomState(14)
    tpl = r.randint(0, 32, (8,))
    reqs = [(r.randint(0, 32, (3 + i,)), 3 + i % 4) for i in range(3)]
    reqs += [(np.concatenate([tpl, r.randint(0, 32, (2 + i,))]), 4)
             for i in range(3)]
    with ContinuousBatchingEngine(lm, max_slots=3, prefill_chunk=4,
                                  prefill_rows=3) as eng:
        warm_p = r.randint(0, 32, (6,))
        np.testing.assert_array_equal(eng.submit(warm_p, 3).result(60),
                                      _direct(lm, warm_p, 3))
        # donate the template so the trio below hits the cache (they
        # are admitted in ONE multi-row wave — a donation landing
        # after their admission would be too late)
        warm_t = np.concatenate([tpl, r.randint(0, 32, (2,))])
        np.testing.assert_array_equal(eng.submit(warm_t, 2).result(60),
                                      _direct(lm, warm_t, 2))
        compiles_after_warmup = eng.stats()["jit_compiles"]
        assert compiles_after_warmup > 0

        # submit everything at once: the queue drains through batched
        # multi-row admission (and, for the template trio, the hit
        # path) with no further compiles
        handles = [eng.submit(p, n) for p, n in reqs]
        for (p, n), h in zip(reqs, handles):
            np.testing.assert_array_equal(h.result(timeout=120),
                                          _direct(lm, p, n))
        assert eng.stats()["prefix_cache"]["hits"] >= 1
        assert eng.stats()["jit_compiles"] == compiles_after_warmup, \
            "hit/donation/batched-prefill paths must not compile " \
            "anything new after warmup"


def test_norope_model_ragged_path():
    """The learned-positional (non-rope) model exercises the ragged
    pos_embed gather: parity for a batched, prefix-hitting pair."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(22)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=False)
    m.evaluate()
    r = np.random.RandomState(15)
    tpl = r.randint(0, 32, (8,))
    pa = np.concatenate([tpl, r.randint(0, 32, (2,))])
    pb = np.concatenate([tpl, r.randint(0, 32, (3,))])
    with ContinuousBatchingEngine(m, max_slots=2, prefill_chunk=4,
                                  prefill_rows=2) as eng:
        ha, hb = eng.submit(pa, 4), eng.submit(pb, 4)
        np.testing.assert_array_equal(ha.result(60), _direct(m, pa, 4))
        np.testing.assert_array_equal(hb.result(60), _direct(m, pb, 4))


# ---------------------------------------------------------- perf gate
def _gate(history_path, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_gate.py"),
         "--history", history_path, *extra],
        capture_output=True, text=True)


def _serving_row(p99_ms, metric="serving_shared_prefix_tokens_per_sec",
                 requests=24, ts="2026-08-04T00:00:00+00:00"):
    return {"metric": metric, "value": 100.0, "unit": "tokens/sec",
            "ts": ts,
            "detail": {"device": "cpu",
                       "cached": {"ttft": {"p50": p99_ms / 2e3,
                                           "p99": p99_ms / 1e3}},
                       "workload": {"kind": "shared_prefix",
                                    "requests": requests,
                                    "rate_hz": 30.0}}}


def test_perf_gate(tmp_path):
    hist = tmp_path / "hist.jsonl"

    # no file / no serving rows / single row: the gate passes
    assert _gate(str(hist)).returncode == 0
    hist.write_text(json.dumps({"metric": "training", "value": 1}) + "\n")
    assert _gate(str(hist)).returncode == 0
    hist.write_text(json.dumps(_serving_row(10.0)) + "\n")
    assert _gate(str(hist)).returncode == 0

    # within budget (+10% < 20%): pass
    rows = [_serving_row(10.0), _serving_row(11.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0, res.stdout + res.stderr

    # >20% p99 regression: FAIL
    rows = [_serving_row(10.0), _serving_row(12.5)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1 and "FAIL" in res.stdout

    # regression vs a NON-comparable row (different workload): pass —
    # the gate compares only rows with matching signatures
    rows = [_serving_row(10.0, requests=8), _serving_row(30.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert _gate(str(hist)).returncode == 0

    # the newest row gates against the newest COMPARABLE one, skipping
    # interleaved rows of other workloads; custom threshold respected
    rows = [_serving_row(10.0), _serving_row(5.0, requests=8),
            _serving_row(10.5)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert _gate(str(hist)).returncode == 0
    assert _gate(str(hist), "--threshold", "0.01").returncode == 1


def test_perf_gate_inter_token(tmp_path):
    """The gate also holds the p99 inter-token line: a steady-state
    decode regression fails even when TTFT is flat, and rows predating
    the inter_token field skip that comparison instead of crashing."""
    def row(ttft_ms, itl_ms=None, ts="2026-08-04T00:00:00+00:00"):
        r = _serving_row(ttft_ms, ts=ts)
        if itl_ms is not None:
            r["detail"]["cached"]["inter_token"] = {
                "p50": itl_ms / 2e3, "p99": itl_ms / 1e3}
        return r

    hist = tmp_path / "hist.jsonl"
    # TTFT flat, inter-token +50%: FAIL, and the verdict names it
    rows = [row(10.0, 2.0), row(10.0, 3.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1
    assert "inter-token" in res.stdout and "FAIL" in res.stdout

    # both within budget: pass, both comparisons reported
    rows = [row(10.0, 2.0), row(10.5, 2.1)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0
    assert res.stdout.count("ok:") == 2

    # an old row without the field: inter-token comparison skipped
    rows = [row(10.0), row(10.5, 2.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0 and "skip" in res.stdout
