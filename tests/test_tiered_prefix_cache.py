"""Tiered prefix-KV cache: device pages + host-RAM spill
(``bigdl_tpu/serving/paging.py`` ``PagedPrefixIndex`` host tier, wired
through the engine's admission and donation paths).

The acceptance contract under test: reclaiming pages under allocation
pressure DEMOTES unpinned entries into host buffers (one bulk d2h copy
per page, separate host byte budget with its own LRU) instead of
dropping them; a trie hit on a host-tier entry promotes its pages back
before admission; and none of
that bends the engine's invariants — warm output stays token-identical
to the cache-disabled engine (and the lone-generate oracle) across
demote→promote→reuse cycles, including under tensor parallelism and
with speculative decoding on; the jit-compile gauge stays flat through
promotions; usage-ledger device-seconds still conserve; both tiers
attribute in the memory-pool registry; and the generation guard turns
every tier-transition race (lookup vs demote, promote vs host-evict)
into a clean miss, never a share of reused pages. Plus the
``scripts/perf_gate.py`` tiered-row gates (headline hit rate
higher-is-better, tiered p50 TTFT lower-is-better)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.parallel import Engine, fetch_to_host, put_from_host
from bigdl_tpu.serving import (
    ContinuousBatchingEngine, PagedPrefixIndex, PagePool,
)

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


@pytest.fixture(scope="module")
def lm_tp():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(23)
    m = TransformerLM(32, embed_dim=32, num_heads=8, num_kv_heads=4,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


@pytest.fixture(scope="module")
def mesh():
    return Engine.create_mesh([("model", 4)], devices=jax.devices()[:4])


def _direct(lm, prompt, n):
    return np.asarray(lm.generate(jnp.asarray(prompt)[None], n))[0]


PS = 4          # page_size of the index units: an 8-token key is 2 pages


def _index(max_pages, **kw):
    """A prefix index over a small pool of a one-layer (k, v) tree."""
    leaf = np.zeros((max_pages, PS, 8), np.float32)
    pool = PagePool(((leaf, leaf.copy()),), PS)
    return pool, PagedPrefixIndex(pool, min_tokens=4, **kw)


def _spill(pages):
    """The engine's demotion callback: one host buffer per page."""
    return ["host-kv"] * len(pages)


def _serve(pool, pc, tokens, spill=_spill):
    """One request's life on the page surface: reserve its pages
    (reclaiming — demoting — retained prefixes under pressure), finish,
    offer them to the index, free its own references."""
    n = -(-len(tokens) // PS)
    pages = pool.alloc(n)
    if pages is None:
        pc.reclaim(n, spill)
        pages = pool.alloc(n)
    assert pages is not None
    accepted = pc.donate_pages(tokens, pages) is not None
    pool.free(pages)
    return accepted


# ---------------------------------------------------- host-tier units
def test_host_lru_and_byte_budget():
    """Device reclaim demotes into the host tier; the host tier has
    its OWN byte budget and LRU; only attached buffers count toward
    host bytes."""
    # 4 allocatable pages = two retained keys; host budget the same
    pool, pc = _index(5, max_entries=8, host_pages=4)
    ts = [np.asarray([k] * 8, np.int32) for k in range(1, 6)]
    assert _serve(pool, pc, ts[0]) and _serve(pool, pc, ts[1])
    assert pc.host_capacity_bytes == 4 * pool.page_bytes
    assert pc.host_bytes_in_use == 0

    # third request: device LRU (ts[0]) demotes instead of dropping;
    # while its copy is in flight it holds no host bytes yet
    seen = []

    def spill(pages):
        e, m = pc.match(ts[0])[:2]
        seen.append((m, e.tier, pc.host_bytes_in_use))
        return _spill(pages)

    assert _serve(pool, pc, ts[2], spill)
    assert seen == [(8, "host", 0)]
    e0, m = pc.match(ts[0])[:2]
    assert m == 8 and e0.tier == "host" and e0.pages == ()
    assert pc.host_bytes_in_use == 2 * pool.page_bytes
    assert pc.stats()["demotions"] == 1

    # fourth: ts[1] demotes too — host tier now at its budget
    assert _serve(pool, pc, ts[3])
    assert pc.match(ts[1]).entry.tier == "host"
    assert pc.host_bytes_in_use == 4 * pool.page_bytes \
        == pc.host_capacity_bytes
    assert pc.stats()["host_entries"] == 2

    # fifth: the HOST tier is full, so its LRU (ts[0], the oldest
    # stamp) truly leaves the cache to make room for the new demotion
    assert _serve(pool, pc, ts[4])
    assert pc.stats()["host_evictions"] == 1
    assert pc.match(ts[0]).entry is None
    e, _ = pc.match(ts[1])[:2]
    assert e is not None and e.tier == "host"

    # host hits split from device hits in the counters
    pc.record_hit(e, 8, host=True)
    s = pc.stats()
    assert s["host_hits"] == 1 and s["hits"] == 1
    assert s["device_hits"] == 0
    # and the snapshot labels each entry's tier
    tiers = {sn["tier"] for sn in pc.snapshot()}
    assert tiers == {"device", "host"}


def test_pin_spans_demote_and_blocks_host_eviction():
    """refs pin an entry in WHATEVER tier it occupies: a pinned device
    entry is never demoted, a pinned host entry is never host-evicted
    — when every host page is pinned the demotion degrades to a plain
    drop, never an over-budget spill."""
    pool, pc = _index(5, max_entries=8, host_pages=2)
    t1, t2, t3, t4 = (np.asarray([k] * 8, np.int32) for k in range(1, 5))
    assert _serve(pool, pc, t1) and _serve(pool, pc, t2)
    e1, _ = pc.match(t1)[:2]
    pc.acquire(e1)

    # pinned device entry survives: the victim is t2
    assert _serve(pool, pc, t3)
    assert e1.tier == "device"
    e2, _ = pc.match(t2)[:2]
    assert e2.tier == "host"
    pc.acquire(e2)                     # pin SPANS the demoted tier

    # host tier full of pinned entries: the next device victim (t3)
    # cannot spill — it drops, and e2's buffers survive untouched
    assert _serve(pool, pc, t4)
    assert pc.stats()["demotions"] == 1
    assert pc.stats()["host_evictions"] == 0
    assert pc.match(t3).entry is None
    e2b, m = pc.match(t2)[:2]
    assert e2b is e2 and m == 8 and e2.host_buf == ["host-kv"] * 2

    pc.release(e1), pc.release(e2)


def test_generation_guard_covers_host_tier():
    """The stale-probe regression the satellite pins: EVERY tier
    transition (demote, host-evict, promote, failed demotion) bumps
    ``generation``, so a probe captured before the transition
    re-validates into a clean miss instead of sharing reused pages."""
    # 2 allocatable pages = one retained key; host budget the same
    pool, pc = _index(3, max_entries=8, host_pages=2)
    t1, t2 = np.asarray([1] * 8, np.int32), np.asarray([2] * 8, np.int32)
    assert _serve(pool, pc, t1)
    e1, m = pc.match(t1)[:2]
    probe_gen = pc.generation

    # lookup racing a demotion: the admission that demotes e1 bumps
    # generation, so the engine's (entry, match, gen) probe goes stale
    assert _serve(pool, pc, t2)
    assert pc.generation != probe_gen
    assert e1.tier == "host" and e1.host_buf is not None

    # promote racing a host eviction: capture e1 as a host-tier probe,
    # then evict its buffer — generation moves again, host_buf clears,
    # and promote_pages() of the evicted entry refuses outright
    e1b, _ = pc.match(t1)[:2]
    assert e1b is e1
    probe_gen = pc.generation
    t3 = np.asarray([3] * 8, np.int32)
    assert _serve(pool, pc, t3)               # t2 demotes, e1 host-evicts
    assert pc.generation != probe_gen
    assert e1.host_buf is None
    with pytest.raises(RuntimeError, match="non-host"):
        pc.promote_pages(e1, (1, 2))
    assert pc.match(t1).entry is None

    # a spill completing after its entry left the host tier (the copy
    # runs outside the index lock) is a no-op — the stale buffer is
    # dropped, not re-attached
    e3, _ = pc.match(t3)[:2]

    def racing_spill(pages):
        pc.drop_all()
        return _spill(pages)

    demoted = pc.stats()["demotions"]
    assert _serve(pool, pc, np.asarray([4] * 8, np.int32), racing_spill)
    assert e3.host_buf is None and pc.match(t3).entry is None
    assert pc.stats()["demotions"] == demoted

    # a demotion whose d2h copy FAILED (buf None) drops the entry and
    # bumps generation — a later promotion can never read garbage
    e4, _ = pc.match(np.asarray([4] * 8, np.int32))[:2]
    assert e4 is not None and e4.tier == "device"
    gen = pc.generation
    assert _serve(pool, pc, np.asarray([5] * 8, np.int32),
                  lambda pages: None)
    assert pc.generation != gen
    assert pc.match(np.asarray([4] * 8, np.int32)).entry is None
    assert pc.stats()["host_entries"] == 0

    # a fallen-through promotion returns its claimed pages to the free
    # list: the pool hands the same pages out again
    pc.drop_all()
    pages = pool.alloc(2)
    assert pages is not None
    pool.free(pages)
    assert sorted(pool.alloc(2)) == sorted(pages)


def test_fetch_put_host_round_trip_sharded(mesh):
    """The tp transfer helpers: ``fetch_to_host`` reassembles a
    sharded tree into full host ndarrays (layout-free), and
    ``put_from_host`` lands them back under the requested sharding —
    each device moving only its own shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P(None, "model", None, None))
    x = jnp.arange(1 * 4 * 6 * 2, dtype=jnp.float32).reshape(1, 4, 6, 2)
    tree = {"k": jax.device_put(x, sh), "v": jax.device_put(2 * x, sh)}
    host = fetch_to_host(tree)
    assert isinstance(host["k"], np.ndarray)
    assert host["k"].shape == (1, 4, 6, 2)
    np.testing.assert_array_equal(host["v"], 2 * np.asarray(x))
    back = put_from_host(host, sh)
    assert back["k"].sharding == sh
    np.testing.assert_array_equal(np.asarray(back["k"]), np.asarray(x))


# ------------------------------------------------- engine: tiered flow
#: 7 allocatable pages of 4 tokens: a request of these tests holds 4-5
#: and its retained prefix 3-4, so each admission reclaims (demotes)
#: the previous template's pages and each revisit promotes them back
TIGHT = dict(max_len=24, max_pages=8)


def _cycle_requests(rstate, templates, rounds, tail=2, decode=4):
    """Round-robin template traffic: over a pool with room for one
    request and one retained template (``TIGHT``) every revisit forces
    a demote→promote cycle."""
    reqs = []
    for i in range(rounds * len(templates)):
        tpl = templates[i % len(templates)]
        reqs.append((np.concatenate(
            [tpl, rstate.randint(0, 32, (tail + i % 2,))]),
            decode + i % 3))
    return reqs


def test_demote_promote_reuse_parity_and_flat_jit(lm):
    """The tentpole end-to-end: a one-template device pool under
    3-template round-robin traffic demotes on every admission and
    promotes on every revisit — output stays token-identical to the cache-DISABLED
    engine and the lone oracle, reuse still lands (prefix_tokens), the
    per-tier counters move, and the compile gauge is flat from the
    first finished request on ('copy:demote'/'copy:promote' are
    construction-warmed)."""
    r = np.random.RandomState(31)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    reqs = _cycle_requests(r, tpls, rounds=3)

    def run(**kw):
        rows, handles = [], []
        with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                      **kw) as eng:
            first = eng.submit(*reqs[0][:2])
            rows.append(first.result(timeout=120))
            jit0 = eng.stats()["jit_compiles"]
            for p, n in reqs[1:]:
                h = eng.submit(p, n)
                handles.append(h)
                rows.append(h.result(timeout=120))
            st = eng.stats()
        return rows, handles, st, jit0

    rows_t, handles, st, jit0 = run(prefix_host_rows=8, **TIGHT)
    rows_d, _, _, _ = run(prefix_cache_bytes=0)
    for (p, n), rt, rd in zip(reqs, rows_t, rows_d):
        want = _direct(lm, p, n)
        np.testing.assert_array_equal(rt, want)
        np.testing.assert_array_equal(rd, want)

    pc = st["prefix_cache"]
    assert pc["demotions"] >= 2 and pc["promotions"] >= 2, pc
    assert pc["host_hits"] >= 2, pc
    assert pc["hits"] == pc["host_hits"] + pc["device_hits"]
    # revisits actually reused the 8-token template head
    assert any(h.prefix_tokens == 8 for h in handles)
    assert st["jit_compiles"] == jit0, \
        "demote/promote traffic must not compile new programs"


def test_host_tier_off_by_default(lm):
    """Without ``prefix_host_bytes``/``prefix_host_rows`` the engine
    behaves exactly as seeded: evictions DROP (no demotions, no host
    occupancy), and the host-tier pool is not registered."""
    from bigdl_tpu.observability import memory as obs_memory

    r = np.random.RandomState(33)
    t1, t2 = r.randint(0, 32, (8,)), r.randint(0, 32, (8,))
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefix_cache_rows=1,
                                  service_name="tier_off") as eng:
        for t in (t1, t2, t1):
            eng.submit(np.concatenate([t, r.randint(0, 32, (2,))]),
                       3).result(timeout=60)
        pc = eng.stats()["prefix_cache"]
        assert pc["host_rows"] == 0 and pc["demotions"] == 0
        assert pc["evictions"] >= 1 and pc["host_entries"] == 0
        assert "serving/tier_off/prefix_host_kv" not in \
            obs_memory.pool_sizes()


def test_memory_pool_attributes_both_tiers(lm):
    """The memory-pool registry answers "who owns the spill" exactly
    like "who owns the HBM": the host-tier pool appears beside the
    device pools and tracks the demoted rows' pinned bytes."""
    from bigdl_tpu.observability import memory as obs_memory

    r = np.random.RandomState(34)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefix_host_rows=4,
                                  service_name="tier_mem",
                                  **TIGHT) as eng:
        for tpl in tpls:
            eng.submit(np.concatenate([tpl, r.randint(0, 32, (2,))]),
                       3).result(timeout=60)
        sizes = obs_memory.pool_sizes()
        pc = eng.stats()["prefix_cache"]
        assert sizes["serving/tier_mem/prefix_kv_in_use"] == pc["bytes"]
        assert sizes["serving/tier_mem/prefix_host_kv"] == \
            pc["host_bytes"]
        assert pc["host_bytes"] > 0          # demotions actually landed
        assert pc["host_bytes"] <= pc["host_capacity_bytes"]


def test_ledger_conservation_with_promotions_in_flight(lm):
    """Per-tenant device-second sums still conserve the measured busy
    total when admissions run through host-tier promotions."""
    r = np.random.RandomState(35)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    reqs = _cycle_requests(r, tpls, rounds=2)
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefix_host_rows=8,
                                  service_name="tier_usage",
                                  **TIGHT) as eng:
        for i, (p, n) in enumerate(reqs):
            eng.submit(p, n, tenant=f"t{i % 2}").result(timeout=120)
        usage = eng.stats()["usage"]
        busy = eng._usage.device_time()
        pc = eng.stats()["prefix_cache"]
    assert pc["promotions"] >= 1, pc
    total_busy = busy["total"]
    assert total_busy > 0
    tenant_sum = sum(a["device_s"] for a in usage["tenants"].values())
    assert tenant_sum == pytest.approx(total_busy, rel=1e-6, abs=1e-9)


def test_tp_demote_promote_parity_on_mesh(lm_tp, mesh):
    """Under a 4-way model mesh the demote/promote path moves
    PER-SHARD buffers (heads-sharded pool → device_get ships each
    device's shard only), and the cycle still yields token-identical
    output with the gauge flat."""
    r = np.random.RandomState(36)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    reqs = _cycle_requests(r, tpls, rounds=2)
    with ContinuousBatchingEngine(lm_tp, max_slots=2, prefill_chunk=4,
                                  prefix_host_rows=8, mesh=mesh,
                                  service_name="tp_tiered",
                                  **TIGHT) as eng:
        first = eng.submit(*reqs[0][:2])
        rows = [first.result(timeout=180)]
        jit0 = eng.stats()["jit_compiles"]
        rows += [eng.submit(p, n).result(timeout=180)
                 for p, n in reqs[1:]]
        st = eng.stats()
    for (p, n), row in zip(reqs, rows):
        np.testing.assert_array_equal(row, _direct(lm_tp, p, n))
    pc = st["prefix_cache"]
    assert pc["demotions"] >= 1 and pc["promotions"] >= 1, pc
    assert st["jit_compiles"] == jit0, (jit0, st["jit_compiles"])


def test_speculative_decode_with_host_tier_parity(lm):
    """Speculative decoding composes with the host tier: the int8
    draft proposes through demote→promote→reuse cycles and greedy
    output still matches the oracle."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    r = np.random.RandomState(37)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    reqs = _cycle_requests(r, tpls, rounds=2, decode=6)
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefix_host_rows=8, draft=draft,
                                  spec_gamma=3,
                                  service_name="spec_tiered",
                                  **TIGHT) as eng:
        rows = [eng.submit(p, n).result(timeout=180) for p, n in reqs]
        st = eng.stats()
    for (p, n), row in zip(reqs, rows):
        np.testing.assert_array_equal(row, _direct(lm, p, n))
    assert st["prefix_cache"]["promotions"] >= 1
    assert st["speculation"]["proposed_tokens"] > 0


# ---------------------------------------------------------- perf gate
def _gate(history_path, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_gate.py"),
         "--history", history_path, *extra],
        capture_output=True, text=True)


def _tiered_row(hit_rate, ttft_p50_ms=3.0,
                ts="2026-08-04T00:00:00+00:00", headline=True):
    row = {"metric": "serving_tiered_prefix_hit_rate",
           "value": hit_rate, "unit": "fraction", "ts": ts,
           "detail": {"device": "cpu",
                      "tiered": {"ttft": {"p50": ttft_p50_ms / 1e3,
                                          "p99": 2 * ttft_p50_ms / 1e3}},
                      "workload": {"kind": "working_set_sweep",
                                   "device_rows": 2,
                                   "max_working_set": 8,
                                   "rate_hz": 40.0}}}
    if headline:
        row["detail"]["headline"] = {"tiered_hit_rate": hit_rate}
    return row


def test_perf_gate_tiered_hit_rate_and_ttft(tmp_path):
    hist = tmp_path / "hist.jsonl"

    # flat hit rate + flat TTFT: pass, both tiered measures reported
    rows = [_tiered_row(0.6), _tiered_row(0.6)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "tiered hit rate" in res.stdout
    assert "tiered p50 TTFT" in res.stdout

    # hit rate collapsing 0.6 -> 0.4 (-33%): FAIL on the inverted
    # (higher-is-better) direction
    rows = [_tiered_row(0.6), _tiered_row(0.4)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1
    assert "FAIL" in res.stdout and "tiered hit rate" in res.stdout

    # p50 TTFT regressing past budget fails even with the rate flat
    rows = [_tiered_row(0.6, ttft_p50_ms=3.0),
            _tiered_row(0.6, ttft_p50_ms=4.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1 and "tiered p50 TTFT" in res.stdout

    # a predecessor predating the headline block: the hit-rate
    # comparison SKIPS (established pattern) instead of crashing
    rows = [_tiered_row(0.6, headline=False), _tiered_row(0.6)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0
    assert "skip" in res.stdout and "tiered hit rate" in res.stdout
