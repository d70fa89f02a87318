"""Quantized serving: int8 KV pools and weights on the decode hot path
(``ContinuousBatchingEngine(kv_dtype="int8", weights_dtype="int8")``).

The acceptance contract under test: every persistent pool (slot KV,
prefill staging, prefix pool + host tier, draft pools) optionally
stores int8 rows with per-row/per-head f32 scale sidecars; quantize
happens at the write site, dequantize inside the fused attention
chunk, and the stored row IS what every pass attends — so within the
int8 numerics regime the engine keeps all of its invariants: prefix
hits, tiered demote→promote cycles, and speculative decoding are
token-identical to the plain int8 engine, the jit-compile gauge stays
flat, and a demoted+promoted row is bit-identical to one that never
left the device. Against the FLOAT engine the contract is a bounded
drift, not identity: the teacher-forced logit-divergence report and
the spec acceptance delta quantify it, and ``scripts/perf_gate.py``
gates both as absolute ceilings. Capacity: physical row bytes (codes +
scales) halve, so equal byte budgets buy ~2x the prefix rows and the
memory-pool registry reports the honest quantized figures."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import dequantize_kv, quantize_kv
from bigdl_tpu.parallel import Engine, fetch_to_host, put_from_host
from bigdl_tpu.serving import ContinuousBatchingEngine

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


# ------------------------------------------------------ numerics units
def test_quantize_roundtrip_deterministic_and_bounded():
    """Symmetric per-(row, head, position) int8: the roundtrip error is
    bounded by half a step of each head-slice's own scale, re-quantizing
    the dequantized values is a fixed point (prefix reuse re-reads the
    SAME bytes), and an all-zero row maps to scale 1/127, never a NaN."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 3, 5, 4).astype(np.float32)) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 3, 5, 1)
    back = dequantize_kv(q, s)
    step = np.asarray(s)
    assert float(np.max(np.abs(np.asarray(back) - np.asarray(x)))) <= \
        float(np.max(step)) * 0.5 + 1e-7
    q2, s2 = quantize_kv(back)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))

    zq, zs = quantize_kv(jnp.zeros((1, 1, 2, 4)))
    assert float(jnp.max(jnp.abs(zq))) == 0.0
    np.testing.assert_allclose(np.asarray(zs), 1.0 / 127.0)


def test_init_cache_int8_shape_and_bytes(lm):
    """``init_cache(kv_dtype="int8")`` returns per-layer 4-tuples
    (codes + scale sidecars) whose physical bytes are exactly
    (D + 4) / (4 D) of the fp cache — 0.5 for this head_dim=4 model —
    and an unknown kv_dtype raises."""
    fp = lm.init_cache(2, 16)
    q8 = lm.init_cache(2, 16, kv_dtype="int8")
    assert len(fp[0]) == 2 and len(q8[0]) == 4
    k_q, v_q, k_s, v_s = q8[0]
    assert k_q.dtype == jnp.int8 and v_q.dtype == jnp.int8
    assert k_s.dtype == jnp.float32
    assert k_s.shape == k_q.shape[:-1] + (1,)
    bytes_fp = sum(x.nbytes for x in jax.tree.leaves(fp))
    bytes_q8 = sum(x.nbytes for x in jax.tree.leaves(q8))
    head_dim = lm.block0.attn.head_dim
    assert bytes_q8 / bytes_fp == (head_dim + 4) / (4 * head_dim)
    with pytest.raises(ValueError, match="kv_dtype"):
        lm.init_cache(2, 16, kv_dtype="int4")


def test_engine_dtype_validation(lm):
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousBatchingEngine(lm, max_slots=2, kv_dtype="fp8",
                                 service_name="q_bad")


# ------------------------------------------- quality vs the float path
def test_logit_divergence_and_greedy_match(lm):
    """The quality harness: teacher-forced int8 logits track the float
    logits within a scale-free ceiling, the free-running greedy prefix
    agrees on short horizons, and the report is deterministic (same
    floats → same bytes → same figures)."""
    from bigdl_tpu.serving.benchmark import quantized_quality_report

    rep = quantized_quality_report(lm, horizon=8, n_prompts=4, seed=3)
    assert rep["kv_dtype"] == "int8"
    assert rep["logit_div_rel"] < 0.2, rep
    assert rep["logit_div_max"] > 0.0          # int8 really ran
    assert rep["greedy_match_fraction"] >= 0.5, rep
    rep2 = quantized_quality_report(lm, horizon=8, n_prompts=4, seed=3)
    assert rep == rep2


# ------------------------------------ engine invariants, int8 regime
def _cycle_requests(rstate, templates, rounds, tail=2, decode=4):
    reqs = []
    for i in range(rounds * len(templates)):
        tpl = templates[i % len(templates)]
        reqs.append((np.concatenate(
            [tpl, rstate.randint(0, 32, (tail + i % 2,))]),
            decode + i % 3))
    return reqs


def test_int8_regime_parity_and_flat_jit(lm):
    """The tentpole invariant: WITHIN the int8 numerics regime the
    engine's machinery is token-invariant. One template workload runs
    through (a) the plain int8 engine, (b) int8 + prefix cache + host
    tier (hit/miss/donate/demote/promote all fire), and (c) int8 +
    speculative decoding under the int8 draft — all three produce
    identical greedy tokens, and the compile gauge is flat from the
    first finished request on in every variant."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    r = np.random.RandomState(41)
    tpls = [r.randint(0, 32, (8,)) for _ in range(3)]
    reqs = _cycle_requests(r, tpls, rounds=3)

    def run(**kw):
        rows = []
        with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                      kv_dtype="int8",
                                      weights_dtype="int8",
                                      **kw) as eng:
            first = eng.submit(*reqs[0][:2])
            rows.append(first.result(timeout=120))
            jit0 = eng.stats()["jit_compiles"]
            for p, n in reqs[1:]:
                rows.append(eng.submit(p, n).result(timeout=120))
            st = eng.stats()
        assert st["jit_compiles"] == jit0, (jit0, st["jit_compiles"])
        return rows, st

    rows_plain, st_plain = run(prefix_cache_bytes=0,
                               service_name="q_plain")
    # 7 allocatable pages: a request holds 4-5 and its retained
    # template 3-4, so every admission demotes and every revisit
    # promotes
    rows_tier, st_tier = run(max_len=24, max_pages=8,
                             prefix_host_rows=8, service_name="q_tier")
    rows_spec, st_spec = run(prefix_cache_bytes=0, draft=draft,
                             spec_gamma=3, service_name="q_spec")
    for a, b, c in zip(rows_plain, rows_tier, rows_spec):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    pc = st_tier["prefix_cache"]
    assert pc["demotions"] >= 2 and pc["promotions"] >= 2, pc
    assert st_spec["speculation"]["proposed_tokens"] > 0
    assert st_spec["speculation"]["accepted_tokens"] > 0
    qz = st_plain["quantization"]
    assert qz["kv_dtype"] == "int8" and qz["weights_dtype"] == "int8"


def test_demote_promote_bit_identical(lm):
    """The tiered-interplay regression: a quantized row's d2h spill
    holds the int8 codes + f32 scales (no dequant round-trip — host
    bytes stay halved), and fetch→put returns bit-identical leaves, so
    a demoted+promoted row equals one that never left the device."""
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  kv_dtype="int8", max_len=16,
                                  max_pages=6, prefix_host_rows=4,
                                  service_name="q_bits") as eng:
        r = np.random.RandomState(42)
        tpls = [r.randint(0, 32, (8,)) for _ in range(2)]
        for tpl in tpls:
            eng.submit(np.concatenate([tpl, r.randint(0, 32, (2,))]),
                       3).result(timeout=60)
        # the second admission (4 of 5 allocatable pages) demoted the
        # first template's 3 retained pages
        pc = eng._prefix
        assert pc.stats()["demotions"] >= 1
        entry = next(e for e in pc._host_entries if e.host_buf
                     is not None)
        leaves = jax.tree.leaves(entry.host_buf)
        dtypes = {leaf.dtype for leaf in leaves}
        assert np.dtype(np.int8) in dtypes          # codes spilled raw
        assert np.dtype(np.float32) in dtypes       # scales ride along
        host_bytes = sum(leaf.nbytes for leaf in leaves)
        assert host_bytes == 3 * eng._pages.page_bytes
        assert eng._row_bytes < eng._fp_row_bytes

        # the promotion transfer itself is bit-exact: host → device →
        # host round-trips every code and scale unchanged
        back = fetch_to_host(put_from_host(entry.host_buf,
                                           eng._kv_shard))
        for a, b in zip(jax.tree.leaves(entry.host_buf),
                        jax.tree.leaves(back)):
            np.testing.assert_array_equal(a, b)

        # and a revisit promotes + reuses the row end-to-end
        p = np.concatenate([tpls[0], r.randint(0, 32, (2,))])
        h = eng.submit(p, 3)
        row = h.result(timeout=60)
        assert eng._prefix.stats()["promotions"] >= 1
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  kv_dtype="int8", prefix_cache_rows=8,
                                  service_name="q_nodem") as ref:
        for tpl in tpls:
            ref.submit(np.concatenate([tpl, r.randint(0, 32, (2,))]),
                       3).result(timeout=60)
        want = ref.submit(p, 3).result(timeout=60)
    np.testing.assert_array_equal(row, want)


def test_tp_quantized_parity_on_mesh(lm_tp, mesh):
    """A mesh changes WHERE the math runs, never the tokens — also
    under int8: the heads-sharded quantized pools (codes AND scale
    sidecars both split on the head axis) yield output token-identical
    to the unsharded int8 engine, gauge flat."""
    r = np.random.RandomState(43)
    reqs = [(r.randint(0, 32, (t0,)), n)
            for t0, n in [(6, 6), (9, 4), (4, 7)]]

    def run(**kw):
        with ContinuousBatchingEngine(lm_tp, max_slots=2,
                                      prefill_chunk=4, kv_dtype="int8",
                                      **kw) as eng:
            first = eng.submit(*reqs[0][:2])
            rows = [first.result(timeout=180)]
            jit0 = eng.stats()["jit_compiles"]
            rows += [eng.submit(p, n).result(timeout=180)
                     for p, n in reqs[1:]]
            st = eng.stats()
        assert st["jit_compiles"] == jit0
        return rows

    rows_sh = run(mesh=mesh, service_name="q_tp")
    rows_un = run(service_name="q_untp")
    for a, b in zip(rows_sh, rows_un):
        np.testing.assert_array_equal(a, b)


def test_spec_acceptance_delta_bounded(lm):
    """Quantizing the cache must not change how often the target
    agrees with its draft: fp-KV vs int8-KV spec engines over the same
    repeated-text traffic stay within a small acceptance delta (the
    bench gates 0.05 on the recipe model; this tiny model gets a
    looser bound against small-sample noise)."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    r = np.random.RandomState(44)
    motifs = [np.tile(r.randint(0, 32, (4,)), 3) for _ in range(4)]
    reqs = [(m, 8) for m in motifs for _ in range(2)]

    def acceptance(**kw):
        with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                      draft=draft, spec_gamma=4,
                                      **kw) as eng:
            for p, n in reqs:
                eng.submit(p, n).result(timeout=120)
            sp = eng.stats()["speculation"]
        assert sp["proposed_tokens"] > 0
        return sp["accepted_tokens"] / sp["proposed_tokens"]

    a_fp = acceptance(service_name="q_acc_fp")
    a_q8 = acceptance(kv_dtype="int8", service_name="q_acc_int8")
    assert abs(a_fp - a_q8) < 0.25, (a_fp, a_q8)


# ----------------------------------------------- capacity and honesty
def test_capacity_doubles_at_equal_byte_budget(lm):
    """The capacity claim: at the SAME ``max_pages * page_bytes`` budget
    the int8 pool holds 2x the pages, and at the same
    ``prefix_cache_bytes`` the index keeps 2x the entries (head_dim=4:
    ratio exactly 0.5); the memory-pool registry + stats report the
    honest quantized bytes, scale sidecars included."""
    from bigdl_tpu.observability import memory as obs_memory

    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  service_name="q_cap_fp") as fp_eng:
        fp_bytes = fp_eng.stats()["quantization"]["kv_row_bytes"]
        fp_pool = fp_eng.stats()["paging"]["pool"]
        budget = 4 * fp_bytes
        fp_rows = None
        with ContinuousBatchingEngine(
                lm, max_slots=2, prefill_chunk=4,
                prefix_cache_bytes=budget,
                service_name="q_cap_fp2") as e2:
            fp_rows = e2.stats()["prefix_cache"]["rows"]
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  kv_dtype="int8",
                                  max_pages=2 * fp_pool["max_pages"],
                                  prefix_cache_bytes=budget,
                                  service_name="q_cap_q8") as q_eng:
        qz = q_eng.stats()["quantization"]
        q_pool = q_eng.stats()["paging"]["pool"]
        q_rows = q_eng.stats()["prefix_cache"]["rows"]
        sizes = obs_memory.pool_sizes()
        assert sizes["serving/q_cap_q8/kv_page_pool"] == \
            obs_memory.tree_device_bytes(q_eng._kv_pool)
        assert sizes["serving/q_cap_q8/kv_page_pool"] == \
            q_pool["capacity_bytes"] == fp_pool["capacity_bytes"]
    assert q_pool["page_bytes"] * 2 == fp_pool["page_bytes"]
    assert q_pool["max_pages"] == 2 * fp_pool["max_pages"]
    assert qz["row_bytes_ratio"] == 0.5
    assert qz["kv_row_bytes"] * 2 == qz["fp_row_bytes"] == fp_bytes
    assert fp_rows == 4 and q_rows == 8


def test_weights_only_quantization(lm):
    """``weights_dtype="int8"`` alone: the serving params are the int8
    clone's (halved weight bytes), the KV pools stay fp, and the
    engine still serves greedily deterministic tokens."""
    r = np.random.RandomState(45)
    p = r.randint(0, 32, (6,))
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  weights_dtype="int8",
                                  service_name="q_wonly") as eng:
        qz = eng.stats()["quantization"]
        assert qz == {**qz, "kv_dtype": "fp", "weights_dtype": "int8",
                      "row_bytes_ratio": 1.0}
        row1 = eng.submit(p, 5).result(timeout=60)
        row2 = eng.submit(p, 5).result(timeout=60)
    np.testing.assert_array_equal(row1, row2)


# ------------------------------------------------ bench + perf gate
def test_run_quantized_comparison_smoke(lm):
    """The harness behind ``bench.py --serving --quantized``: both
    parity flags hold (speculation never changes tokens within a
    numerics regime), the capacity block shows the halved row, and the
    row shape carries what perf_gate reads."""
    from bigdl_tpu.serving.benchmark import run_quantized_comparison

    res = run_quantized_comparison(lm, n_requests=6, rate_hz=50.0,
                                   max_slots=2, prefill_chunk=4,
                                   prefill_rows=2, gamma=3, seed=11)
    assert res["token_parity_spec_fp"] is True
    assert res["token_parity_spec_int8"] is True
    assert res["workload"]["kind"] == "quantized"
    assert res["capacity"]["row_bytes_ratio"] == 0.5
    assert res["capacity"]["capacity_multiplier"] == 2.0
    assert res["quality"]["logit_div_rel"] is not None
    assert res["quality"]["acceptance_delta"] is not None
    assert res["quantized"]["quantization"]["kv_dtype"] == "int8"
    assert res["fp_baseline"]["quantization"]["kv_dtype"] == "fp"
    assert res["membw_util"]["fp"] is not None

    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "scripts", "perf_gate.py"))
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)
    row = {"metric": "serving_quantized_tokens_per_sec",
           "detail": {"quantized": res["quantized"],
                      "quality": res["quality"]}}
    assert pg.ttft_p99(row) == res["quantized"]["ttft"]["p99"]
    assert pg.inter_token_p99(row) == \
        res["quantized"]["inter_token"]["p99"]
    assert pg.quantized_logit_div_rel(row) == \
        res["quality"]["logit_div_rel"]
    assert pg.quantized_acceptance_delta(row) == \
        res["quality"]["acceptance_delta"]


def _gate(history_path, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_gate.py"),
         "--history", history_path, *extra],
        capture_output=True, text=True)


def _quant_row(div_rel=0.01, delta=0.01, it_p99_ms=1.0, quality=True,
               ts="2026-08-05T00:00:00+00:00"):
    row = {"metric": "serving_quantized_tokens_per_sec",
           "value": 400.0, "unit": "tokens/sec", "ts": ts,
           "detail": {"device": "cpu",
                      "quantized": {
                          "ttft": {"p50": 0.003, "p99": 0.004},
                          "inter_token": {"p50": 0.8 * it_p99_ms / 1e3,
                                          "p99": it_p99_ms / 1e3}},
                      "workload": {"kind": "quantized", "requests": 24,
                                   "rate_hz": 20.0, "gamma": 8}}}
    if quality:
        row["detail"]["quality"] = {"logit_div_rel": div_rel,
                                    "acceptance_delta": delta}
    return row


def test_perf_gate_quantized_quality_ceilings(tmp_path):
    """The quantized row gates its inter-token p99 run-to-run like any
    serving leg, and its quality fields as ABSOLUTE ceilings — a
    numerics drift fails even when latency is flat; rows predating the
    quality block skip the ceiling, never crash."""
    hist = tmp_path / "hist.jsonl"

    rows = [_quant_row(), _quant_row()]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "quantized logit divergence" in res.stdout
    assert "quantized spec acceptance delta" in res.stdout

    # divergence past the absolute ceiling: FAIL with latency flat
    rows = [_quant_row(), _quant_row(div_rel=0.3)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1
    assert "FAIL" in res.stdout and "logit divergence" in res.stdout

    # acceptance delta past 0.05: FAIL
    rows = [_quant_row(), _quant_row(delta=0.08)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1 and "acceptance delta" in res.stdout

    # inter-token p99 regression on the quantized leg still gates
    rows = [_quant_row(), _quant_row(it_p99_ms=1.5)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 1 and "p99 inter-token" in res.stdout

    # a row predating the quality block: ceilings skip silently
    rows = [_quant_row(), _quant_row(quality=False)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    res = _gate(str(hist))
    assert res.returncode == 0, res.stdout
