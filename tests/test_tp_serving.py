"""Tensor-parallel serving: the continuous-batching engine SPMD over a
device mesh (``ContinuousBatchingEngine(mesh=...)``).

The acceptance contract under test, on the conftest's virtual 8-device
CPU host mesh: a mesh changes WHERE the math runs, never the tokens —
sharded greedy output is token-identical to the unsharded engine (and
therefore to lone ``model.generate``) through cold prefill, prefix-
cache hits, speculative decoding, and mid-flight admission into
recycled slots; the jit-compile gauge stays FLAT after warmup (pinned
output shardings keep every donated cache tree cycling in one layout);
usage device-seconds scale by the mesh size while still conserving;
and ``stats()["mesh"]`` / the memory-pool registry report honest
per-pool sharded byte attribution. Plus the ``data_axis`` (FSDP-style)
rule set of ``transformer_tp_rules`` and the KV-head divisibility
guard."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.parallel import (
    Engine, kv_pool_spec, shard_params, spec_for_params,
    transformer_tp_rules,
)
from bigdl_tpu.serving import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(23)
    m = TransformerLM(32, embed_dim=32, num_heads=8, num_kv_heads=4,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


@pytest.fixture(scope="module")
def mesh():
    # 4-way model axis over the first half of the virtual host devices
    return Engine.create_mesh([("model", 4)], devices=jax.devices()[:4])


def _direct(lm, prompt, n):
    return np.asarray(lm.generate(jnp.asarray(prompt)[None], n))[0]


def test_sharded_parity_concurrent_mixed_load(lm, mesh):
    """Five mixed-length requests through two slots of a 4-way sharded
    engine: mid-flight admission recycles slots while earlier rows
    decode, and every reply is token-identical to the unsharded
    oracle."""
    r = np.random.RandomState(0)
    reqs = [(r.randint(0, 32, (t0,)), n)
            for t0, n in [(5, 6), (9, 4), (3, 8), (12, 5), (7, 7)]]
    rows = [None] * len(reqs)
    errs = []
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  mesh=mesh,
                                  service_name="tp_parity") as eng:
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


def test_prefix_hit_parity_and_flat_jit(lm, mesh):
    """Template traffic against the sharded engine: warm admissions
    reuse the heads-sharded prefix pool (hits recorded), warm output
    stays token-identical, and the compile gauge is FLAT from the
    first finished request on — the pinned output shardings keep
    every donated tree in one layout."""
    r = np.random.RandomState(1)
    tpl = r.randint(0, 32, (12,)).astype(np.int32)
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  prefill_rows=2, mesh=mesh,
                                  service_name="tp_prefix") as eng:
        p0 = np.concatenate([tpl, r.randint(0, 32, (3,))]).astype(
            np.int32)
        first = eng.submit(p0, 6).result(timeout=120)
        jit0 = eng.stats()["jit_compiles"]
        warm = []
        for _ in range(3):
            p = np.concatenate([tpl, r.randint(0, 32, (2,))]).astype(
                np.int32)
            warm.append((p, eng.submit(p, 5)))
        warm = [(p, h.result(timeout=120)) for p, h in warm]
        st = eng.stats()
    np.testing.assert_array_equal(first, _direct(lm, p0, 6))
    for p, row in warm:
        np.testing.assert_array_equal(row, _direct(lm, p, 5))
    assert st["prefix_cache"]["hits"] >= 1, st["prefix_cache"]
    assert st["jit_compiles"] == jit0, (jit0, st["jit_compiles"])
    assert st["prefix_cache"]["bytes_per_device"] * 4 == \
        st["prefix_cache"]["bytes"]


def test_speculative_parity_on_mesh(lm, mesh):
    """Speculative decode under the mesh: the int8-clone draft's pools
    shard alongside the target's, proposals flow (the clone agrees
    with its float source, so bursts actually extend), and greedy
    output still matches the unsharded oracle with the gauge flat."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    r = np.random.RandomState(2)
    reqs = [(r.randint(0, 32, (t0,)), n)
            for t0, n in [(6, 8), (9, 6), (4, 7)]]
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  mesh=mesh, draft=draft, spec_gamma=3,
                                  service_name="tp_spec") as eng:
        outs = [eng.submit(p, n).result(timeout=180) for p, n in reqs]
        jit0 = eng.stats()["jit_compiles"]
        outs2 = [eng.submit(p, n).result(timeout=180) for p, n in reqs]
        st = eng.stats()
    for (p, n), row in zip(reqs, outs):
        np.testing.assert_array_equal(row, _direct(lm, p, n))
    for (p, n), row in zip(reqs, outs2):
        np.testing.assert_array_equal(row, _direct(lm, p, n))
    assert st["speculation"]["proposed_tokens"] > 0
    assert st["speculation"]["accepted_tokens"] > 0
    assert st["jit_compiles"] == jit0, (jit0, st["jit_compiles"])


def test_mesh_stats_and_pool_attribution(lm, mesh):
    """``stats()["mesh"]`` reports topology + per-pool logical/
    physical/per-device bytes; the process-wide memory-pool registry
    serves the PHYSICAL figure (shards summed — what the devices
    actually hold); the heads-sharded KV pool splits evenly while
    params (mixed sharded/replicated leaves) commit more than their
    logical size."""
    from bigdl_tpu.observability import memory as obs_memory

    eng = ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                   mesh=mesh, service_name="tp_stats")
    try:
        ms = eng.stats()["mesh"]
        assert ms["enabled"] and ms["devices"] == 4
        assert ms["axes"] == {"model": 4}
        assert ms["model_shards"] == 4
        kv = ms["pools"]["kv_page_pool"]
        # evenly sharded: physical == logical, per-device == 1/4
        assert kv["sharded"]
        assert kv["physical_bytes"] == kv["logical_bytes"]
        assert kv["bytes_per_device"] * 4 == kv["physical_bytes"]
        par = ms["pools"]["params"]
        # replicated leaves (layernorms, biases) count once per device
        assert par["physical_bytes"] > par["logical_bytes"]
        sizes = obs_memory.pool_sizes()
        assert sizes["serving/tp_stats/kv_page_pool"] == \
            obs_memory.tree_device_bytes(eng._kv_pool)
        assert sizes["serving/tp_stats/params"] == par["physical_bytes"]
    finally:
        eng.stop(drain=False)


def test_device_seconds_scale_by_mesh_and_conserve(lm, mesh):
    """One SPMD dispatch occupies every mesh device: the ledger bills
    wall x devices on BOTH the per-tenant and the busy side, so
    tenant device-second sums still conserve the measured busy total,
    and the summary names the factor."""
    r = np.random.RandomState(3)
    with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                  mesh=mesh,
                                  service_name="tp_usage") as eng:
        hs = [eng.submit(r.randint(0, 32, (6,)), 5, tenant=t)
              for t in ("a", "b", "a")]
        for h in hs:
            h.result(timeout=120)
        usage = eng.stats()["usage"]
        busy = eng._usage.device_time()
    assert usage["devices"] == 4
    total_busy = busy["total"]
    assert total_busy > 0
    tenant_sum = sum(a["device_s"] for a in usage["tenants"].values())
    # warmup (cold-compile) dispatches are excluded from both sides
    assert tenant_sum == pytest.approx(total_busy, rel=1e-6, abs=1e-9)


def test_kv_head_divisibility_guard(mesh):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(5)
    bad = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                        num_layers=1, max_len=32, use_rope=True)
    bad.evaluate()
    with pytest.raises(ValueError, match="num_kv_heads"):
        ContinuousBatchingEngine(bad, mesh=mesh,
                                 service_name="tp_guard")


def test_kv_pool_spec_shape():
    from jax.sharding import PartitionSpec as P

    assert kv_pool_spec("model") == P(None, "model", None, None)


class TestDataAxisRules:
    """``transformer_tp_rules(data_axis=...)``: the documented (and
    previously DEAD) FSDP-style second axis — weight matrices shard
    over it on the dimension the model split leaves free, and the
    positional table's rows spread across it."""

    def _model(self):
        from bigdl_tpu.models.transformer import TransformerLM
        from bigdl_tpu.utils import random as rnd

        rnd.set_seed(7)
        m = TransformerLM(32, embed_dim=32, num_heads=8, num_layers=2,
                          max_len=16, use_rope=False)
        m.evaluate()
        return m

    def test_specs_cover_both_axes(self):
        from jax.sharding import PartitionSpec as P

        m = self._model()
        specs = spec_for_params(m.params_dict(),
                                transformer_tp_rules("model", "data"))
        blk = specs["block0"]
        assert blk["attn"]["qkv"]["~params"]["weight"] == \
            P("model", "data")
        assert blk["fc2"]["~params"]["weight"] == P("data", "model")
        assert specs["~params"]["tok_embed"] == P("model", "data")
        assert specs["~params"]["pos_embed"] == P("data", None)
        assert specs["ln_f"]["~params"]["weight"] == P()
        # and the one-axis form is unchanged by the refactor
        tp_only = spec_for_params(m.params_dict(),
                                  transformer_tp_rules("model"))
        assert tp_only["block0"]["attn"]["qkv"]["~params"]["weight"] \
            == P("model", None)
        # no FSDP rule without the axis: the table stays replicated
        assert tp_only["~params"]["pos_embed"] == P()

    def test_2d_sharded_forward_matches_replicated(self):
        m = self._model()
        params, buffers = m.params_dict(), m.buffers_dict()
        ids = jnp.asarray(np.random.RandomState(8).randint(
            0, 32, (4, 8)))
        want = m(ids)

        from bigdl_tpu.nn.module import pure_apply

        mesh2d = Engine.create_mesh([("data", 2), ("model", 4)])
        sharded = shard_params(params, mesh2d,
                               transformer_tp_rules("model", "data"))
        apply_fn = pure_apply(m)

        @jax.jit
        def fwd(p, ids):
            out, _ = apply_fn(p, buffers, ids, rng=None, training=False)
            return out

        got = fwd(sharded, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_run_tp_comparison_smoke(lm):
    """The bench harness behind ``bench.py --serving --tp``: one tiny
    Poisson workload, sharded vs unsharded, token parity asserted by
    the harness itself, row shape carries what perf_gate reads."""
    from bigdl_tpu.serving import run_tp_comparison

    res = run_tp_comparison(lm, tp=2, n_requests=4, rate_hz=50.0,
                            max_slots=2, prefill_chunk=4,
                            prefill_rows=2, seed=11)
    assert res["token_parity"] is True
    assert res["workload"]["kind"] == "tensor_parallel"
    assert res["workload"]["tp"] == 2
    assert res["sharded"]["mesh"]["model_shards"] == 2
    assert res["sharded"]["ttft"]["p99"] is not None
    assert res["sharded"]["inter_token"]["p99"] is not None
    assert res["unsharded"]["mesh"]["enabled"] is False
    # the perf-gate reader finds the sharded block
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "perf_gate.py"))
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)
    row = {"metric": "serving_tp_tokens_per_sec",
           "detail": {"sharded": res["sharded"]}}
    assert pg.ttft_p99(row) == res["sharded"]["ttft"]["p99"]
    assert pg.inter_token_p99(row) == \
        res["sharded"]["inter_token"]["p99"]
