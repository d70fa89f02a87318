"""Device-resource observability: memory accounting + pool attribution
(``observability/memory.py``), on-demand profiler capture
(``profiler.py``), recompile/SLO watchdogs (``watchdog.py``), their
engine wiring (pools registered, queue-wait histogram, alerts in
``stats()``/degraded ``/healthz``), the ``/debug/memory`` +
``/debug/profile`` endpoints, and the metrics lint.

The acceptance arc under test: an injected recompile storm and a
synthetic SLO breach each produce a flight-recorder alert event, a
Prometheus alert gauge, and a ``degraded`` healthz body (still HTTP
200 — 503 stays reserved for a crashed loop); ``/debug/memory``
attributes HBM to the KV slot pool, prefill staging, prefix pool, and
params by name; pool gauges move when KV is donated into the prefix
pool.
"""

import gc
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu.observability import memory as obs_memory
from bigdl_tpu.observability import profiler
from bigdl_tpu.observability.events import FlightRecorder
from bigdl_tpu.observability.watchdog import (
    RecompileWatchdog, SloObjective, SloWatchdog,
)


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


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(29)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


# --------------------------------------------------------- pool registry
class TestPoolRegistry:
    def test_register_and_tree_bytes(self):
        import jax.numpy as jnp

        tree = {"a": jnp.ones((4, 8), jnp.float32),
                "b": [jnp.ones((2,), jnp.int32), None]}
        assert obs_memory.tree_bytes(tree) == 4 * 8 * 4 + 2 * 4
        assert obs_memory.tree_bytes(None) == 0

        name = obs_memory.register_pool("t/static", lambda: 42)
        try:
            assert "t/static" in obs_memory.registered_pools()
            assert obs_memory.pool_sizes()["t/static"] == 42
        finally:
            obs_memory.unregister_pool(name)
        assert "t/static" not in obs_memory.registered_pools()
        # a raising (or non-int) pool is skipped THIS sample but stays
        # registered — transient errors must not delete attribution
        obs_memory.register_pool("t/broken", lambda: 1 // 0)
        obs_memory.register_pool("t/notint", lambda: "nope")
        sizes = obs_memory.pool_sizes()
        assert "t/broken" not in sizes and "t/notint" not in sizes
        assert "t/broken" in obs_memory.registered_pools()
        obs_memory.unregister_pool("t/broken")
        obs_memory.unregister_pool("t/notint")
        # fn-guarded unregister: the wrong fn is a no-op
        fn = lambda: 5  # noqa: E731
        obs_memory.register_pool("t/guarded", fn)
        obs_memory.unregister_pool("t/guarded", lambda: 6)
        assert "t/guarded" in obs_memory.registered_pools()
        obs_memory.unregister_pool("t/guarded", fn)
        assert "t/guarded" not in obs_memory.registered_pools()
        with pytest.raises(ValueError):
            obs_memory.register_pool("", lambda: 0)
        with pytest.raises(TypeError):
            obs_memory.register_pool("t/x", 7)

    def test_weak_owner_pools_pruned_after_collection(self):
        class Owner:
            bytes = 99

        o = Owner()
        obs_memory.register_owned_pools(o, {"t/weak": lambda s: s.bytes})
        assert obs_memory.pool_sizes()["t/weak"] == 99
        del o
        gc.collect()
        # the registration held only a weakref: the pool self-prunes
        assert "t/weak" not in obs_memory.pool_sizes()
        assert "t/weak" not in obs_memory.registered_pools()


# --------------------------------------------------------- memory monitor
def test_memory_monitor_sample_gauges_and_watermark(reg, rec):
    import jax.numpy as jnp

    # something must live on the device for a watermark to exist
    # (importing the package no longer puts an RNG key there)
    held = jnp.ones((256,), jnp.float32).block_until_ready()
    mon = obs.DeviceMemoryMonitor(registry=reg, history=4)
    obs_memory.register_pool("t/mon", lambda: 1000)
    try:
        s = mon.sample()
    finally:
        obs_memory.unregister_pool("t/mon")
    assert s["devices"], "at least one local device"
    d0 = s["devices"][0]
    assert d0["source"] in ("memory_stats", "live_arrays")
    assert d0["bytes_in_use"] >= 0 and s["bytes_in_use"] >= 0
    assert s["pools"]["t/mon"] == 1000
    # gauges landed in THIS registry under the canonical names
    assert reg.get("bigdl_device_hbm_bytes_in_use") \
        .labels("0").get() == d0["bytes_in_use"]
    assert reg.get("bigdl_device_pool_bytes") \
        .labels("t/mon").get() == 1000
    # a pool that disappears is zeroed on the next sample, and the
    # ring + high watermark accumulate
    s2 = mon.sample()
    assert "t/mon" not in s2["pools"]
    assert reg.get("bigdl_device_pool_bytes").labels("t/mon").get() == 0
    dbg = mon.debug_memory()
    assert dbg["peak_bytes"] >= max(s["bytes_in_use"], 1) - 1
    assert dbg["peak"] is not None
    assert 1 <= len(dbg["history"]) <= 4
    assert {"ts", "bytes_in_use", "pools"} <= set(dbg["history"][0])
    # the watermark left a recorder event
    assert any(e.kind == "memory/high_watermark" for e in rec.tail()) \
        or s["bytes_in_use"] == 0
    del held


# ------------------------------------------------------ recompile watchdog
def test_recompile_watchdog_storm_fires_and_clears(reg, rec):
    compiles = [0]
    wd = RecompileWatchdog(lambda: compiles[0], service="t",
                           warmup_growths=2, window=16, storm_growths=3,
                           clear_after=4, registry=reg, recorder=rec)
    # warmup growths are free: no alert however fast they come
    for _ in range(3):
        compiles[0] += 1
        assert wd.sample() is False
    # post-warmup growth keeps happening -> storm
    fired_at = None
    for i in range(4):
        compiles[0] += 1
        if wd.sample():
            fired_at = i
            break
    assert fired_at is not None and wd.active
    alert = wd.alert()
    assert alert["alert"] == "recompile_storm"
    assert alert["severity"] == "critical"
    assert reg.get("bigdl_watchdog_alert_active") \
        .labels("recompile_storm", "t").get() == 1
    assert any(e.kind == "watchdog/recompile_storm" for e in rec.tail())
    # stable compiles for clear_after samples -> alert clears
    for _ in range(6):
        wd.sample()
    assert not wd.active and wd.alert() is None
    assert reg.get("bigdl_watchdog_alert_active") \
        .labels("recompile_storm", "t").get() == 0
    assert any(e.kind == "watchdog/recompile_cleared"
               for e in rec.tail())
    # a broken probe is survivable
    bad = RecompileWatchdog(lambda: 1 // 0, registry=reg, recorder=rec)
    assert bad.sample() is False


def test_recompile_watchdog_clear_after_exceeds_window(reg, rec):
    """clear_after > window must hold the alert for the full quiet
    interval — window-pruned storm marks are detection state, not the
    clear countdown."""
    compiles = [0]
    wd = RecompileWatchdog(lambda: compiles[0], service="t2",
                           warmup_growths=0, window=4, storm_growths=2,
                           clear_after=10, registry=reg, recorder=rec)
    wd.sample()
    for _ in range(3):
        compiles[0] += 1
        wd.sample()
    assert wd.active
    # 9 quiet samples: past the window, still inside clear_after
    for _ in range(9):
        wd.sample()
    assert wd.active
    wd.sample()  # 10th quiet sample: clears
    assert not wd.active


# ------------------------------------------------------------ slo watchdog
def test_slo_watchdog_burn_rate_synthetic_timelines(reg, rec):
    hist = reg.histogram("t_latency_seconds", "t",
                         buckets=(0.01, 0.1, 1.0))
    wd = SloWatchdog(service="t", registry=reg, recorder=rec)
    wd.watch(SloObjective("ttft_p90", threshold_s=0.1, target=0.9,
                          window_s=60.0, burn_threshold=2.0,
                          min_count=10), hist._only())
    t = 1000.0
    wd.sample(now=t)
    # healthy traffic: 5% violations < budget*burn_threshold (20%)
    for i in range(40):
        hist.observe(0.5 if i % 20 == 0 else 0.02)
    assert wd.sample(now=t + 10) is False
    # SLO-violating timelines: half the observations blow the threshold
    for i in range(40):
        hist.observe(0.5 if i % 2 == 0 else 0.02)
    assert wd.sample(now=t + 20) is True
    (alert,) = wd.alerts()
    assert alert["alert"] == "slo:ttft_p90"
    assert alert["burn_rate"] >= 2.0
    assert reg.get("bigdl_watchdog_alert_active") \
        .labels("slo:ttft_p90", "t").get() == 1
    assert reg.get("bigdl_watchdog_slo_burn_rate") \
        .labels("ttft_p90", "t").get() == pytest.approx(
            alert["burn_rate"], rel=0.01)
    assert any(e.kind == "watchdog/slo_burn" for e in rec.tail())
    # the violating window ages out under good traffic -> clears
    for _ in range(200):
        hist.observe(0.02)
    assert wd.sample(now=t + 100) is False
    assert wd.alerts() == []
    assert any(e.kind == "watchdog/slo_cleared" for e in rec.tail())
    assert reg.get("bigdl_watchdog_alert_active") \
        .labels("slo:ttft_p90", "t").get() == 0


def test_slo_threshold_between_bucket_edges_rounds_pessimistic(reg, rec):
    """A threshold that is not a bucket edge must round DOWN to the
    previous edge (over-alerting), never up — a watchdog that counts
    2.2s observations as 'good' against a 2.0s objective would sit
    silent through a full breach."""
    hist = reg.histogram("t_mid_seconds", "t", buckets=(1.0, 2.5, 5.0))
    wd = SloWatchdog(service="t", registry=reg, recorder=rec)
    wd.watch(SloObjective("mid", threshold_s=2.0, target=0.9,
                          window_s=60.0, burn_threshold=2.0,
                          min_count=10), hist._only())
    wd.sample(now=500.0)
    for _ in range(20):
        hist.observe(2.2)  # violates the 2.0s objective
    assert wd.sample(now=510.0) is True
    assert wd.alerts()[0]["alert"] == "slo:mid"


def test_slo_objective_validation():
    with pytest.raises(ValueError):
        SloObjective("x", threshold_s=0.1, target=1.0)
    with pytest.raises(ValueError):
        SloObjective("x", threshold_s=0.0)
    with pytest.raises(ValueError):
        SloObjective("x", threshold_s=0.1, window_s=0)


# ------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def engine_run(lm):
    """ONE shared engine + request mix for the integration assertions:
    a hair-trigger TTFT objective (every real request violates 1µs)
    makes the synthetic SLO breach, pools register at construction,
    donations populate the prefix pool."""
    mreg = obs.MetricRegistry()
    prev_reg = obs.set_default_registry(mreg)
    mrec = FlightRecorder()
    prev_rec = obs.set_default_recorder(mrec)
    from bigdl_tpu.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        lm, max_slots=2, prefill_chunk=4, service_name="resobs",
        slo_objectives=[dict(name="ttft_p99", metric="ttft",
                             threshold_s=1e-6, target=0.99,
                             window_s=600.0, min_count=2)])
    try:
        with eng:
            r = np.random.RandomState(11)
            handles = [eng.submit(r.randint(0, 32, (t0,)), n)
                       for t0, n in [(5, 4), (9, 3), (6, 4)]]
            for h in handles:
                h.result(timeout=120)
            yield eng, mreg, mrec, handles
    finally:
        obs.set_default_registry(prev_reg)
        obs.set_default_recorder(prev_rec)


def test_engine_pool_attribution_moves_on_donation(engine_run):
    eng, mreg, mrec, handles = engine_run
    sizes = obs_memory.pool_sizes()
    kv = sizes["serving/resobs/kv_page_pool"]
    assert kv == obs_memory.tree_bytes(eng._kv_pool) > 0
    assert sizes["serving/resobs/params"] > 0
    # the slots are idle again, so every live page is one a finished
    # slot DONATED to the prefix index: occupied bytes moved off 0
    in_use = sizes["serving/resobs/prefix_kv_in_use"]
    assert in_use == eng._prefix.bytes_in_use > 0
    assert in_use == sizes["serving/resobs/kv_pages_in_use"] <= kv
    # and the monitor publishes the attribution as gauges
    mon = obs.DeviceMemoryMonitor(registry=mreg)
    mon.sample()
    assert mreg.get("bigdl_device_pool_bytes") \
        .labels("serving/resobs/prefix_kv_in_use").get() == in_use


def test_engine_queue_wait_histogram(engine_run):
    eng, mreg, _, handles = engine_run
    _, total, count = mreg.get("bigdl_serving_queue_wait_seconds") \
        .labels("resobs").get()
    assert count == len(handles)
    assert total >= 0.0


def test_engine_slo_breach_degrades_healthz(engine_run):
    eng, mreg, mrec, _ = engine_run
    alerts = eng.stats()["alerts"]
    slo = [a for a in alerts if a["alert"] == "slo:ttft_p99"]
    assert slo, alerts
    assert slo[0]["burn_rate"] >= 2.0
    hz = eng.healthz()
    assert hz["status"] == "degraded" and hz["alerts"]
    assert mreg.get("bigdl_watchdog_alert_active") \
        .labels("slo:ttft_p99", "resobs").get() == 1
    assert any(e.kind == "watchdog/slo_burn" for e in mrec.tail())
    # degraded is 200-with-detail on the endpoint; 503 stays reserved
    # for a crashed loop
    with obs.start_http_server(host="127.0.0.1",
                               healthz=eng.healthz) as srv:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz")
        assert resp.status == 200
        body = json.loads(resp.read())
        assert body["status"] == "degraded"
        assert body["alerts"][0]["alert"] == "slo:ttft_p99"
    assert eng.debug_requests()["alerts"]


def test_debug_memory_endpoint_roundtrip(engine_run):
    eng, mreg, _, _ = engine_run
    mon = obs.DeviceMemoryMonitor(registry=mreg)
    with obs.start_http_server(host="127.0.0.1",
                               debug_memory=mon.debug_memory) as srv:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/memory").read())
    assert doc["now"]["devices"]
    assert doc["now"]["pools"]["serving/resobs/kv_page_pool"] \
        == obs_memory.tree_bytes(eng._kv_pool)
    assert doc["peak_bytes"] >= 0 and doc["history"]
    # the default-monitor route answers too (no explicit monitor wired)
    with obs.start_http_server(host="127.0.0.1") as srv:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/memory").read())
        assert "now" in doc and doc["now"]["devices"]


def test_engine_injected_recompile_storm(engine_run):
    """Last in the shared-engine arc: swap in a hair-trigger watchdog
    over an injected ever-growing compile counter — post-warmup growth
    across loop iterations must raise the storm alert, its gauge, its
    recorder event, and degrade healthz."""
    eng, mreg, mrec, _ = engine_run
    fake = {"n": 0}

    def probe():
        fake["n"] += 1  # "every iteration compiled something new"
        return fake["n"]

    eng._recompile_wd = RecompileWatchdog(
        probe, service="resobs", warmup_growths=1, window=16,
        storm_growths=3, clear_after=1000, registry=mreg, recorder=mrec)
    h = eng.submit(np.arange(1, 6, dtype=np.int32), 8)
    h.result(timeout=120)
    alerts = eng.stats()["alerts"]
    storm = [a for a in alerts if a["alert"] == "recompile_storm"]
    assert storm, alerts
    assert eng.healthz()["status"] == "degraded"
    assert mreg.get("bigdl_watchdog_alert_active") \
        .labels("recompile_storm", "resobs").get() == 1
    assert any(e.kind == "watchdog/recompile_storm"
               for e in mrec.tail())


def test_fresh_engine_stats_latency_never_raises(lm, reg, rec):
    """The percentile façade on a just-constructed engine (no requests,
    loop never started) reports count-0/None summaries instead of
    raising — and a fresh GenerationService does the same."""
    from bigdl_tpu.optim import GenerationService
    from bigdl_tpu.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(lm, max_slots=1, prefill_chunk=4)
    s = eng.stats()
    for phase in ("queue_wait", "prefill", "ttft", "decode", "total"):
        assert s["latency"][phase]["count"] == 0
        assert s["latency"][phase]["p99"] is None
    assert s["alerts"] == []
    assert eng.debug_requests()["latency"]["ttft"]["p50"] is None
    svc = GenerationService(lm, max_batch=2)
    lat = svc.stats()["latency"]
    assert all(v["count"] == 0 and v["p50"] is None
               for v in lat.values())


# ---------------------------------------------------------- profiler
def test_profiler_capture_and_endpoint(reg, rec, tmp_path):
    try:
        path = profiler.capture(0.05, out_dir=str(tmp_path / "prof"))
    except profiler.ProfilerUnavailable as e:
        pytest.skip(f"profiler capture unsupported here: {e}")
    import os
    assert os.path.isdir(path)
    assert sum(len(fs) for _, _, fs in os.walk(path)) > 0
    kinds = [e.kind for e in rec.tail()]
    assert "profiler/capture_start" in kinds
    assert "profiler/capture_done" in kinds
    assert not profiler.capturing()

    with obs.start_http_server(host="127.0.0.1") as srv:
        base = f"http://127.0.0.1:{srv.port}"
        try:
            doc = json.loads(urllib.request.urlopen(
                f"{base}/debug/profile?seconds=0.05").read())
            assert os.path.isdir(doc["artifact"])
        except urllib.error.HTTPError as e:
            assert e.code in (501, 409), e.code
        # POST works; hostile seconds is a 400, not a 500
        req = urllib.request.Request(
            f"{base}/debug/profile?seconds=0.05", data=b"",
            method="POST")
        try:
            doc = json.loads(urllib.request.urlopen(req).read())
            assert os.path.isdir(doc["artifact"])
        except urllib.error.HTTPError as e:
            assert e.code in (501, 409), e.code
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/debug/profile?seconds=nope")
        assert exc.value.code == 400

    with pytest.raises(ValueError):
        profiler.capture(0)


def test_profiler_busy_is_exclusive(tmp_path):
    try:
        profiler.start_capture(str(tmp_path / "p1"))
    except profiler.ProfilerUnavailable as e:
        pytest.skip(f"profiler capture unsupported here: {e}")
    try:
        with pytest.raises(profiler.ProfilerBusy):
            profiler.start_capture(str(tmp_path / "p2"))
    finally:
        assert profiler.stop_capture() is not None
    # idempotent soft stop for timer/finally races
    assert profiler.stop_capture(strict=False) is None
    with pytest.raises(profiler.ProfilerBusy):
        profiler.stop_capture(strict=True)


# -------------------------------------------------------- metrics lint
def _load_lint():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "metrics_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_lint_tree_is_clean(capsys):
    """Tier-1 enforcement of the one-schema rule: no bigdl_* metric is
    registered outside observability/instruments.py anywhere in the
    tree (bench.py included — its gauges moved into instruments)."""
    lint = _load_lint()
    assert lint.main([]) == 0
    assert "ok" in capsys.readouterr().out


def test_metrics_lint_catches_violation(tmp_path, capsys):
    bad = tmp_path / "rogue.py"
    bad.write_text(
        'reg.gauge("bigdl_rogue_bytes", "minted out of place")\n')
    lint = _load_lint()
    assert lint.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "rogue.py" in out and "bigdl_rogue_bytes" in out
    # tests/ and docs/ are out of scope by design
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "t.py").write_text(
        'reg.gauge("bigdl_test_only", "x")\n')
    bad.unlink()
    assert lint.main(["--root", str(tmp_path)]) == 0
