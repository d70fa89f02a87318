"""Unified runtime telemetry: registry semantics, span tracing,
Prometheus rendering, the /metrics endpoint against a live
GenerationService, and the Optimizer integration."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import observability as obs


@pytest.fixture()
def reg():
    """A fresh registry installed as the process default for the test
    (integrations resolve the default at use time)."""
    r = obs.MetricRegistry()
    prev = obs.set_default_registry(r)
    try:
        yield r
    finally:
        obs.set_default_registry(prev)


# ----------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_gauge_basics(self, reg):
        c = reg.counter("req_total", "requests")
        c.inc()
        c.inc(2.5)
        assert c.get() == 3.5
        with pytest.raises(ValueError, match="only go up"):
            c.inc(-1)
        g = reg.gauge("temp", "gauge")
        g.set(4.0)
        g.inc()
        g.dec(2)
        assert g.get() == 3.0

    def test_get_or_create_and_type_mismatch(self, reg):
        a = reg.counter("x_total", "x")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="labels"):
            reg.counter("x_total", labelnames=("a",))
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("bad name")

    def test_labels_children_are_independent(self, reg):
        fam = reg.counter("svc_total", "per-service", labelnames=("svc",))
        fam.labels("a").inc(2)
        fam.labels(svc="b").inc(5)
        assert fam.labels("a") is fam.labels("a")
        assert fam.labels("a").get() == 2
        assert fam.labels("b").get() == 5
        with pytest.raises(ValueError, match="label"):
            fam.labels("a", "b")
        with pytest.raises(ValueError, match="labels"):
            fam.inc()  # labeled family has no anonymous child

    def test_name_validation_prometheus_charset(self, reg):
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("9starts_with_digit")
        with pytest.raises(ValueError, match="invalid label name"):
            reg.gauge("ok", labelnames=("a:b",))
        reg.counter("ns:ok_total")  # ':' is legal in METRIC names

    def test_histogram_bucket_mismatch_raises(self, reg):
        reg.histogram("hb_seconds", "h", buckets=(0.001, 0.01))
        reg.histogram("hb_seconds", "h")  # buckets=None: don't-care
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("hb_seconds", "h", buckets=(1.0, 10.0))

    def test_gauge_track_survives_mid_flight_toggle(self, reg):
        g = reg.gauge("inflight", "g")
        with g.track():
            assert g.get() == 1
            reg.disable()
        # exit mirrored the ENTRY decision: back to 0, not stuck at 1
        reg.enable()
        assert g.get() == 0
        reg.disable()
        with g.track():
            reg.enable()
        assert g.get() == 0  # and the reverse toggle never goes to -1

    def test_histogram_buckets_cumulative(self, reg):
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 5.0))
        for v in (0.05, 0.5, 0.7, 3.0, 100.0):
            h.observe(v)
        cum, total, count = h.get()
        assert cum == [1, 3, 4, 5]  # cumulative incl. +Inf
        assert count == 5 and total == pytest.approx(104.25)
        with pytest.raises(ValueError, match="sorted"):
            reg.histogram("bad_h", buckets=(1.0, 0.5))

    def test_histogram_timer(self, reg):
        h = reg.histogram("t_seconds", "t")
        with h.time():
            pass
        _, total, count = h.get()
        assert count == 1 and total >= 0

    def test_concurrent_increments_are_exact(self, reg):
        c = reg.counter("n_total", "n")
        h = reg.histogram("hc", "h", buckets=(10.0,))

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get() == 8000
        assert h.get()[2] == 8000

    def test_disabled_registry_is_noop(self, reg):
        c = reg.counter("c_total", "c")
        h = reg.histogram("h_seconds", "h")
        reg.disable()
        c.inc(100)
        h.observe(1.0)
        assert c.get() == 0 and h.get()[2] == 0
        reg.enable()
        c.inc()
        assert c.get() == 1


# ------------------------------------------------------------------ tracing
class TestTracing:
    def test_span_nesting_builds_tree(self):
        tr = obs.Tracer()
        with tr.span("outer"):
            with tr.span("inner_a"):
                pass
            with tr.span("inner_b"):
                with tr.span("leaf"):
                    pass
        roots = tr.roots()
        assert [r.name for r in roots] == ["outer"]
        outer = roots[0]
        assert [c.name for c in outer.children] == ["inner_a", "inner_b"]
        assert outer.children[1].children[0].name == "leaf"
        assert outer.duration >= sum(c.duration for c in outer.children)
        assert "outer" in tr.render() and "leaf" in tr.render()

    def test_threads_get_their_own_stacks(self):
        tr = obs.Tracer()
        done = threading.Event()

        def worker():
            with tr.span("worker_root"):
                with tr.span("worker_child"):
                    done.wait(5)

        t = threading.Thread(target=worker)
        with tr.span("main_root"):
            t.start()
            done.set()
            t.join()
        names = {r.name for r in tr.roots()}
        # the worker's span is a ROOT of its own thread's trace, never a
        # child of the main thread's open span
        assert names == {"main_root", "worker_root"}
        main = tr.roots(name="main_root")[0]
        assert [c.name for c in main.children] == []

    def test_span_feeds_histogram_and_disable(self, reg):
        h = reg.histogram("span_seconds", "s")
        tr = obs.Tracer()
        with tr.span("x", histogram=h):
            pass
        assert h.get()[2] == 1
        tr.disable()
        # a disabled TRACER stops recording spans but must not silence
        # the caller's METRIC (the registry has its own disable switch)
        with tr.span("y", histogram=h):
            pass
        assert h.get()[2] == 2 and tr.roots(name="y") == []


# --------------------------------------------------------------- exporters
GOLDEN = """\
# HELP demo_requests_total requests served
# TYPE demo_requests_total counter
demo_requests_total{service="gen"} 3
# HELP demo_queue_depth queue depth
# TYPE demo_queue_depth gauge
demo_queue_depth 2.5
# HELP demo_wait_seconds wait time
# TYPE demo_wait_seconds histogram
demo_wait_seconds_bucket{le="0.1"} 1
demo_wait_seconds_bucket{le="1"} 2
demo_wait_seconds_bucket{le="+Inf"} 3
demo_wait_seconds_sum 3.55
demo_wait_seconds_count 3
"""


def test_prometheus_text_golden(reg):
    reg.counter("demo_requests_total", "requests served",
                labelnames=("service",)).labels("gen").inc(3)
    reg.gauge("demo_queue_depth", "queue depth").set(2.5)
    h = reg.histogram("demo_wait_seconds", "wait time", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 3.0):
        h.observe(v)
    assert obs.render_prometheus(reg) == GOLDEN


def test_label_escaping(reg):
    reg.gauge("esc", "e", labelnames=("v",)).labels('a"b\\c\nd').set(1)
    line = [l for l in obs.render_prometheus(reg).splitlines()
            if l.startswith("esc{")][0]
    assert line == 'esc{v="a\\"b\\\\c\\nd"} 1'


def _unescape_label(s: str) -> str:
    """Decode a label value per the exposition format (the scraper's
    side of the contract: \\\\ -> \\, \\" -> ", \\n -> newline)."""
    out, i = [], 0
    while i < len(s):
        c = s[i]
        if c == "\\":
            nxt = s[i + 1]  # a trailing lone backslash would be a bug
            out.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
            i += 2
        else:
            assert c not in ('"', "\n"), \
                f"raw {c!r} must never appear inside a label value"
            out.append(c)
            i += 1
    return "".join(out)


def test_label_escaping_hostile_values_roundtrip(reg):
    """Regression: every exposition-format special (backslash,
    double-quote, line feed) survives a render → parse round-trip,
    including the adversarial literal-backslash-then-n sequence that
    naive escapers turn into a newline, on gauges AND on histogram
    bucket lines (where the hostile value shares the label set with
    ``le``)."""
    hostiles = [
        'plain',
        'he said "hi"',
        'back\\slash',
        'line\nfeed',
        'literal\\nbackslash-n',
        'trailing\\',
        '\\"\n mixed \n"\\',
    ]
    g = reg.gauge("esc_hostile", "g", labelnames=("v",))
    for i, v in enumerate(hostiles):
        g.labels(v).set(i)
    h = reg.histogram("esc_hostile_hist", "h", labelnames=("v",),
                      buckets=(0.1, 1.0))
    h.labels(hostiles[-1]).observe(0.5)
    text = obs.render_prometheus(reg)

    label_re = re.compile(r'\{v="((?:[^"\\]|\\.)*)"')
    seen = []
    for line in text.splitlines():
        if line.startswith("esc_hostile{"):
            m = label_re.match(line[len("esc_hostile"):])
            assert m, f"unparseable label set in {line!r}"
            seen.append(_unescape_label(m.group(1)))
    assert sorted(seen) == sorted(hostiles)  # children render sorted
    # each physical line is one sample: a raw newline inside a value
    # would have split it and broken the value column
    for line in text.splitlines():
        if line.startswith("esc_hostile{"):
            assert line.rsplit(" ", 1)[1] in {str(i) for i in
                                              range(len(hostiles))}
    # histogram bucket lines keep (v, le) both parseable
    bucket_lines = [l for l in text.splitlines()
                    if l.startswith("esc_hostile_hist_bucket")]
    assert len(bucket_lines) == 3  # 0.1, 1.0, +Inf
    for line in bucket_lines:
        m = label_re.match(line[len("esc_hostile_hist_bucket"):])
        assert _unescape_label(m.group(1)) == hostiles[-1]
        assert ',le="' in line
    # HELP lines escape backslash + newline too
    reg.gauge("esc_help", "help with\nnewline and \\ backslash").set(1)
    help_line = [l for l in obs.render_prometheus(reg).splitlines()
                 if l.startswith("# HELP esc_help")][0]
    assert help_line == ("# HELP esc_help help with\\nnewline and "
                         "\\\\ backslash")


def test_percentile_summary_single_and_none_samples():
    """Regression for the freshly-constructed-engine path: one sample
    and all-None samples must summarize, never raise."""
    from bigdl_tpu.observability import percentile_summary

    s = percentile_summary([0.25])
    assert s == {"count": 1, "mean": 0.25, "p50": 0.25, "p90": 0.25,
                 "p99": 0.25}
    s = percentile_summary([None, None])
    assert s["count"] == 0
    assert s["mean"] is s["p50"] is s["p90"] is s["p99"] is None
    assert percentile_summary(iter([]))["count"] == 0


def test_write_prometheus_snapshot(reg, tmp_path):
    reg.counter("snap_total", "s").inc(7)
    path = str(tmp_path / "metrics.prom")
    text = obs.write_prometheus(path, reg)
    with open(path) as f:
        assert f.read() == text
    assert "snap_total 7" in text


def test_tensorboard_bridge(reg):
    reg.counter("b_total", "b").inc(4)
    reg.gauge("b_g", "g", labelnames=("k",)).labels("v").set(1.5)
    h = reg.histogram("b_h", "h", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    seen = []

    class Writer:
        def add_scalar(self, tag, value, step):
            seen.append((tag, value, step))

    obs.TensorBoardBridge(Writer(), registry=reg).publish(step=7)
    d = {t: v for t, v, _ in seen}
    assert d["b_total"] == 4
    assert d['b_g{k="v"}'] == 1.5
    assert d["b_h_count"] == 2 and d["b_h_sum"] == 2.5
    assert d["b_h_mean"] == pytest.approx(1.25)
    assert all(s == 7 for _, _, s in seen)


def test_http_endpoint_and_healthz(reg):
    reg.counter("httpd_total", "h").inc()
    healthy = {"ok": True}
    with obs.start_http_server(registry=reg, host="127.0.0.1",
                               healthz=lambda: healthy["ok"]) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        resp = urllib.request.urlopen(f"{base}/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        assert "httpd_total 1" in resp.read().decode()
        hz = urllib.request.urlopen(f"{base}/healthz")
        assert json.loads(hz.read())["status"] == "ok"
        healthy["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz")
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")


# ----------------------------------------------------- service integration
def test_metrics_endpoint_roundtrip_live_generation_service(reg):
    """The acceptance bar: scrape /metrics off a live GenerationService
    and get valid Prometheus text including the batch-occupancy
    histogram and tokens/sec."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import GenerationService
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(5)
    lm = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                       num_layers=2, max_len=48, use_rope=True)
    lm.evaluate()
    svc = GenerationService(lm, max_batch=4, batch_timeout_ms=50.0,
                            bucket_tokens=8)
    r = np.random.RandomState(3)
    reqs = [(r.randint(0, 32, (5,)), 6) for _ in range(4)]
    out = [None] * len(reqs)
    threads = [threading.Thread(
        target=lambda i=i, p=p, n=n: out.__setitem__(
            i, svc.generate(p, n))) for i, (p, n) in enumerate(reqs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(o is not None for o in out)

    with obs.start_http_server(registry=reg, host="127.0.0.1") as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read().decode()
    assert ('bigdl_serve_batch_occupancy_bucket{service="generation",'
            'le="+Inf"}') in body
    assert 'bigdl_generation_tokens_total{service="generation"} 24' \
        in body  # 4 requests x 6
    assert "bigdl_generation_tokens_per_sec" in body
    assert 'bigdl_serve_requests_total{service="generation"} 4' in body
    assert 'bigdl_serve_queue_wait_seconds_count{service="generation"}' \
        in body
    # every exposition line parses as `name{labels} value`
    for line in body.splitlines():
        if line and not line.startswith("#"):
            parts = line.rsplit(" ", 1)
            assert len(parts) == 2 and parts[1], line
            float(parts[1])

    # the stats() façade reads the same registry series
    s = svc.stats()
    assert s["served"] == 4
    assert s["served"] / s["dispatches"] == pytest.approx(
        s["mean_batch_occupancy"], abs=5e-4)


def test_prediction_service_telemetry(reg):
    from bigdl_tpu import nn
    from bigdl_tpu.optim.prediction_service import PredictionService

    m = nn.Sequential(nn.Linear(4, 2))
    svc = PredictionService(m, num_threads=2, max_batch=4,
                            batch_timeout_ms=20.0)
    xs = [np.random.RandomState(i).randn(4).astype(np.float32)
          for i in range(4)]
    outs = [None] * 4
    threads = [threading.Thread(
        target=lambda i=i: outs.__setitem__(i, svc.predict(xs[i])))
        for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(o is not None and o.shape == (2,) for o in outs)
    text = obs.render_prometheus(reg)
    assert 'bigdl_serve_requests_total{service="prediction"} 4' in text
    assert 'bigdl_serve_dispatch_seconds_count{service="prediction"}' \
        in text
    s = svc.stats()
    assert s["served"] == 4 and s["dispatches"] >= 1


# ----------------------------------------------------- optimizer integration
def test_optimizer_smoke_populates_training_metrics(reg):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(32)]
    m = nn.Sequential(nn.Linear(4, 2))
    opt = Optimizer(model=m, dataset=samples, criterion=nn.MSECriterion(),
                    batch_size=8, end_when=Trigger.max_epoch(2))
    opt.set_optim_method(SGD(learning_rate=0.05))
    obs.trace.reset()
    opt.optimize()

    assert reg.get("bigdl_train_step_seconds").get()[2] == 8  # 2 epochs x 4
    assert reg.get("bigdl_train_records_total").get() == 64
    assert reg.get("bigdl_train_loss").get() > 0
    assert reg.get("bigdl_train_learning_rate").get() == \
        pytest.approx(0.05)
    assert reg.get("bigdl_train_grad_norm").get() > 0
    # the compile-count gauge rides jax's _cache_size
    assert reg.get("bigdl_train_jit_compiles").get() == 1
    assert reg.get("bigdl_train_throughput_records_per_sec").get() > 0
    # one root an iteration; the step is its child
    its = obs.trace.roots(name="train/iteration")
    assert len(its) == 8
    assert all("train/step" in [c.name for c in it.children] for it in its)
    # the same registry renders cleanly for a scraper
    text = obs.render_prometheus(reg)
    assert "# TYPE bigdl_train_step_seconds histogram" in text


def test_optimizer_disabled_observability_takes_plain_step(reg):
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.optim import Optimizer, SGD, Trigger

    rng = np.random.RandomState(1)
    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(16)]
    m = nn.Sequential(nn.Linear(4, 2))
    opt = Optimizer(model=m, dataset=samples, criterion=nn.MSECriterion(),
                    batch_size=8, end_when=Trigger.max_epoch(1))
    opt.set_optim_method(SGD(learning_rate=0.05))
    obs.disable()
    try:
        opt.optimize()
    finally:
        obs.enable()
    step = reg.get("bigdl_train_step_seconds")
    assert step is None or step.get()[2] == 0
