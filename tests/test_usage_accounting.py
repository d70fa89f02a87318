"""Per-request usage accounting and goodput attribution
(``observability/accounting.py`` + its serving-engine wiring).

The acceptance arc under test is CONSERVATION: a finished request's
ledgered token counts equal its delivered tokens exactly
(``prefill + prefix_reused == prompt``, ``decode == timeline tokens``),
and the device-seconds summed across all tenants equal the engine's
measured dispatch busy time within float tolerance — every dispatch's
wall is split across the rows it advanced with weights summing to 1,
so nothing is double-billed and nothing vanishes. Plus: the tenant
cardinality cap folds overflow names into ``"other"``, concurrent
submits keep the ledger consistent, ``/debug/usage`` round-trips over
HTTP, the jit-compile gauge stays flat with accounting on (zero
device programs), and the metrics lint's doc-drift check catches an
instrument registered but undocumented.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu.observability.accounting import UsageLedger
from bigdl_tpu.observability.events import FlightRecorder


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

    rnd.set_seed(37)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


def _engine(lm, reg, **kw):
    from bigdl_tpu.serving import ContinuousBatchingEngine

    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("registry", reg)
    return ContinuousBatchingEngine(lm, **kw)


def _conserves(summary, rel=1e-3):
    """Tenant device-second sums match the measured busy time."""
    attributed = sum(a["device_s"]
                     for a in summary["tenants"].values())
    busy = summary["goodput"]["device_seconds"]["total"]
    return abs(attributed - busy) <= 1e-6 + rel * busy


# ------------------------------------------------------- ledger units
def test_ledger_unit_conservation_and_residency(reg, rec):
    led = UsageLedger(service="unit", registry=reg, recorder=rec,
                      token_bytes=10.0)
    a = led.begin("req-a", "alice", prompt_tokens=8, max_new_tokens=4,
                  submitted_at=0.0)
    b = led.begin("req-b", None, prompt_tokens=6, max_new_tokens=4,
                  submitted_at=4.0)
    assert a.tenant == "alice" and b.tenant == "default"
    assert led.totals()["in_flight"] == 2

    # admission at t=10: queue wait closes + the reuse credit lands
    led.admitted(a, 10.0, reused_tokens=4)
    led.admitted(b, 10.0)
    assert a.queue_wait_s == 10.0 and b.queue_wait_s == 6.0
    assert a.prefix_reused_tokens == 4 and a.prefix_bytes_saved == 40

    # one prefill dispatch advancing both rows, 3:1 by tokens
    led.add_prefill(a, 4)
    led.add_prefill(b, 6)
    led.charge_dispatch("prefill", 2.0, [(a, 0.75), (b, 0.25)],
                        rows_advanced=2, capacity_rows=4)
    assert a.device_prefill_s == pytest.approx(1.5)
    assert b.device_prefill_s == pytest.approx(0.5)

    # residency as the engine feeds it: 500 B of pages held 10->12
    # while prefilling, 1000 B 12->22 while decoding
    led.accrue_kv(a, 500 * 2.0)
    assert a.kv_byte_seconds == pytest.approx(1000.0)
    led.delivered(a, 1)
    led.charge_dispatch("decode", 1.0, [(a, 1.0)],
                        rows_advanced=1, capacity_rows=2)
    led.accrue_kv(a, 1000 * 10.0)
    led.finalize(a, "finished", 22.0)
    assert a.kv_byte_seconds == pytest.approx(1000.0 + 10000.0)
    # double-finalize is a no-op (the _finish_handle race contract)
    led.finalize(a, "cancelled", 99.0)
    assert a.outcome == "finished"
    led.finalize(b, "timed_out", 30.0)

    t = led.tenants()
    assert t["alice"]["requests"] == 1 and t["alice"]["finished"] == 1
    assert t["default"]["finished"] == 0
    assert led.totals()["in_flight"] == 0
    assert _conserves(led.summary())
    gp = led.goodput()
    assert gp["device_seconds"] == {"prefill": 2.0, "decode": 1.0,
                                    "total": 3.0}
    # waste: prefill round left 2/4 rows idle, decode 1/2
    assert gp["padding_waste_mean"] == pytest.approx(0.5)
    # utilization is wall-weighted: (2*2 + 1*1) / (4*2 + 2*1)
    assert gp["utilization"] == pytest.approx(0.5)
    assert gp["tokens_per_device_second"] == pytest.approx(1 / 3.0,
                                                           abs=0.01)
    # tenant counters landed under (service, tenant)
    assert reg.get("bigdl_serving_tenant_device_seconds_total") \
        .labels("unit", "alice").get() == pytest.approx(a.device_s)
    assert reg.get("bigdl_serving_tenant_requests_total") \
        .labels("unit", "default").get() == 1
    # ... and the usage_final events carry the attribution
    finals = [e for e in rec.snapshot(50)
              if e["kind"] == "request/usage_final"]
    assert [e["outcome"] for e in finals] == ["finished", "timed_out"]
    with pytest.raises(ValueError):
        led.charge_dispatch("verify", 1.0, [], 1, 1)
    with pytest.raises(ValueError):
        UsageLedger(max_tenants=0)


def test_tenant_cardinality_cap_folds_overflow(reg, rec):
    led = UsageLedger(service="cap", registry=reg, recorder=rec,
                      max_tenants=2)
    assert led.resolve_tenant("a") == "a"
    assert led.resolve_tenant("b") == "b"
    # budget spent: new names fold into "other"...
    assert led.resolve_tenant("c") == "other"
    assert led.resolve_tenant("d") == "other"
    # ...while known names keep resolving to themselves (stable)
    assert led.resolve_tenant("a") == "a"
    for name in ("a", "b", "c", "d"):
        r = led.begin(f"req-{name}", name, 4, 2)
        led.delivered(r, 2)
        led.finalize(r, "finished", 1.0)
    t = led.tenants()
    assert set(t) == {"a", "b", "other"}
    assert t["other"]["requests"] == 2
    assert t["other"]["decode_tokens"] == 4


# -------------------------------------------------- engine integration
def test_engine_conservation_tenants_and_flat_jit(lm, reg, rec):
    r = np.random.RandomState(3)
    with _engine(lm, reg, service_name="usage_eng") as eng:
        reqs = [(5, 6, "alice"), (9, 4, "bob"), (3, 8, None),
                (7, 5, "alice"), (6, 3, "bob")]
        handles = [eng.submit(r.randint(0, 32, (t0,)), n, tenant=t)
                   for t0, n, t in reqs]
        for h in handles:
            h.result(timeout=120)
        jit_after_warmup = eng.stats()["jit_compiles"]
        # more traffic under accounting: the compile gauge must not move
        more = [eng.submit(r.randint(0, 32, (t0,)), n, tenant=t)
                for t0, n, t in reqs[:3]]
        for h in more:
            h.result(timeout=120)
        st = eng.stats()
        assert st["jit_compiles"] == jit_after_warmup

        # per-request conservation against the timeline
        for h in handles + more:
            u = h.usage()
            tl = h.timeline()
            assert u["outcome"] == "finished"
            assert u["decode_tokens"] == tl["tokens"]
            assert u["prefill_tokens"] + u["prefix_reused_tokens"] \
                == u["prompt_tokens"]
            assert tl["prefix_tokens"] == u["prefix_reused_tokens"]
            assert u["kv_byte_seconds"] > 0
            assert u["device_s"] >= 0
            assert abs(u["queue_wait_s"] - tl["queue_wait_s"]) < 0.05

        # engine-level conservation: tenant sums == measured busy time
        usage = st["usage"]
        assert _conserves(usage)
        tens = usage["tenants"]
        assert set(tens) == {"alice", "bob", "default"}
        assert usage["totals"]["requests"] == len(handles) + len(more)
        assert usage["totals"]["in_flight"] == 0
        # delivered tokens line up with the tenant aggregates
        want = sum(len(h._tokens) for h in handles + more)
        assert usage["totals"]["decode_tokens"] == want
        assert usage["goodput"]["tokens_delivered"] == want

        # the per-tenant counters mirror the aggregates exactly
        for t, agg in tens.items():
            assert reg.get("bigdl_serving_tenant_decode_tokens_total") \
                .labels("usage_eng", t).get() == agg["decode_tokens"]
            assert reg.get("bigdl_serving_tenant_requests_total") \
                .labels("usage_eng", t).get() == agg["requests"]

        # goodput instruments: device-second counters sum to busy time
        busy = usage["goodput"]["device_seconds"]
        got = sum(reg.get("bigdl_serving_device_seconds_total")
                  .labels("usage_eng", k).get()
                  for k in ("prefill", "decode"))
        # summaries round to 6 decimals; counters keep full precision
        assert got == pytest.approx(busy["total"], abs=1e-5)
        _, _, waste_n = reg.get("bigdl_serving_dispatch_padding_waste") \
            .labels("usage_eng", "decode").get()
        assert waste_n > 0
        assert 0.0 < reg.get(
            "bigdl_serving_occupancy_weighted_utilization") \
            .labels("usage_eng").get() <= 1.0

        # every request recorded its usage_final event
        finals = [e for e in rec.snapshot(4096)
                  if e["kind"] == "request/usage_final"]
        assert len(finals) == len(handles) + len(more)
        # top-N is ordered by attributed device-seconds
        top = eng.debug_usage(3)["top_requests"]
        assert len(top) == 3
        assert top[0]["device_s"] >= top[1]["device_s"] \
            >= top[2]["device_s"]


def test_engine_conservation_under_speculative_decode(lm, reg, rec):
    """Variable-advance conservation: with a draft, decode dispatch
    walls split by per-row ACCEPTED tokens instead of evenly — the
    weights must still sum to 1 (tenant sums equal the measured busy
    time), cold/warmup dispatches stay excluded from both sides, and
    the per-request token identities survive multi-token bursts."""
    from bigdl_tpu.nn.quantized import Quantizer

    draft = Quantizer.quantize(lm)
    draft.evaluate()
    r = np.random.RandomState(9)
    with _engine(lm, reg, service_name="usage_spec", draft=draft,
                 spec_gamma=3) as eng:
        reqs = [(5, 9, "alice"), (8, 4, "bob"), (4, 11, "alice")]
        handles = [eng.submit(r.randint(0, 32, (t0,)), n, tenant=t)
                   for t0, n, t in reqs]
        for h in handles:
            h.result(timeout=120)
        st = eng.stats()
    usage = st["usage"]
    assert _conserves(usage)
    assert st["speculation"]["accepted_tokens"] > 0
    for h, (t0, n, _) in zip(handles, reqs):
        u = h.usage()
        assert u["decode_tokens"] == h.timeline()["tokens"] == n
        assert u["prefill_tokens"] + u["prefix_reused_tokens"] == t0
    # tenant decode-token sums line up despite burst delivery
    want = {"alice": 20, "bob": 4}
    for t, tokens in want.items():
        assert usage["tenants"][t]["decode_tokens"] == tokens


def test_prefix_reuse_savings_credit(lm, reg, rec):
    head = np.arange(1, 17, dtype=np.int32) % 32
    tails = [np.asarray([7, 9], np.int32), np.asarray([3], np.int32)]
    with _engine(lm, reg, service_name="usage_px",
                 admission_window=1) as eng:
        eng.submit(np.concatenate([head, tails[0]]), 3,
                   tenant="warm").result(timeout=120)
        h = eng.submit(np.concatenate([head, tails[1]]), 3,
                       tenant="warm")
        h.result(timeout=120)
        u = h.usage()
        assert u["prefix_reused_tokens"] == h.prefix_tokens > 0
        assert u["prefix_bytes_saved"] == int(
            u["prefix_reused_tokens"] * eng._token_bytes)
        assert u["prefill_tokens"] + u["prefix_reused_tokens"] \
            == u["prompt_tokens"]
        # the cache's own cumulative savings credit agrees
        pc = eng.stats()["prefix_cache"]
        assert pc["bytes_saved"] >= u["prefix_bytes_saved"] > 0
        # and the tenant got the reuse credit too
        assert eng.stats()["usage"]["tenants"]["warm"][
            "prefix_reused_tokens"] == u["prefix_reused_tokens"]


def test_concurrent_submits_ledger_consistent(lm, reg, rec):
    r = np.random.RandomState(5)
    names = ["t-a", "t-b", "t-c", "t-d"]  # one past the cap below
    reqs = [(r.randint(0, 32, (int(r.randint(3, 10)),)),
             int(r.randint(2, 6)), names[i % 4]) for i in range(12)]
    errs = []
    with _engine(lm, reg, service_name="usage_cc",
                 usage_tenants=3) as eng:
        handles = [None] * len(reqs)

        def worker(i, p, n, t):
            try:
                handles[i] = eng.submit(p, n, tenant=t)
                handles[i].result(timeout=120)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i, p, n, t))
                   for i, (p, n, t) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        usage = eng.stats()["usage"]
        # 4 names raced for 3 cap slots: whichever 3 won keep their
        # series, the 4th folded into "other" (scheduling-dependent
        # WHICH one folds, never WHETHER)
        tens = set(usage["tenants"])
        assert "other" in tens and len(tens) == 4
        assert len(tens & set(names)) == 3
        assert usage["totals"]["requests"] == len(reqs)
        assert usage["totals"]["in_flight"] == 0
        # ledger totals equal the sum over the handles' own records
        by_handle = [h.usage() for h in handles]
        for key in ("decode_tokens", "prefill_tokens",
                    "prefix_reused_tokens"):
            assert usage["totals"][key] == sum(u[key]
                                               for u in by_handle)
        assert usage["totals"]["device_s"] == pytest.approx(
            sum(u["device_s"] for u in by_handle), abs=1e-4)
        assert _conserves(usage)


def test_dropped_requests_still_billed(lm, reg, rec):
    """A request that dies in the queue is finalized with its queue
    wait billed and zero device-seconds — tenant tables account for
    every submitted request, not just the served ones."""
    with _engine(lm, reg, service_name="usage_drop") as eng:
        h = eng.submit(np.asarray([1, 2, 3], np.int32), 4,
                       tenant="flaky", timeout_s=0.0)
        with pytest.raises(Exception):
            h.result(timeout=120)
        u = h.usage()
        assert u["outcome"] in ("timed_out", "cancelled")
        assert u["device_s"] == 0.0 and u["decode_tokens"] == 0
        # never admitted: its whole life is billed as queue wait
        assert u["queue_wait_s"] is not None and u["queue_wait_s"] >= 0
        agg = eng.stats()["usage"]["tenants"]["flaky"]
        assert agg["requests"] == 1 and agg["finished"] == 0


# --------------------------------------------------------- HTTP route
def test_debug_usage_http_roundtrip(lm, reg, rec):
    r = np.random.RandomState(9)
    with _engine(lm, reg, service_name="usage_http") as eng:
        hs = [eng.submit(r.randint(0, 32, (6,)), 4, tenant=t)
              for t in ("alice", "bob", "alice")]
        for h in hs:
            h.result(timeout=120)
        with obs.start_http_server(host="127.0.0.1", registry=reg,
                                   debug_usage=eng.debug_usage) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            got = json.loads(urllib.request.urlopen(
                f"{base}/debug/usage?n=2").read())
            assert got["service"] == "usage_http"
            assert set(got["tenants"]) == {"alice", "bob"}
            assert got["tenants"]["alice"]["requests"] == 2
            assert len(got["top_requests"]) == 2
            assert got["goodput"]["device_seconds"]["total"] > 0
            assert _conserves(got)
            # the same numbers the in-process summary reports
            assert got["tenants"] == eng.stats()["usage"]["tenants"]
            # the tenant counters ride the same scrape endpoint
            body = urllib.request.urlopen(f"{base}/metrics") \
                .read().decode()
            assert ('bigdl_serving_tenant_requests_total'
                    '{service="usage_http",tenant="alice"} 2') in body
    # no source attached: the route answers with a note, not a 500
    with obs.start_http_server(host="127.0.0.1", registry=reg) as srv:
        got = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/usage").read())
        assert got["tenants"] == {} and "note" in got


# ------------------------------------------------------ lint drift
def _load_lint():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_lint_drift", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "metrics_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_lint_doc_drift_check(tmp_path, capsys):
    """The lint's second check: an instrument registered in
    instruments.py but absent from the docs instrument table fails the
    build; table rows may expand {a,b} alternations and prefix*
    wildcards."""
    lint = _load_lint()
    ins = tmp_path / "bigdl_tpu" / "observability"
    ins.mkdir(parents=True)
    (ins / "instruments.py").write_text(
        'r.counter("bigdl_serving_tenant_requests_total", "x")\n'
        'r.counter("bigdl_serving_tenant_decode_tokens_total", "x")\n'
        'r.gauge("bigdl_widget_spin_rate", "x")\n'
        'r.gauge("bigdl_bench_extra_thing", "x")\n')
    docs = tmp_path / "docs" / "programming-guide"
    docs.mkdir(parents=True)
    doc = docs / "observability.md"
    doc.write_text(
        "| metric | type |\n|---|---|\n"
        "| `bigdl_serving_tenant_{requests,decode_tokens}_total` |"
        " counter |\n"
        "| `bigdl_bench_*` | gauge |\n"
        "prose mention of bigdl_widget_spin_rate does not count\n")
    assert lint.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "bigdl_widget_spin_rate" in out
    assert "bigdl_serving_tenant_requests_total" not in out  # covered
    assert "bigdl_bench_extra_thing" not in out              # wildcard
    # adding the missing row clears the drift
    doc.write_text(doc.read_text()
                   + "| `bigdl_widget_spin_rate` | gauge |\n")
    assert lint.main(["--root", str(tmp_path)]) == 0
    # REVERSE direction: a table row whose instrument was deleted (or
    # renamed) is a ghost — it promises a series no scrape will emit
    doc.write_text(doc.read_text()
                   + "| `bigdl_deleted_thing_total` | counter |\n"
                   + "| `bigdl_ghost_family_*` | gauge |\n")
    assert lint.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "bigdl_deleted_thing_total" in out
    assert "bigdl_ghost_family_*" in out
    assert "ghost doc row" in out
    # restoring the instruments clears it — wildcard rows are satisfied
    # by ANY registered name under the prefix
    (ins / "instruments.py").write_text(
        (ins / "instruments.py").read_text()
        + 'r.counter("bigdl_deleted_thing_total", "x")\n'
        + 'r.gauge("bigdl_ghost_family_width", "x")\n')
    assert lint.main(["--root", str(tmp_path)]) == 0
    # the real tree is clean BOTH directions (the tier-1 wiring in
    # test_resource_observability runs the registration check; this
    # pins the drift sides against HEAD's docs)
    repo = lint.os.path.dirname(lint.os.path.dirname(
        lint.os.path.abspath(lint.__file__)))
    assert lint.doc_drift(repo) == []
    assert lint.reverse_drift(repo) == []
