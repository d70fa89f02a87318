"""Fleet-wide distributed tracing (observability/fleettrace.py + the
fleet layer's trace plumbing).

The contracts under test: trace-context propagation (front door mints
or honors a ``trace_id``; it rides ``engine.submit`` into the
recorder so every per-request event carries it); min-RTT clock-offset
estimation recovers a known skew within the RTT bound and re-recovers
after drift; the cross-process trace merge produces one Chrome trace
with per-process tracks, preserved per-request ordering, and no
negative-duration spans; hop decomposition sums to the client-
observed total; the supervisor's wedged-child path (explicit RPC
deadline -> ``rpc_timeout`` drain + counter + probe backoff) and
crash-postmortem collection; and the replica-labeled child-registry
aggregation on ``/metrics``. Everything is in-process / fake-replica
except the final acceptance run: a hermetic 2-worker-process fleet
whose merged trace must carry spans from all three processes."""

import json
import urllib.request

import numpy as np
import pytest

from bigdl_tpu.observability import MetricRegistry
from bigdl_tpu.observability.events import FlightRecorder
from bigdl_tpu.observability.exporters import (
    render_prometheus, render_snapshot_prometheus,
)
from bigdl_tpu.observability.fleettrace import (
    FLEET_HOPS, estimate_clock_offset, hop_breakdown,
    merge_fleet_trace, merge_request_timelines, mint_trace_id,
    parse_traceparent,
)
from bigdl_tpu.observability.postmortem import registry_snapshot
from bigdl_tpu.serving import ContinuousBatchingEngine
from bigdl_tpu.serving.fleet import (
    FleetFrontDoor, InProcessReplica, ReplicaSupervisor,
    WorkerRPCTimeout,
)

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(23)
    m = TransformerLM(VOCAB, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


# ------------------------------------------------------- trace context
def test_parse_traceparent_and_mint():
    tid = "ab" * 16
    assert parse_traceparent(f"00-{tid}-{'cd' * 8}-01") == tid
    assert parse_traceparent(tid) == tid          # bare 32-hex
    assert parse_traceparent(None) is None
    assert parse_traceparent("") is None
    assert parse_traceparent("not-a-header") is None
    assert parse_traceparent(f"00-{'0' * 32}-{'cd' * 8}-01") is None
    assert parse_traceparent(tid.upper()) == tid   # normalized
    minted = mint_trace_id()
    assert len(minted) == 32 and int(minted, 16) >= 0
    assert mint_trace_id() != minted


def test_recorder_context_and_request_binding():
    rec = FlightRecorder(capacity=64)
    rec.set_context(replica="r7")
    rec.bind_request("req-1", trace="t-abc")
    rec.record("request/submitted", "req-1")
    rec.record("request/submitted", "req-2")       # unbound request
    rec.record("other", None, replica="explicit")  # explicit attr wins
    evs = rec.snapshot()
    by_kind = {e["kind"]: e for e in evs}
    e1 = [e for e in evs if e.get("request_id") == "req-1"][0]
    assert e1["replica"] == "r7" and e1["trace"] == "t-abc"
    e2 = [e for e in evs if e.get("request_id") == "req-2"][0]
    assert e2["replica"] == "r7" and "trace" not in e2
    assert by_kind["other"]["replica"] == "explicit"
    # bindings are bounded: flooding evicts the oldest first
    for i in range(rec.capacity + 5):
        rec.bind_request(f"flood-{i}", trace=str(i))
    assert rec.request_context("req-1") == {}
    assert rec.request_context(f"flood-{rec.capacity + 4}") != {}


def test_engine_submit_binds_trace_to_events(lm):
    tid = mint_trace_id()
    with ContinuousBatchingEngine(lm, max_slots=1,
                                  prefill_chunk=4) as eng:
        h = eng.submit(np.asarray([1, 2, 3]), 4, trace_id=tid)
        h.result(timeout=60)
        assert h.trace_id == tid
        evs = eng._rec.for_request(h.request_id)
        assert evs, "engine recorded nothing for the request"
        assert any(e.attrs.get("trace") == tid for e in evs)
        kinds = [e.kind for e in evs if e.attrs.get("trace") == tid]
        assert "request/submitted" in kinds


# ---------------------------------------------------- hop decomposition
def test_hop_breakdown_sums_to_total_exactly():
    tl = {"queue_wait_s": 0.010, "prefill_s": 0.020,
          "decode_s": 0.050, "client_ttft_s": 0.040}
    hops = hop_breakdown(tl, route_s=0.001, rpc_submit_s=0.002,
                         total_s=0.100)
    assert set(hops) == set(FLEET_HOPS)
    assert all(v >= 0.0 for v in hops.values())
    assert sum(hops.values()) == pytest.approx(0.100, abs=1e-12)
    # first_token is the TTFT not explained by submit+queue+prefill
    assert hops["first_token"] == pytest.approx(0.008, abs=1e-12)


def test_hop_breakdown_scales_engine_phases_into_budget():
    # replica-clock phases overrun the client window (pipe jitter on
    # a short request): they are scaled, never summed past total
    tl = {"queue_wait_s": 0.02, "prefill_s": 0.03, "decode_s": 0.06,
          "client_ttft_s": 0.012}
    hops = hop_breakdown(tl, route_s=0.001, rpc_submit_s=0.001,
                         total_s=0.050)
    assert sum(hops.values()) == pytest.approx(0.050, abs=1e-12)
    assert all(v >= 0.0 for v in hops.values())
    # proportions of the engine phases are preserved by the scaling
    assert hops["decode"] == pytest.approx(2 * hops["prefill"],
                                           rel=1e-6)


def test_hop_breakdown_in_process_fallback():
    # no client_ttft_s: the engine clock IS the client clock
    tl = {"queue_wait_s": 0.01, "prefill_s": 0.02, "decode_s": 0.03}
    hops = hop_breakdown(tl, route_s=0.0005, rpc_submit_s=0.0005,
                         total_s=0.070)
    assert hops["first_token"] == 0.0
    assert sum(hops.values()) == pytest.approx(0.070, abs=1e-12)


# ------------------------------------------------------ clock alignment
class _FakeClocks:
    """Deterministic supervisor/worker clock pair: the worker runs
    ``skew`` seconds ahead, pings cost ``rtt`` round trip."""

    def __init__(self, skew, rtt=0.001, jitter=0.0):
        self.t = 100.0
        self.skew = skew
        self.rtt = rtt
        self.jitter = jitter
        self.n = 0

    def local(self):
        self.t += 1e-6
        return self.t

    def ping(self):
        self.n += 1
        extra = self.jitter * (self.n % 3)   # asymmetric noise
        self.t += (self.rtt + extra) / 2
        remote = self.t + self.skew
        self.t += (self.rtt + extra) / 2
        return remote


@pytest.mark.parametrize("skew", [3.75, -0.5, 0.0])
def test_estimate_clock_offset_recovers_skew(skew):
    clk = _FakeClocks(skew, rtt=0.002, jitter=0.004)
    offset, rtt = estimate_clock_offset(clk.ping, samples=8,
                                        clock=clk.local)
    # remote + offset lands on the local timeline: offset == -skew,
    # within the min-RTT half-width error bound
    assert offset == pytest.approx(-skew, abs=rtt / 2 + 1e-6)
    assert rtt >= 0.002 - 1e-9


def test_estimate_clock_offset_tracks_drift_on_refresh():
    clk = _FakeClocks(1.0, rtt=0.002)
    off1, _ = estimate_clock_offset(clk.ping, samples=4,
                                    clock=clk.local)
    clk.skew = 1.5                      # the worker's clock drifted
    off2, rtt2 = estimate_clock_offset(clk.ping, samples=4,
                                       clock=clk.local)
    assert off1 == pytest.approx(-1.0, abs=0.002)
    assert off2 == pytest.approx(-1.5, abs=rtt2 / 2 + 1e-6)


# ---------------------------------------------------------- trace merge
def _export(process, offset, reqs, pid=None):
    """Synthetic per-process export: full lifecycle per request on
    this process's own (skewed) clock."""
    evs = []
    seq = 0
    for rid, trace, t0 in reqs:
        for kind, dt in (("request/submitted", 0.0),
                         ("request/admitted", 0.010),
                         ("request/first_token", 0.030),
                         ("request/finished", 0.070)):
            seq += 1
            evs.append({"seq": seq, "ts_s": t0 + dt - offset,
                        "thread": "engine", "kind": kind,
                        "request_id": rid, "trace": trace})
    ex = {"process": process, "clock_offset_s": offset, "events": evs}
    if pid is not None:
        ex["pid"] = pid
    return ex


def test_merge_fleet_trace_invariants():
    exports = [
        _export("front-door", 0.0,
                [("req-A", "t-aa", 1.000),
                 ("req-B", "t-bb", 1.050)], pid=10),
        _export("r0", +2.5, [("req-000001", "t-aa", 1.001)], pid=20),
        _export("r1", -1.25, [("req-000001", "t-bb", 1.051)], pid=30),
    ]
    evs = merge_fleet_trace(exports, wall_offset=50.0)
    procs = {e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert procs == {"front-door", "r0", "r1"}
    assert not any(e.get("ph") == "X" and e["dur"] < 0 for e in evs)
    # alignment: every instant lands on the common timeline near the
    # reference-side submit stamps (1.0s + 50s wall anchor), despite
    # per-process skews of +2.5 / -1.25 seconds
    instants = [e for e in evs if e.get("ph") == "i"]
    assert instants
    for e in instants:
        assert 50.9e6 < e["ts"] < 51.3e6
    # per-request event order survives alignment in every process
    reqs = {(e["pid"], e["args"]["request_id"]) for e in instants}
    for pid, rid in reqs:
        mine = [e["ts"] for e in instants if e["pid"] == pid
                and e["args"]["request_id"] == rid]
        assert mine == sorted(mine) and len(mine) == 4
    # derived spans: one request envelope + queue/prefill/decode
    # phases per (process, request)
    envelopes = [e for e in evs if e.get("cat") == "request"]
    assert len(envelopes) == 4
    phases = {e["name"].split()[0] for e in evs
              if e.get("cat") == "phase"}
    assert phases == {"queue", "prefill", "decode"}


def test_merge_request_timelines_keys_by_trace():
    # both replicas minted "req-000001" — only the trace id is
    # fleet-unique, so the per-request join must key on it
    exports = [
        _export("front-door", 0.0, [("req-000001", "t-aa", 1.0),
                                    ("req-000001", "t-bb", 1.1)]),
        _export("r0", 0.0, [("req-000001", "t-aa", 1.0)]),
        _export("r1", 0.0, [("req-000001", "t-bb", 1.1)]),
    ]
    tls = merge_request_timelines(exports)
    assert set(tls) == {"t-aa", "t-bb"}
    assert set(tls["t-aa"]["processes"]) == {"front-door", "r0"}
    assert set(tls["t-bb"]["processes"]) == {"front-door", "r1"}
    for tl in tls.values():
        for p in tl["processes"].values():
            assert p["first_ts_s"] <= p["last_ts_s"]
            assert p["kinds"][0] == "request/submitted"


# ------------------------------------------- replica-labeled /metrics
def test_render_snapshot_prometheus_labels_every_series():
    reg = MetricRegistry()
    reg.counter("bigdl_serving_requests_total", "requests",
                labelnames=("service",)).labels("svc").inc(3)
    reg.histogram("bigdl_serving_ttft_seconds", "ttft",
                  buckets=(0.1, 1.0)).observe(0.05)
    snap = registry_snapshot(reg)
    text = render_snapshot_prometheus({"r0": snap, "r1": snap})
    assert text.count("# HELP bigdl_serving_requests_total") == 1
    assert ('bigdl_serving_requests_total{replica="r0",'
            'service="svc"} 3') in text
    assert ('bigdl_serving_requests_total{replica="r1",'
            'service="svc"} 3') in text
    assert 'le="0.1"' in text and 'le="+Inf"' in text
    assert 'bigdl_serving_ttft_seconds_count{replica="r0"} 1' in text


# --------------------------------------- wedged RPC + postmortem paths
class FakeReplica:
    def __init__(self, rid, status="ok"):
        self.id = rid
        self.status = status      # str, or an Exception to raise
        self.calls = []

    def healthz(self):
        if isinstance(self.status, Exception):
            raise self.status
        return {"status": self.status, "alerts": [], "draining": False,
                "queue_depth": 0, "active_slots": 0}

    def stats(self):
        return {"finished": 0}

    def drain(self):
        self.calls.append("drain")

    def resume(self):
        self.calls.append("resume")

    def start(self):
        self.calls.append("start")

    def stop(self):
        self.calls.append("stop")


def test_wedged_replica_drains_with_counter_and_backoff():
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=64)
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    sup = ReplicaSupervisor([r0, r1], poll_interval=999.0,
                            registry=reg, recorder=rec, chunk=4)
    with sup:
        r0.status = WorkerRPCTimeout("healthz deadline (10.0s)")
        res = sup.poll_once()
        assert res["r0"]["status"] == "wedged"
        assert sup.healthz()["drain_reasons"] == {"r0": "rpc_timeout"}
        assert "drain" in r0.calls
        text = render_prometheus(reg)
        assert ('bigdl_fleet_rpc_timeouts_total{fleet="fleet",'
                'replica="r0"} 1') in text
        # backoff: the wedged child is NOT re-probed next sweep (each
        # probe would block a full rpc_timeout)
        r0.status = Exception("must not be probed")
        assert sup.poll_once()["r0"] == {"status": "wedged",
                                        "backoff": True}
        # recovery: once the backoff lapses, a clean probe rejoins
        r0.status = "ok"
        sup._wedged_until["r0"] = 0.0
        sup.poll_once()
        assert sup.healthz()["status"] == "ok"
        assert "resume" in r0.calls


def test_crash_drain_collects_postmortem(tmp_path):
    pm_path = tmp_path / "r0_postmortem.json"
    pm_path.write_text(json.dumps({
        "schema": "bigdl_postmortem/1",
        "error": {"type": "Boom", "message": "loop crashed"},
        "events": [{"kind": "x"}] * 3,
        "requests": [{"request_id": "req-000001"}],
    }))
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=64)
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    r0.postmortem_path = str(pm_path)
    sup = ReplicaSupervisor([r0, r1], poll_interval=999.0,
                            registry=reg, recorder=rec, chunk=4)
    with sup:
        r0.status = RuntimeError("dead pipe")
        sup.poll_once()
        st = sup.stats()
        pm = st["postmortems"]["r0"]
        assert pm["path"] == str(pm_path)
        assert pm["error"]["type"] == "Boom"
        assert pm["events"] == 3 and pm["requests"] == 1
        drains = [e for e in rec.tail() if e.kind == "fleet/drain"]
        assert drains and drains[-1].attrs["postmortem"] == str(pm_path)
        assert drains[-1].attrs["postmortem_error"] == "Boom"


# ------------------------------------------------ front door, in-process
def _post(url, body, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=60)


def test_frontdoor_trace_roundtrip_and_hop_reconciliation(lm):
    reg = MetricRegistry()
    reps = [InProcessReplica(
        f"r{i}", ContinuousBatchingEngine(lm, max_slots=2,
                                          prefill_chunk=4))
        for i in range(2)]
    sent = mint_trace_id()
    with ReplicaSupervisor(reps, registry=reg, chunk=4,
                           poll_interval=999.0) as sup, \
            FleetFrontDoor(sup, registry=reg) as door:
        base = f"http://{door.host}:{door.port}"
        r = _post(base + "/v1/generate",
                  {"prompt_ids": [1, 2, 3, 4], "max_new_tokens": 6,
                   "stream": False},
                  headers={"traceparent":
                           f"00-{sent}-{'cd' * 8}-01"})
        assert r.headers["X-Trace-Id"] == sent
        out = json.loads(r.read())
        assert out["trace_id"] == sent
        assert r.headers["X-Request-Id"] == out["request_id"]
        assert set(out["hops"]) == set(FLEET_HOPS)
        hop_sum = sum(out["hops"].values())
        assert abs(hop_sum - out["total_s"]) <= 0.10 * out["total_s"]
        # a request WITHOUT traceparent gets a minted id
        r2 = _post(base + "/v1/generate",
                   {"prompt_ids": [2, 3, 4], "max_new_tokens": 4,
                    "stream": False})
        assert len(r2.headers["X-Trace-Id"]) == 32
        assert r2.headers["X-Trace-Id"] != sent
        # the merged trace serves, spans are sane, the request ring
        # and hop histograms reflect both requests
        tr = json.loads(urllib.request.urlopen(
            base + "/debug/fleet/trace", timeout=30).read())
        evs = tr["traceEvents"]
        assert {e["args"]["name"] for e in evs
                if e.get("name") == "process_name"} == {"front-door"}
        assert not any(e.get("ph") == "X" and e["dur"] < 0
                       for e in evs)
        assert any(e.get("args", {}).get("trace") == sent
                   for e in evs)
        fr = json.loads(urllib.request.urlopen(
            base + "/debug/fleet/requests", timeout=30).read())
        assert len(fr["requests"]) == 2
        assert {e["trace_id"] for e in fr["requests"]} >= {sent}
        assert all(abs(e["hop_sum_s"] - e["total_s"])
                   <= 0.10 * e["total_s"] + 1e-6
                   for e in fr["requests"])
        text = urllib.request.urlopen(
            base + "/metrics", timeout=30).read().decode()
        assert 'bigdl_fleet_hop_seconds_bucket' in text
        assert 'hop="prefill"' in text


# ----------------------------------------- multi-process acceptance run
def test_two_worker_fleet_merged_trace_end_to_end():
    """The ISSUE's acceptance run: a hermetic 2-replica worker fleet
    produces ONE merged Chrome trace with spans from the front door
    AND both worker processes, aligned (no negative durations), and
    every finished request's hops sum to the client total within
    10%."""
    from bigdl_tpu.serving.fleet import spawn_worker_fleet

    model = dict(vocab_size=64, embed_dim=16, num_heads=4,
                 num_kv_heads=2, num_layers=2, max_len=96,
                 use_rope=True)
    # a worker takes the platform it is given: this CPU suite says so
    reps = spawn_worker_fleet(
        2, model, engine={"max_slots": 2, "prefill_chunk": 4}, seed=7,
        env={"JAX_PLATFORMS": "cpu"})
    reg = MetricRegistry()
    with ReplicaSupervisor(reps, poll_interval=0.1,
                           registry=reg) as sup, \
            FleetFrontDoor(sup, registry=reg) as door:
        base = f"http://{door.host}:{door.port}"
        for rep in reps:
            assert rep.clock_offset_s is not None
            assert rep.clock_rtt_s >= 0.0
            # each worker reports the device it came up on
            assert rep.device == {"platform": "cpu", "kind": "cpu"}
        outs = [json.loads(_post(
            base + "/v1/generate",
            {"prompt_ids": [1 + i, 2, 3, 4], "max_new_tokens": 6,
             "stream": False}).read()) for i in range(4)]
        assert {o["replica"] for o in outs} == {"r0", "r1"}
        for o in outs:
            s = sum(o["hops"].values())
            assert abs(s - o["total_s"]) <= 0.10 * o["total_s"]
        tr = json.loads(urllib.request.urlopen(
            base + "/debug/fleet/trace", timeout=60).read())
        evs = tr["traceEvents"]
        procs = {e["args"]["name"] for e in evs
                 if e.get("name") == "process_name"}
        assert procs == {"front-door", "r0", "r1"}
        assert not any(e.get("ph") == "X" and e["dur"] < 0
                       for e in evs)
        fr = json.loads(urllib.request.urlopen(
            base + "/debug/fleet/requests", timeout=60).read())
        multi = [t for t in fr["timelines"].values()
                 if len(t["processes"]) >= 2]
        assert len(multi) >= 4       # every request, in both procs
        text = urllib.request.urlopen(
            base + "/metrics", timeout=60).read().decode()
        assert 'replica="r0"' in text and 'replica="r1"' in text
        assert "bigdl_fleet_clock_offset_seconds" in text


def test_worker_fleet_on_an_accelerator_without_a_chip_each_is_refused():
    """N worker processes on one accelerator would contend for it: the
    supervisor stops at the first worker that reports a non-CPU device
    and names the ROADMAP item that builds per-chip pinning."""
    class Worker:
        def __init__(self, rid):
            self.id, self.device = rid, None
            self.started = self.stopped = False

        def start(self):
            self.started = True
            self.device = {"platform": "tpu", "kind": "TPU v5 lite"}

        def stop(self):
            self.stopped = True

    reps = [Worker("r0"), Worker("r1")]
    sup = ReplicaSupervisor(reps, registry=MetricRegistry())
    with pytest.raises(RuntimeError, match="Reach 7"):
        sup.start()
    assert reps[0].started and reps[0].stopped
    assert not reps[1].started      # never brought up to fight for the chip
