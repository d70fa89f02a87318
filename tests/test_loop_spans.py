"""The tracer's spans as records on one clock, and the spans the training and
serving loops open: ids and parents, export and self time, the garbage
collection span, and that each loop's iteration is tiled by its children."""

import gc
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn, observability as obs
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.observability import tracing
from bigdl_tpu.optim import Optimizer, SGD, Trigger


# ------------------------------------------------------------- the tracer
def _export(tr, **kw):
    """The tracer's records less any full collection that happened to run
    while the test did (each is a ``host/gc`` span of its own)."""
    return [r for r in tr.export(**kw) if r["name"] != "host/gc"]


def _tree(tr):
    with tr.span("outer", neval=3) as outer:
        with tr.span("a"):
            time.sleep(0.002)
        with tr.span("b", rows=2, request_ids=["r1", "r2"]):
            with tr.span("leaf"):
                time.sleep(0.001)
    return outer


def test_spans_are_records_with_ids_parents_and_one_clock():
    tr = obs.Tracer()
    before = time.time_ns()
    outer = _tree(tr)
    after = time.time_ns()
    recs = _export(tr)
    assert [r["name"] for r in recs] == ["outer", "a", "b", "leaf"]
    by = {r["name"]: r for r in recs}
    assert set(by["outer"]) == {"name", "start_ns", "end_ns", "span_id",
                                "parent_id", "thread", "attrs"}
    ids = [r["span_id"] for r in recs]
    assert len(set(ids)) == 4
    assert by["outer"]["parent_id"] is None
    assert by["a"]["parent_id"] == by["b"]["parent_id"] == \
        by["outer"]["span_id"]
    assert by["leaf"]["parent_id"] == by["b"]["span_id"]
    assert by["outer"]["attrs"] == {"neval": 3} and by["a"]["attrs"] == {}
    assert by["b"]["attrs"] == {"rows": 2, "request_ids": ["r1", "r2"]}
    assert {r["thread"] for r in recs} == {threading.current_thread().name}
    # one clock, time.time_ns(): ends after starts, a child inside its
    # parent, all of it between two readings of that clock
    for r in recs:
        assert before <= r["start_ns"] <= r["end_ns"] <= after
        if r["parent_id"] is not None:
            p = next(q for q in recs if q["span_id"] == r["parent_id"])
            assert p["start_ns"] <= r["start_ns"] and r["end_ns"] <= p["end_ns"]
    # duration is a property of the two stamps; Span.start is gone
    assert outer.duration == (outer.end_ns - outer.start_ns) / 1e9
    assert not hasattr(outer, "start")
    assert not hasattr(tr, "forward_to_jax")


def test_span_ids_are_unique_across_threads_and_tracers():
    a, b = obs.Tracer(), obs.Tracer()
    seen = []

    def work(tr):
        for _ in range(200):
            with tr.span("x") as sp:
                seen.append(sp.span_id)

    ts = [threading.Thread(target=work, args=(tr,)) for tr in (a, b, a, b)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(seen) == len(set(seen)) == 800


@pytest.mark.parametrize("case", ["names", "since", "until", "between"])
def test_export_filters(case):
    tr = obs.Tracer()
    with tr.span("first"):
        time.sleep(0.001)
    cut = time.time_ns()
    time.sleep(0.001)
    with tr.span("second"):
        with tr.span("inner"):
            pass
    end = time.time_ns()
    got = {"names": _export(tr, names=["inner", "first"]),
           "since": _export(tr, since_ns=cut),
           "until": _export(tr, until_ns=cut),
           "between": _export(tr, since_ns=cut, until_ns=end,
                              names={"second"})}[case]
    assert [r["name"] for r in got] == {
        "names": ["first", "inner"], "since": ["second", "inner"],
        "until": ["first"], "between": ["second"]}[case]


def test_self_time_of_a_span_and_of_exported_records():
    tr = obs.Tracer()
    outer = _tree(tr)
    own = tracing.self_ns(_export(tr))
    by = {r["name"]: r for r in _export(tr)}
    dur = lambda n: by[n]["end_ns"] - by[n]["start_ns"]
    assert own[by["outer"]["span_id"]] == dur("outer") - dur("a") - dur("b")
    assert own[by["b"]["span_id"]] == dur("b") - dur("leaf")
    assert own[by["leaf"]["span_id"]] == dur("leaf")
    assert outer.self_ns() == own[outer.span_id] >= 0
    # a filtered export: children that are not among the records cover
    # nothing
    only = tracing.self_ns(tr.export(names=["outer", "a"]))
    assert only[by["outer"]["span_id"]] == dur("outer") - dur("a")


def test_ring_holds_two_minutes_of_both_cells_roots():
    # 120 s of the training cell (an iteration and a batch every 225 ms)
    # and of a serving loop at the 30 ms step the roadmap aims for
    assert tracing.MAX_ROOTS >= 120 * 2 / 0.225
    assert tracing.MAX_ROOTS >= 120 / 0.030
    tr = obs.Tracer()
    gc.disable()        # a full collection would be a root of its own
    try:
        for i in range(tracing.MAX_ROOTS + 10):
            with tr.span("r", i=i):
                pass
    finally:
        gc.enable()
    roots = tr.roots()
    assert len(roots) == tracing.MAX_ROOTS
    assert roots[0].attrs == {"i": 10} and roots[-1].attrs["i"] == \
        tracing.MAX_ROOTS + 9


def test_full_collection_is_a_span_on_the_thread_that_ran_it():
    tr = obs.Tracer()
    with tr.span("work"):
        gc.collect(0)           # young generations are not recorded
        gc.collect(1)
        gc.collect()
    gcs = tr.export(names=["host/gc"])
    assert len(gcs) == 1 and gcs[0]["attrs"] == {"generation": 2}
    work = tr.export(names=["work"])[0]
    assert gcs[0]["parent_id"] == work["span_id"]
    # on a thread with nothing open it is a root of its own
    t = threading.Thread(target=gc.collect, name="collector")
    t.start()
    t.join()
    roots = [r for r in tr.export(names=["host/gc"])
             if r["parent_id"] is None]
    assert [r["thread"] for r in roots] == ["collector"]
    # disable() takes the tracer off the hook
    tr.disable()
    n = len(tr.export(names=["host/gc"]))
    gc.collect()
    assert len(tr.export(names=["host/gc"])) == n
    assert tr not in tracing._GC_TRACERS


def test_gc_hook_follows_the_process_switch():
    assert tracing._on_gc in gc.callbacks        # tracing is on by default
    obs.disable()
    try:
        assert obs.trace not in tracing._GC_TRACERS
    finally:
        obs.enable()
    assert obs.trace in tracing._GC_TRACERS and tracing._on_gc in gc.callbacks


def test_disabled_tracer_times_the_block_and_keeps_no_record():
    reg = obs.MetricRegistry()
    h = reg.histogram("span_seconds", "s")
    tr = obs.Tracer()
    tr.disable()
    with tr.span("outer", histogram=h) as outer:
        with tr.span("inner") as inner:
            time.sleep(0.002)
    # the loops read their seconds from the span whatever the switch says
    assert inner.duration >= 0.002 and outer.duration >= inner.duration
    assert outer.self_ns() == (outer.end_ns - outer.start_ns) - \
        (inner.end_ns - inner.start_ns)
    assert h.get()[2] == 1
    assert tr.roots() == [] and tr.export() == []
    assert tr.open_spans() == [] and tr._live == {}
    tr.enable()
    with tr.span("kept"):
        pass
    assert [r["name"] for r in _export(tr)] == ["kept"]


def test_chrome_trace_reads_the_stamps_and_the_attrs():
    tr = obs.Tracer()
    outer = _tree(tr)
    ev = [e for e in obs.chrome_trace_events(tracer=tr)
          if e.get("cat") == "span"]
    top = next(e for e in ev if e["name"] == "outer")
    assert top["ts"] == outer.start_ns / 1e3
    assert top["dur"] == (outer.end_ns - outer.start_ns) / 1e3
    assert top["args"] == {"neval": 3}


# ------------------------------------------------------ the training loops
class SlowSummary:
    """A train-summary hook that takes some milliseconds, so that an
    iteration of a toy model is long beside the spans' own cost (some
    15 us a span, seven spans an iteration: 1 % of 10 ms)."""

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            time.sleep(0.02)


def _samples(n=64, d=8):
    rng = np.random.RandomState(0)
    return [Sample(rng.randn(d).astype(np.float32),
                   rng.randn(2).astype(np.float32)) for _ in range(n)]


def _assert_iterations_tiled(recs, want):
    its = [r for r in recs if r["name"] == "train/iteration"]
    assert len(its) == want
    assert [r["attrs"]["neval"] for r in its] == list(range(1, want + 1))
    own = tracing.self_ns(recs)
    kids = {}
    for r in recs:
        kids.setdefault(r["parent_id"], []).append(r)
    for it in its:
        names = [c["name"] for c in kids[it["span_id"]]]
        assert names[:4] == ["train/data_wait", "train/arguments",
                             "train/step", "train/bookkeeping"], names
        step = kids[it["span_id"]][2]
        assert kids[step["span_id"]][0]["name"] == "train/dispatch"
    # the children tile the iteration: what no child names is under 1 % of
    # it (the median: under load another thread can take the interpreter
    # between two spans of one iteration)
    unnamed = sorted(own[it["span_id"]] / (it["end_ns"] - it["start_ns"])
                     for it in its)
    assert unnamed[len(unnamed) // 2] <= 0.01, unnamed
    return its, kids


def test_local_optimizer_iterations_are_tiled_by_named_spans():
    opt = Optimizer(model=nn.Sequential(nn.Linear(8, 2)), dataset=_samples(),
                    criterion=nn.MSECriterion(), batch_size=8,
                    end_when=Trigger.max_iteration(6))
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_train_summary(SlowSummary())
    obs.trace.reset()
    opt.optimize()
    recs = _export(obs.trace)
    its, kids = _assert_iterations_tiled(recs, 6)
    for k, it in enumerate(its, 1):
        # one step is kept in flight: the step holds its enqueue alone, and
        # the wait is for the step BEFORE, behind this one's counters and
        # ahead of that step's log line and summary
        step = kids[it["span_id"]][2]
        assert [c["name"] for c in kids[step["span_id"]]] == ["train/dispatch"]
        rest = kids[it["span_id"]][4:]
        assert [c["name"] for c in rest] == \
            ([] if k == 1 else ["train/fence", "train/bookkeeping"])
        if rest:
            assert rest[0]["attrs"] == {"neval": k - 1, "behind": 1}
            assert rest[1]["end_ns"] - rest[1]["start_ns"] >= 20e6  # the hook
    # the last step is fenced and reported on the way out of the loop
    tail = [r for r in recs if r["parent_id"] is None
            and r["start_ns"] >= its[-1]["end_ns"]
            and r["thread"] == its[-1]["thread"]]
    assert [r["name"] for r in tail] == ["train/fence", "train/bookkeeping"]
    assert tail[0]["attrs"] == {"neval": 6, "behind": 0}
    # the producer thread: one root a batch, busy time only
    batches = [r for r in recs if r["name"] == "input/batch"]
    assert len(batches) >= 6
    assert {r["thread"] for r in batches} == {"bigdl-prefetch"}
    assert all(r["parent_id"] is None for r in batches)
    assert {r["thread"] for r in its} != {"bigdl-prefetch"}
    for b in batches[:6]:
        assert [c["name"] for c in kids[b["span_id"]]] == \
            ["input/stack", "input/place"]


def test_distri_optimizer_iterations_carry_the_same_names():
    import jax

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.parallel import DistriOptimizer, Engine

    mesh = Engine.create_mesh([("data", len(jax.devices()))])
    opt = DistriOptimizer(
        model=nn.Sequential(nn.Linear(8, 2)), dataset=DataSet.array(
            _samples()), criterion=nn.MSECriterion(), batch_size=16,
        end_when=Trigger.max_iteration(5), mesh=mesh,
        parameter_sync="sharded", compress_dtype=None)
    opt.set_optim_method(SGD(learning_rate=0.05))
    opt.set_train_summary(SlowSummary())
    obs.trace.reset()
    opt.optimize()
    recs = _export(obs.trace)
    its, kids = _assert_iterations_tiled(recs, 5)
    for it in its:
        # the loss is fetched where this loop logs, inside its bookkeeping
        book = kids[it["span_id"]][3]
        assert [c["name"] for c in kids[book["span_id"]]] == ["train/fence"]
    assert len([r for r in recs if r["name"] == "input/batch"]) >= 5


# -------------------------------------------------------- the serving loop
@pytest.fixture()
def reg():
    r = obs.MetricRegistry()
    prev = obs.set_default_registry(r)
    try:
        yield r
    finally:
        obs.set_default_registry(prev)


@pytest.fixture(scope="module")
def lm():
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(29)
    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=48, use_rope=True)
    m.evaluate()
    return m


PHASE_SPANS = {"sweep": "serving/sweep", "admission": "serving/admission",
               "prefill_dispatch": "serving/prefill_dispatch",
               "decode_dispatch": "serving/decode_dispatch",
               "deliver": "serving/deliver", "observe": "serving/observe"}


def test_engine_iterations_are_tiled_and_feed_the_phase_accumulator(lm, reg):
    from bigdl_tpu.serving import ContinuousBatchingEngine

    obs.trace.reset()
    eng = ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                   registry=reg)
    assert not hasattr(eng, "_iter_disp")
    with eng:
        before = eng.stats()["loop"]
        t_before = time.time_ns()
        r = np.random.RandomState(11)
        hs = [eng.submit(r.randint(0, 32, (4 + i % 5,)), 4)
              for i in range(4)]
        for h in hs:
            h.result(timeout=120)
        time.sleep(0.05)             # the loop is in its idle wait again
        after = eng.stats()["loop"]
    assert set(after) >= {"iterations", "phases", "fractions",
                          "device_idle_fraction", "device_busy_s"}
    assert tuple(after["phases"]) == tuple(PHASE_SPANS)
    recs = obs.trace.export(since_ns=t_before)
    own = tracing.self_ns(recs)
    its = [r for r in recs if r["name"] == "serving/iteration"]
    assert len(its) == after["iterations"] - before["iterations"] > 0
    kids = {}
    for r in recs:
        if r["name"] != "host/gc":
            kids.setdefault(r["parent_id"], []).append(r)
    for it in its:
        assert [c["name"] for c in kids[it["span_id"]]] == [
            "serving/sweep", "serving/admission", "serving/deliver",
            "serving/observe"]
    # children tile the iterations (summed: a toy iteration is short)
    assert sum(own[it["span_id"]] for it in its) <= 0.05 * sum(
        it["end_ns"] - it["start_ns"] for it in its)
    # stats()["loop"] is fed from the spans' closes: the phase seconds ARE
    # the spans' (self time where the dispatches are children)
    for phase, name in PHASE_SPANS.items():
        spans = [r for r in recs if r["name"] == name]
        secs = sum((own[r["span_id"]] if phase in ("admission", "deliver")
                    else r["end_ns"] - r["start_ns"]) for r in spans) / 1e9
        assert after["phases"][phase] - before["phases"][phase] == \
            pytest.approx(secs, abs=2e-5), phase
    pre = [r for r in recs if r["name"] == "serving/prefill_dispatch"]
    assert pre and all(r["attrs"]["rows"] >= 1 and r["attrs"]["tokens"] >= 1
                       and r["attrs"]["request_ids"] for r in pre)
    assert {i for r in pre for i in r["attrs"]["request_ids"]} == \
        {h.request_id for h in hs}
    dec = [r for r in recs if r["name"] == "serving/decode_dispatch"]
    assert dec and all(1 <= r["attrs"]["rows"] <= 2 for r in dec)
    assert all([c["name"] for c in kids[r["span_id"]]] ==
               ["serving/fetch_tokens"] for r in dec)
    # an engine with no work waits under a span of its own, outside the
    # iterations
    idle = [r for r in obs.trace.export(names=["serving/idle_wait"])]
    assert idle and all(r["parent_id"] is None for r in idle)


def test_engine_phases_are_fed_with_tracing_disabled(lm, reg):
    from bigdl_tpu.serving import ContinuousBatchingEngine

    obs.trace.disable()
    try:
        obs.trace.reset()
        with ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                      registry=reg) as eng:
            eng.submit(np.arange(5), 3).result(timeout=120)
            loop = eng.stats()["loop"]
        assert loop["iterations"] > 0
        assert loop["phases"]["decode_dispatch"] > 0
        assert loop["phases"]["deliver"] > 0
        assert obs.trace.export() == []
    finally:
        obs.trace.enable()


def test_crashed_engine_stops_its_sampler_thread(lm, reg, tmp_path):
    from bigdl_tpu.serving import ContinuousBatchingEngine, EngineStopped

    eng = ContinuousBatchingEngine(
        lm, max_slots=2, prefill_chunk=4, registry=reg,
        timeseries_interval_s=0.02,
        postmortem_path=str(tmp_path / "pm.json"))
    eng.start()
    sampler = eng._ts._thread
    assert sampler.is_alive()

    def boom(*a, **k):
        raise RuntimeError("injected decode fault")

    eng._decode_all = boom
    h = eng.submit(np.arange(5), 3)
    with pytest.raises(EngineStopped):
        h.result(timeout=120)
    sampler.join(5)
    # nobody called stop(): the crash path itself stopped the sampler
    assert not sampler.is_alive() and not eng._ts.running
    eng.stop()
