"""The span readers of the benchmark, checked on the CPU: the clock arithmetic
on a real capture's ``Task Environment`` plane, ``named_gaps`` and
``clock_check`` on a hand-built trace beside hand-built spans, each new
per-layer metric on hand-worked numbers, and the names of the jitted programs
the trace readers look for."""

import glob
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, program_spans, reduce_trace  # noqa: E402

MS = 1_000_000
T0 = 1_790_000_000 * 1_000_000_000      # a capture's start, Unix ns


def span(name, start_ms, end_ms, sid, parent=None, thread="MainThread",
         **attrs):
    return {"name": name, "start_ns": T0 + round(start_ms * MS),
            "end_ns": T0 + round(end_ms * MS), "span_id": sid,
            "parent_id": parent, "thread": thread, "attrs": attrs}


def iteration(k, t, wait=0.2, args=1.0, dispatch=1.0, fence=218.0, book=4.0,
              tail=0.0):
    """One ``train/iteration`` tree starting at ``t`` ms; ids from 10 * k.
    ``tail`` is time at the iteration's end that no child names."""
    a = t + wait + args
    b = a + dispatch
    c = b + fence
    d = c + book
    return [span("train/iteration", t, d + tail, 10 * k, neval=k),
            span("train/data_wait", t, t + wait, 10 * k + 1, 10 * k),
            span("train/arguments", t + wait, a, 10 * k + 9, 10 * k),
            span("train/step", a, c, 10 * k + 2, 10 * k),
            span("train/dispatch", a, b, 10 * k + 3, 10 * k + 2),
            span("train/fence", b, c, 10 * k + 4, 10 * k + 2),
            span("train/bookkeeping", c, d, 10 * k + 5, 10 * k)]


def training_spans(n=6, period=225.0, **kw):
    """``n`` iterations back to back, a producer batch beside each, and one
    full collection of 90 ms inside the third iteration's bookkeeping."""
    out = []
    for k in range(1, n + 1):
        t = (k - 1) * period
        out += iteration(k, t, **kw)
        out += [span("input/batch", t + 5, t + 175, 10 * k + 6,
                     thread="bigdl-prefetch"),
                span("input/stack", t + 5, t + 115, 10 * k + 7, 10 * k + 6,
                     thread="bigdl-prefetch"),
                span("input/place", t + 115, t + 175, 10 * k + 8, 10 * k + 6,
                     thread="bigdl-prefetch")]
    return sorted(out, key=lambda r: (r["start_ns"], r["span_id"]))


def step_events(n=6, period=225.0, start=1.5, length=217.0):
    """The step program's runs and its operations (two a step, a 1 ms gap
    between them), in nanoseconds from the capture's start."""
    mods, ops = [], []
    for k in range(n):
        s = (k * period + start) * MS
        mods.append(("jit__core(123)", s, length * MS))
        ops.append(("%fusion.1 = f32[8]{0} fusion(%p)", s, 100 * MS))
        ops.append(("%fusion.2 = f32[8]{0} fusion(%p)", s + 101 * MS,
                    (length - 101) * MS))
    return mods, ops


@pytest.fixture
def hand_built(monkeypatch):
    """Hand-built spans in place of the process's tracer, a hand-built
    capture in place of the newest .xplane.pb."""
    def install(spans, capture=None):
        monkeypatch.setattr(program_spans, "program_spans",
                            lambda: spans)
        monkeypatch.setattr(program_spans, "traced", lambda: capture)

    return install


def capture_of(mods, ops, marker=None):
    return {"start_ns": T0, "modules": mods, "ops": ops,
            "window": marker or reduce_trace.steady_window(mods)}


def read(name, run, trace=None):
    return harness.load_module("metrics", name).value(run, trace)


# --------------------------------------------------------------- the clock
def test_a_span_sits_on_a_real_captures_clock(tmp_path, monkeypatch):
    """``profile_start_time`` of a CPU-backend capture's Task Environment
    plane plus an annotation's ``start_ns`` is ``time.time_ns()`` taken
    inside that annotation: the arithmetic every span reader rests on."""
    import jax

    from bigdl_tpu.observability import Tracer

    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    out = tmp_path / ".bench_trace" / "cell"
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.MARKER):
            with tr.span("inside") as sp:
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert program_spans.newest_xplane() == glob.glob(
        str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    cap = program_spans.traced()
    assert cap["modules"] == [] and cap["ops"] == []   # no TPU plane here
    lo, hi = cap["window"]                              # the marker's ends
    (rel,) = program_spans.since([sp.record()], cap["start_ns"])
    # the span opened inside the annotation, within a millisecond of it
    assert 0 <= rel["start_ns"] - lo < 1 * MS
    assert 0 <= hi - rel["end_ns"] < 1 * MS
    assert hi - lo >= 20 * MS
    # the capture's own span is seen on the same clock, under the name the
    # program gave it (host level 1 records TraceAnnotations)
    planes = reduce_trace.read_xplane(program_spans.newest_xplane())
    seen = [(s, d) for ev in planes["/host:CPU"].values()
            for n, s, d in ev if n == "inside"]
    assert len(seen) == 1
    assert abs(seen[0][0] - rel["start_ns"]) < 1 * MS
    # read once: the second call hands back the same object
    assert program_spans.traced() is cap


def test_no_capture_and_no_export_give_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert program_spans.newest_xplane() is None
    assert program_spans.traced() is None
    # a program from before the tracer could hand its spans out
    import bigdl_tpu.observability as obs

    class Old:
        pass

    monkeypatch.setattr(obs, "trace", Old())
    assert program_spans.program_spans() is None
    assert program_spans.training({"iteration_ms": [225.0]}, None) is None
    assert program_spans.serving({}) is None


# ---------------------------------------------------------------- the gaps
def test_named_gaps_on_a_hand_built_trace():
    spans = program_spans.since(
        program_spans.loop_thread_spans(training_spans(3), "train/iteration"),
        T0)
    assert {r["thread"] for r in spans} == {"MainThread"}
    mods, ops = step_events(3)
    lo, hi = reduce_trace.steady_window(mods)     # first to last step start
    assert (lo, hi) == (1.5 * MS, 451.5 * MS)
    table, named = program_spans.named_gaps(ops, spans, lo, hi)
    # per period: 1 ms between the two operations (under train/fence), then
    # 8 ms from the step's end (t+218.5) to the next step's start (t+226.5)
    # whose middle (t+222.5) lies in train/bookkeeping (t+220.2 .. t+224.2)
    assert table == pytest.approx({"train/bookkeeping": 2 * 0.008,
                                   "train/fence": 2 * 0.001})
    assert list(table) == ["train/bookkeeping", "train/fence"]
    assert named == pytest.approx(100.0)
    # without the bookkeeping spans the innermost cover is the iteration
    table, named = program_spans.named_gaps(
        ops, [r for r in spans if r["name"] != "train/bookkeeping"], lo, hi)
    assert table["train/iteration"] == pytest.approx(0.016)
    # without spans every gap is unattributed, none of it named
    table, named = program_spans.named_gaps(ops, [], lo, hi)
    assert table == pytest.approx({"unattributed": 0.018}) and named == 0.0
    # the window's edges count: a marker window that opens before the first
    # operation and closes after the last
    idle = program_spans.idle_intervals(ops, 0.0, 700 * MS)
    assert idle[0] == (0.0, 1.5 * MS) and idle[-1] == (668.5 * MS, 700 * MS)
    # a device that never idled
    assert program_spans.named_gaps(ops[:1], spans, 2 * MS, 50 * MS) == \
        ({}, None)


def test_clock_check_on_a_hand_built_trace():
    spans = program_spans.since(training_spans(6), T0)
    mods, _ = step_events(6)       # the first and the last run are left out
    got = program_spans.clock_check(mods, spans, ["jit__core", "jit_step"])
    # a step starts 1.5 ms into its iteration: 0.3 after its dispatch began
    # (data_wait 0.2, arguments 1.0), and ends at 218.5, 1.7 before the
    # fence's end (220.2): one clock, with 0 inside the interval
    assert got["steps"] == 4 and got["outside"] == 0
    assert got["start_after_dispatch_start_ms"] == pytest.approx([0.3] * 3)
    assert got["end_before_fence_end_ms"] == pytest.approx([1.7] * 3)
    assert got["device_clock_early_ms"] == pytest.approx([0.0, 1.7])
    # a device clock 1 ms early: every step leads its dispatch by the same
    # 0.7 ms, which is how another base shows, and by how much at least
    early = [(n, s - 1 * MS, d) for n, s, d in mods]
    got = program_spans.clock_check(early, spans, ["jit__core"])
    assert got["steps"] == 4 and got["outside"] == 4
    assert got["start_after_dispatch_start_ms"] == pytest.approx([-0.7] * 3)
    assert got["device_clock_early_ms"] == pytest.approx([0.7, 2.7])
    # DistriOptimizer's fence is a child of the iteration's bookkeeping
    moved = [dict(r, parent_id=r["parent_id"] + 3) if r["name"] ==
             "train/fence" else r for r in spans]
    assert program_spans.clock_check(mods, moved, ["jit__core"]) == \
        program_spans.clock_check(mods, spans, ["jit__core"])
    # other programs are not steps
    assert program_spans.clock_check(mods, spans, ["jit_other"]) is None


# ------------------------------------------------------- the new metrics
def test_training_metrics_on_hand_built_spans(hand_built):
    spans = training_spans(6, tail=0.5)
    # a 90 ms full collection inside the third iteration's bookkeeping,
    # which it lengthens... here only marked: a child of that span
    spans.append(span("host/gc", 2 * 225.0 + 221.0, 2 * 225.0 + 223.5, 999,
                      35, generation=2))
    mods, ops = step_events(6)
    hand_built(spans, capture_of(mods, ops))
    summary = {"programs": {"jit__core": {"runs": 6, "median_ms": 217.0,
                                          "total_ms": 6 * 217.0}}}
    # the window holds the newest 4 iterations (3..6)
    programs = {"train_step": ["jit__core", "jit_step"]}
    run = {"programs": programs, "iteration_ms": [225.0] * 4}
    assert read("train_data_wait_ms", run, summary) == pytest.approx(0.2)
    assert read("train_arguments_ms", run, summary) == pytest.approx(1.0)
    assert read("train_dispatch_ms", run, summary) == pytest.approx(1.0)
    # bookkeeping 4.0 plus the 0.5 ms of the iteration no child names
    assert read("train_bookkeeping_ms", run, summary) == pytest.approx(4.5)
    assert read("train_gc_pause_max_ms", run, summary) == pytest.approx(2.5)
    assert read("idle_named_pct.train", run, summary) == pytest.approx(100.0)
    t = program_spans.training(run, summary)
    assert [r["attrs"]["neval"] for r in t["iterations"]] == [3, 4, 5, 6]
    assert list(t["gaps"])[0] == "train/bookkeeping"
    # one computation a run: every reader was handed the same object
    assert program_spans.training(run, summary) is t
    # a window without a full collection reads 0, not nothing
    run2 = {"programs": programs, "iteration_ms": [225.0] * 2}
    assert read("train_gc_pause_max_ms", run2, summary) == 0.0
    # no capture (a run on a program that has spans but left no trace):
    # the span medians are there, the share of named idle time is not
    hand_built(spans, None)
    run3 = {"programs": programs, "iteration_ms": [225.0] * 4}
    assert read("train_dispatch_ms", run3, summary) == pytest.approx(1.0)
    assert read("idle_named_pct.train", run3, summary) is None


def serving_iteration(k, t, deliver_own=0.6, observe=0.4, step=170.0):
    """One ``serving/iteration`` tree starting at ``t`` ms; ids from 10 * k."""
    i = 10 * k
    a = t + 0.05                       # sweep
    b = a + 0.1                        # admission, no prefill this turn
    c = b + deliver_own / 2            # deliver: before the dispatch
    d = c + step                       # the dispatch
    e = d + deliver_own / 2            # deliver: the streams
    f = e + observe
    return [span("serving/iteration", t, f, i, thread="serving-engine"),
            span("serving/sweep", t, a, i + 1, i, "serving-engine"),
            span("serving/admission", a, b, i + 2, i, "serving-engine"),
            span("serving/deliver", b, e, i + 3, i, "serving-engine"),
            span("serving/decode_dispatch", c, d, i + 4, i + 3,
                 "serving-engine", rows=32),
            span("serving/fetch_tokens", c + 1, d, i + 5, i + 4,
                 "serving-engine"),
            span("serving/observe", e, f, i + 6, i, "serving-engine")]


def test_serving_metrics_and_closure_on_hand_built_spans(hand_built, capfd):
    period = 0.05 + 0.1 + 0.6 + 170.0 + 0.4
    spans = [r for k in range(1, 9)
             for r in serving_iteration(k, (k - 1) * period)]
    # the device: one operation a step, from the fetch's start (1 ms into
    # the dispatch, 1.45 into the turn) to its end; idle for the 2.15 ms
    # between. The marker window holds iterations 3..6 whole
    ops = [("%fusion.1 = bf16[8]{0} fusion(%p)",
            ((k - 1) * period + 1.45) * MS, 169.0 * MS) for k in range(1, 9)]
    marker = ((2 * period - 0.01) * MS, (6 * period + 0.01) * MS)
    hand_built(spans, capture_of([], ops, marker))
    phases = lambda n: {"sweep": 0.05e-3 * n, "admission": 0.1e-3 * n,
                        "prefill_dispatch": 0.0,
                        "decode_dispatch": 0.170 * n, "deliver": 0.6e-3 * n,
                        "observe": 0.4e-3 * n}
    run = {"programs": {},
           "loop_before": {"iterations": 2, "phases": phases(2)},
           "loop_after": {"iterations": 7, "phases": phases(7)}}
    assert read("loop_deliver_ms", run) == pytest.approx(0.6, abs=1e-5)
    assert read("loop_observe_ms", run) == pytest.approx(0.4, abs=1e-5)
    t = program_spans.serving(run)
    assert len([r for r in t["inside"]
                if r["name"] == "serving/iteration"]) == 4
    # the middle of a whole idle interval (1.075 ms after the dispatch's
    # end) lies in the next turn's deliver, before its dispatch; the window's
    # two edges cut intervals whose middles lie in a dispatch (before the
    # fetch) and in an observe. Never the bare iteration
    assert t["gaps"] == pytest.approx({
        "serving/deliver": 3 * 2.15e-3, "serving/decode_dispatch": 1.46e-3,
        "serving/observe": 0.71e-3}, abs=1e-8)
    assert list(t["gaps"])[0] == "serving/deliver"
    assert t["named_pct"] == pytest.approx(100.0)
    # the logged table closes against the two stats()["loop"] readings
    import json

    line = next(ln for ln in capfd.readouterr().err.splitlines()
                if ln.startswith("[spans] "))
    table = json.loads(line[len("[spans] "):])
    assert table["iterations_in_trace"] == 4
    for phase, (got, want) in table["closure_s"].items():
        assert got == pytest.approx(want, abs=1e-7), phase
    assert table["closure_s"]["decode_dispatch"][0] == pytest.approx(0.85)


@pytest.mark.parametrize("name", [
    "train_data_wait_ms", "train_arguments_ms", "train_dispatch_ms",
    "train_bookkeeping_ms", "train_gc_pause_max_ms", "idle_named_pct.train",
    "loop_deliver_ms", "loop_observe_ms"])
def test_new_metric_is_in_the_manifest_and_reads_nothing_from_nothing(
        name, hand_built):
    entry = {m["name"]: m for m in harness.manifest()["per_layer"]}[name]
    assert entry["source"] == "program_span"
    assert ("gpt2l-chat-steady" if name.startswith("loop_")
            else "resnet50-local-b256") in entry["workloads"]
    assert entry["moves"] == ("itl_p95_ms" if name.startswith("loop_")
                              else "train_samples_per_s")
    # a program that hands out no spans (the parent of PR 26): no value
    hand_built(None, None)
    run = {"programs": {"train_step": ["jit__core"]},
           "iteration_ms": [225.0] * 3, "loop_before": None}
    assert read(name, run, {"programs": {}}) is None


# ------------------------------------------------------ the programs' names
def test_jitted_programs_carry_the_names_the_configs_look_for(monkeypatch):
    """``decode_step_ms``, ``prefill_chunk_ms`` and ``train_step_ms`` find
    their program in the trace by the jit's name: a rename must fail here,
    not turn them into ``null`` on the chip."""
    import jax
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.optimizer import make_train_step
    from bigdl_tpu.parallel import DistriOptimizer, Engine
    from bigdl_tpu.serving import ContinuousBatchingEngine

    def listed(config, role):
        return harness.load_json(harness.HERE, "configs", config + ".json")[
            "programs"][role]

    lm = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                       num_layers=1, max_len=32, use_rope=True)
    lm.evaluate()
    eng = ContinuousBatchingEngine(lm, max_slots=2, prefill_chunk=4,
                                   page_size=4, max_pages=16)
    assert "jit_" + eng._step_jit.__name__ in listed("gpt2-large",
                                                     "decode_step")
    assert "jit_" + eng._chunk_jit.__name__ in listed("gpt2-large",
                                                      "prefill_chunk")
    model = nn.Sequential(nn.Linear(4, 2))
    ts = make_train_step(model, nn.MSECriterion(), SGD(learning_rate=0.1))
    names = listed("resnet50-imagenet", "train_step")
    # the step with stats (observability on) first, the plain step second
    assert ["jit_" + ts.step_with_stats.__name__,
            "jit_" + ts.step.__name__] == names == ["jit__core", "jit_step"]
    # the sharded step: no configuration of the manifest lists it yet; the
    # four-chip cell of PERF.md section 7 will, under this name
    built = []
    build = DistriOptimizer._build_sharded_step

    def noting(self, *a, **k):
        out = build(self, *a, **k)
        built.append(out[0].__name__)
        return out

    monkeypatch.setattr(DistriOptimizer, "_build_sharded_step", noting)
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(4).astype(np.float32),
                      rng.randn(2).astype(np.float32)) for _ in range(16)]
    opt = DistriOptimizer(
        model=model, dataset=DataSet.array(samples),
        criterion=nn.MSECriterion(), batch_size=16,
        end_when=Trigger.max_iteration(1),
        mesh=Engine.create_mesh([("data", len(jax.devices()))]),
        parameter_sync="sharded", compress_dtype=None)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.optimize()
    assert ["jit_" + n for n in built] == ["jit_shard_step"]
