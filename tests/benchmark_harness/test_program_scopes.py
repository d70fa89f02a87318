"""The reader of device time by scope (``benchmark/program_scopes.py``),
checked on the CPU: the rule that charges an ``op_name`` to a scope, the few
protobuf fields read from a capture's bytes (on a hand-encoded capture), self
times and whole runs on hand-built events, the thirteen per-layer metrics on
hand-worked numbers, the scopes a configuration's file declares, and the
manifest's entries (as invariants: ``manifest_invariants.py``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import manifest_invariants  # noqa: E402  (beside this file)
from benchmark import harness, program_scopes, program_spans  # noqa: E402

MS = 1_000_000
#: metric -> (layer, moves, cells, role, group)
NEW = manifest_invariants.BY_SCOPE


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize("op_name, scope", [
    # the innermost vocabulary scope wins, a module class inside it does not
    ("jit(step)/attn/qkv/Linear/dot_general", "attn/qkv"),
    ("jit(chunk)/while/body/closed_call/attn/attend/attn/kv_gather/gather",
     "attn/kv_gather"),
    ("jit(step)/sparse/attend/attn/kv_gather/jit(_take)/select_n",
     "attn/kv_gather"),
    ("jit(step)/mlp/GatedMLP/mlp/mul", "mlp"),
    ("jit(step)/lightning/step/attn/out/RMSNorm/rsqrt", "attn/out"),
    # no vocabulary scope: the innermost module class
    ("jit(_core)/transpose(jvp(Sequential/SpatialConvolution))/"
     "conv_general_dilated", "SpatialConvolution"),
    ("jit(_core)/jvp(Sequential)/Bottleneck/SpatialBatchNormalization/mul",
     "SpatialBatchNormalization"),
    ("jit(_core)/transpose(jvp(ReLU))/select_n", "ReLU"),
    # wrappers name nothing, whatever they wrap
    ("jit(_core)/transpose(jvp(optim/loss))/jit(take_along_axis)/scatter-add",
     "optim/loss"),
    ("jit(_core)/jvp(optim/update)/convert_element_type", "optim/update"),
    ("jit(step)/vmap(attn/qkv)/sin", "attn/qkv"),
    ("jit(f)/checkpoint(remat(Linear))/dot_general", "Linear"),
    ("jit(step)/cond/branch_1_fun/sample/argmax", "sample"),
    ("jit(shmap)/bigdl/grad_reduce_scatter/psum_scatter",
     "bigdl/grad_reduce_scatter"),
    # a jitted function's own name is no scope, nor is a primitive's
    ("jit(head)/jit(main)/add", None),
    ("jit(step)/jit(_where)/select_n", None),
    ("jit(step)/while/body/add", None),
    ("gather", None), ("", None), (None, None),
])
def test_an_op_name_is_charged_to_its_innermost_scope(op_name, scope):
    assert program_scopes.scope_of(op_name) == scope


def test_the_groups_the_metrics_read():
    g = program_scopes.group_of
    assert g("attn/kv_write") == g("attn/kv_gather") == "kv_pages"
    assert g("attn/attend") == g("sparse/attend") == "attend"
    assert {g(s) for s in ("embed", "attn/qkv", "attn/out", "mlp", "norm",
                           "head", "sample")} == {"dense"}
    assert {g(s) for s in ("gdn/step", "gdn/chunk", "lightning/step",
                           "lightning/chunk")} == {"recurrent"}
    assert g("sparse/select") == "select"
    assert g("SpatialConvolution") == g("SpatialDilatedConvolution") == "conv"
    assert g("SpatialBatchNormalization") == g("BatchNormalization") == "bn"
    assert g("ReLU") == g("optim/update") == "other"
    assert g(None) == "unscoped"


# a configuration's file: {"scopes": DECLARED}
DECLARED = {"moe/experts": "experts", "moe/route": "route",
            "mla/absorb": "attend", "RMSNorm": "rmsnorm"}


@pytest.mark.parametrize("op_name, scope, plain", [
    # a declared scope is vocabulary as a row of GROUPS is: innermost wins
    ("jit(step)/mlp/GatedMLP/moe/experts/dot_general", "moe/experts", "mlp"),
    ("jit(step)/moe/experts/mlp/mul", "mlp", "mlp"),
    ("jit(step)/mlp/moe/route/Linear/dot_general", "moe/route", "mlp"),
    ("jit(step)/while/body/mla/absorb/jit(_einsum)/dot_general",
     "mla/absorb", None),
    # a module class's name may be declared: then it is no mere fallback
    ("jit(step)/attn/out/RMSNorm/rsqrt", "RMSNorm", "attn/out"),
    ("jit(step)/RMSNorm/norm/rsqrt", "norm", "norm"),
    ("jit(step)/jvp(RMSNorm)/rsqrt", "RMSNorm", "RMSNorm"),
    # what no file declares reads as it did
    ("jit(step)/attn/qkv/Linear/dot_general", "attn/qkv", "attn/qkv"),
    ("jit(step)/moe/add", None, None),
])
def test_a_declared_scope_is_vocabulary_for_its_configuration(
        op_name, scope, plain):
    groups = program_scopes.vocabulary(DECLARED)
    assert program_scopes.scope_of(op_name, groups) == scope
    assert program_scopes.scope_of(op_name) == plain
    assert program_scopes.scope_of(
        op_name, program_scopes.vocabulary({})) == plain


def test_a_declaration_adds_groups_and_regroups_nothing():
    assert program_scopes.vocabulary(None) is program_scopes.GROUPS
    assert program_scopes.vocabulary({}) is program_scopes.GROUPS
    groups = program_scopes.vocabulary(DECLARED)
    before = dict(program_scopes.GROUPS)
    assert {k: groups[k] for k in before} == before == program_scopes.GROUPS
    g = lambda scope: program_scopes.group_of(scope, groups)
    assert g("moe/experts") == "experts" and g("moe/route") == "route"
    assert g("mla/absorb") == g("attn/attend") == "attend"
    assert g("RMSNorm") == "rmsnorm" and g("LayerNorm") == "other"
    assert g("SpatialConvolution") == "conv" and g(None) == "unscoped"
    assert program_scopes.group_of("RMSNorm") == "other"
    for bad in ({"mlp": "experts"}, {"attn/attend": "attend"},
                {"optim/loss": "other"}, {"SpatialConvolution": "conv2"},
                {"BatchNormalization": "other"}, {"a/b/c": "x"},
                {"": "x"}, {"jit(step)": "x"}, {"moe/experts": "unscoped"},
                {"moe/experts": ""}, {"moe/experts": 3}):
        with pytest.raises(harness.BenchmarkError):
            program_scopes.vocabulary(bad)


def test_the_reader_knows_the_programs_vocabulary():
    """The program may name a new part when, and only when, the
    configuration that runs it says how to read it."""
    manifest_invariants.the_reader_knows_the_programs_vocabulary(
        harness.manifest(), harness.ROOT)


# ------------------------------------------------ the capture's HLO protos
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def hlo_proto(instructions):
    """A serialized ``HloProto`` of one computation: ``(name, op_name or
    None)`` an instruction (hlo.proto's field numbers)."""
    comp = field(1, "main.1") + b"".join(
        field(2, field(1, name) + field(2, "fusion") + field(35, 7)
              + (field(7, field(1, "op") + field(2, op_name)
                       + field(4, 12)) if op_name is not None else b""))
        for name, op_name in instructions)
    return field(1, field(1, "jit_step") + field(3, comp) + field(5, 3))


def capture_bytes(programs, plane="/host:metadata"):
    """An ``XSpace`` whose ``plane`` holds one event metadata a program,
    each with an ``Hlo Proto`` bytes stat (xplane.proto's field numbers),
    beside a device plane that holds none."""
    metas = b"".join(
        field(4, field(1, i + 1) + field(2, field(1, i + 1) + field(2, name)
              + field(5, field(1, 1) + field(6, hlo_proto(ins)))))
        for i, (name, ins) in enumerate(programs.items()))
    device = field(1, 1) + field(2, "/device:TPU:0") + field(
        4, field(1, 9) + field(2, field(1, 9) + field(2, "%fusion.1 = x")
                               + field(5, field(1, 2) + field(3, 5))))
    return (field(1, device)
            + field(1, field(1, 2) + field(2, plane) + metas
                    + field(5, field(1, 1) + field(2, field(1, 1)
                                                   + field(2, "Hlo Proto")))))


def test_op_names_are_read_from_the_captures_hlo_protos(tmp_path):
    programs = {
        "jit_step(123)": [("fusion.1", "jit(step)/attn/qkv/dot_general"),
                          ("copy.2", None),
                          ("while.3", "jit(step)/attn/attend/while")],
        "jit_chunk(45)": [("fusion.1", "jit(chunk)/mlp/mul")],
    }
    path = tmp_path / "a.xplane.pb"
    path.write_bytes(capture_bytes(programs))
    assert program_scopes.hlo_op_names(str(path)) == {
        name: dict(ins) for name, ins in programs.items()}
    # a capture without the plane (another platform, an older profiler)
    path.write_bytes(capture_bytes(programs, plane="/host:CPU"))
    assert program_scopes.hlo_op_names(str(path)) == {}


# ---------------------------------------------------------- self time, runs
def op(name, start_ms, dur_ms):
    return (f"%{name} = f32[8]{{0}} fusion(%p)", start_ms * MS, dur_ms * MS)


STEP_NAMES = {"fusion.1": "jit(step)/attn/kv_gather/gather",
              "fusion.2": "jit(step)/attn/attend/dot_general",
              "while.3": "jit(step)/attn/attend/while",
              "fusion.4": "jit(step)/attn/attend/while/body/attn/kv_gather/"
                          "gather",
              "fusion.5": "jit(step)/attn/attend/while/body/mul",
              "fusion.6": "jit(step)/mlp/dot_general",
              "copy.7": None, "fusion.8": "jit(step)/add"}
CHUNK_NAMES = {"fusion.1": "jit(chunk)/gdn/chunk/dot_general",
               "fusion.2": "jit(chunk)/attn/qkv/Linear/dot_general"}


def step_run(t):
    """One run of ``jit_step`` from ``t`` ms, 20 ms long: a gather (4), an
    attention (2), a ``while`` (8) with three body operations inside it (a
    gather of 3, products of 2 and 2: the loop's own time is 1), an MLP
    (3), a copy XLA put in (1), a residual add nobody named (1) = 19, and
    1 ms in which no operation ran."""
    return ("jit_step(11)", t * MS, 20 * MS), [
        op("fusion.1", t, 4), op("fusion.2", t + 4, 2),
        op("while.3", t + 6, 8), op("fusion.4", t + 6.5, 3),
        op("fusion.5", t + 9.5, 2), op("fusion.5", t + 11.5, 2),
        op("fusion.6", t + 14, 3), op("copy.7", t + 17, 1),
        op("fusion.8", t + 18, 1)]


def chunk_run(t, dense=6):
    return ("jit_chunk(22)", t * MS, 10 * MS), [
        op("fusion.1", t, 3), op("fusion.2", t + 3, dense)]


def capture():
    """Two programs interleaved over a window of [0, 200] ms: whole runs
    of ``jit_step`` at 10, 50, 90 and of ``jit_chunk`` at 30, 70 and 110
    (their dense part 6, 6 and 7 ms), a ``jit_step`` cut by the window's
    end, one before its start, and an operation outside every run."""
    mods, ops = [], []
    for t in (10, 50, 90, 190, -15):
        m, o = step_run(t)
        mods.append(m), ops.extend(o)
    for t, dense in ((30, 6), (70, 6), (110, 7)):
        m, o = chunk_run(t, dense)
        mods.append(m), ops.extend(o)
    ops.append(op("fusion.9", 150, 5))          # outside any run: dropped
    return {"start_ns": 0, "modules": mods, "ops": ops,
            "window": (0.0, 200.0 * MS)}


HLO = {"jit_step(11)": STEP_NAMES, "jit_chunk(22)": CHUNK_NAMES}


def test_self_time_counts_a_loops_body_once():
    _, events = step_run(0)
    ops, own = program_scopes.self_times(events)
    by = {}
    for (name, _, _), ns in zip(ops, own):
        key = program_scopes.instruction_name(name)
        by[key] = by.get(key, 0) + ns / MS
    assert by["while.3"] == 1 and by["fusion.4"] == 3 and by["fusion.5"] == 4
    assert sum(own) == 19 * MS          # the events' union, nothing twice


def test_whole_runs_in_the_window_by_scope_and_group():
    t = program_scopes.tables(capture(), HLO)
    assert set(t) == {"jit_step", "jit_chunk"}
    step, chunk = t["jit_step"], t["jit_chunk"]
    assert step["runs"] == 3 and chunk["runs"] == 3    # the cut ones are out
    assert step["module_median_ms"] == 20 and step["ops_median_ms"] == 19
    # the loop's gather stays a gather inside the attention; the loop's own
    # millisecond and its products are the attention's
    assert step["by_scope"] == {"attn/kv_gather": 7, "attn/attend": 7,
                                "mlp": 3, "unscoped": 2}
    assert step["by_group"] == {"kv_pages": 7, "attend": 7, "dense": 3,
                                "unscoped": 2}
    # the closure: scopes + unscoped = operations, exactly
    assert step["mean_ms"] == {"ops": 19, "scoped": 17, "unscoped": 2}
    assert step["no_scopes"] is None
    assert [n for n, _ in step["unscoped_top"]] == [
        "copy.7 fusion f32[8]", "fusion.8 fusion f32[8]"]
    # medians over the runs: 6, 6, 7
    assert chunk["by_group"] == {"recurrent": 3, "dense": 6}
    assert chunk["mean_ms"]["ops"] == pytest.approx(28 / 3)
    only = program_scopes.tables(capture(), HLO, {"jit_chunk"}, by_shape=True)
    assert set(only) == {"jit_chunk"}
    assert only["jit_chunk"]["by_shape"][0] == (
        "attn/qkv", "fusion f32[8]", pytest.approx(19 / 3))
    assert "attn/qkv" in program_scopes.render("jit_chunk", only["jit_chunk"])


# -------------------------------------------------------------- the metrics
RUN = {"programs": {"decode_step": ["jit_step"],
                    "prefill_chunk": ["jit_chunk"]}}
TRACE = {"programs": {}}          # run.py's summary: only "is there one"


@pytest.fixture
def hand_built(monkeypatch, tmp_path):
    def install(cap, hlo):
        path = tmp_path / "x.xplane.pb"
        path.write_bytes(b"")
        monkeypatch.setattr(program_spans, "traced", lambda: cap)
        monkeypatch.setattr(program_spans, "newest_xplane",
                            lambda: str(path))
        monkeypatch.setattr(program_scopes, "hlo_op_names", lambda p: hlo)
    return install


def read(name, run, trace):
    return harness.load_module("metrics", name).value(run, trace)


def test_the_metrics_on_hand_worked_numbers(hand_built, capsys):
    hand_built(capture(), HLO)
    run = dict(RUN)
    got = {name: read(name, run, TRACE) for name in NEW}
    assert got == {
        "decode_kv_pages_ms": 7, "decode_attend_ms": 7, "decode_dense_ms": 3,
        "decode_recurrent_ms": None, "decode_select_ms": None,
        "chunk_kv_pages_ms": None, "chunk_attend_ms": None,
        "chunk_dense_ms": 6, "chunk_recurrent_ms": 3,
        "train_conv_ms": None, "train_bn_ms": None,
        # 3 runs x 2 ms unscoped of 3 x 19 + 28 ms of operations
        "program_unscoped_pct.serve": pytest.approx(100 * 6 / 85),
        "program_unscoped_pct.train": pytest.approx(100 * 6 / 85),
    }
    # read once a run, logged once
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[scopes] ")]
    assert len(lines) == 1
    line = json.loads(lines[0][len("[scopes] "):])
    assert line["decode_step"]["program"] == "jit_step"
    assert line["decode_step"]["mean_ms"] == {"ops": 19, "scoped": 17,
                                              "unscoped": 2}
    assert line["unscoped_pct"] == pytest.approx(100 * 6 / 85)
    assert "read_s" in line and line["capture_bytes"] == 0


def test_a_declared_group_is_read_and_counts_as_scoped(hand_built, capsys):
    """The same capture under a configuration that declares two scopes: the
    routed experts' 3 ms leave ``dense`` for a group of their own, the
    residual add nobody named (1 ms) is the router's and no longer unscoped,
    and every group ``GROUPS`` reads stays where it was."""
    hlo = {"jit_step(11)": dict(
        STEP_NAMES, **{"fusion.6": "jit(step)/mlp/moe/experts/dot_general",
                       "fusion.8": "jit(step)/moe/route/add"}),
        "jit_chunk(22)": CHUNK_NAMES}
    hand_built(capture(), hlo)
    plain, run = dict(RUN), dict(RUN, scopes={"moe/experts": "experts",
                                              "moe/route": "route"})
    g = program_scopes.group_ms
    assert g(run, TRACE, "decode_step", "experts") == 3
    assert g(run, TRACE, "decode_step", "route") == 1
    assert g(run, TRACE, "decode_step", "dense") is None
    assert g(run, TRACE, "prefill_chunk", "experts") is None
    assert g(plain, TRACE, "decode_step", "experts") is None
    assert g(plain, TRACE, "decode_step", "dense") == 3
    for group in ("kv_pages", "attend"):
        assert g(run, TRACE, "decode_step", group) == g(
            plain, TRACE, "decode_step", group) == 7
    new, old = (program_scopes.scopes(r, TRACE)["decode_step"]
                for r in (run, plain))
    assert sum(new["by_group"].values()) == sum(old["by_group"].values())
    assert new["mean_ms"] == {"ops": 19, "scoped": 18, "unscoped": 1}
    assert old["mean_ms"] == {"ops": 19, "scoped": 17, "unscoped": 2}
    assert read("program_unscoped_pct.serve", run, TRACE) == pytest.approx(
        100 * 3 / 85)
    assert read("program_unscoped_pct.serve", plain, TRACE) == pytest.approx(
        100 * 6 / 85)
    assert "moe/experts" in capsys.readouterr().err


def test_by_hand_the_same_declaration_from_the_configurations_file(
        monkeypatch, tmp_path, capsys):
    hlo = {"jit_step(11)": dict(
        STEP_NAMES, **{"fusion.6": "jit(step)/mlp/moe/experts/dot_general"}),
        "jit_chunk(22)": CHUNK_NAMES}
    monkeypatch.setattr(program_scopes, "hlo_op_names", lambda p: hlo)
    cap = capture()
    monkeypatch.setattr(program_spans, "read_capture",
                        lambda path: dict(cap, marker=cap["window"]))
    config = tmp_path / "config.json"
    config.write_text(json.dumps({"scopes": {"moe/experts": "experts"}}))
    path = str(tmp_path / "x.xplane.pb")
    assert program_scopes.main([path, "--program", "jit_step"]) == 0
    assert "experts" not in capsys.readouterr().out
    assert program_scopes.main([path, "--program", "jit_step", "--config",
                                str(config)]) == 0
    out = capsys.readouterr().out
    assert "experts 3.000" in out and "moe/experts" in out


def test_the_training_cells_metrics(hand_built):
    names = {"fusion.1": "jit(_core)/transpose(jvp(Sequential/"
                         "SpatialConvolution))/conv_general_dilated",
             "fusion.2": "jit(_core)/jvp(Sequential)/"
                         "SpatialBatchNormalization/mul",
             "fusion.3": "jit(_core)/jvp(Sequential)/ReLU/max",
             "fusion.4": "jit(_core)/optim/update/mul", "copy.5": None}
    mods, ops = [], []
    for t in (0, 40, 80, 120, 160, 200):     # the first is cut short
        mods.append(("jit__core(7)", t * MS, (30 if t else 13) * MS))
        ops += [op("fusion.1", t, 12), op("fusion.2", t + 12, 9),
                op("fusion.3", t + 21, 3), op("fusion.4", t + 24, 4),
                op("copy.5", t + 28, 2)]
    hand_built({"start_ns": 0, "modules": mods, "ops": ops,
                "window": (0.0, 200.0 * MS)}, {"jit__core(7)": names})
    run = {"programs": {"train_step": ["jit__core", "jit_step"]}}
    assert read("train_conv_ms", run, TRACE) == 12
    assert read("train_bn_ms", run, TRACE) == 9
    assert read("program_unscoped_pct.train", run, TRACE) == pytest.approx(
        100 * 2 / 30)
    t = program_scopes.scopes(run, TRACE)["train_step"]
    assert t["by_group"] == {"conv": 12, "bn": 9, "other": 7, "unscoped": 2}
    # without a marker the window opens at the first run's start, which the
    # capture's start may have cut: the capture's first and last runs are out
    assert t["runs"] == 4 and t["mean_ms"]["ops"] == 30


@pytest.mark.parametrize("hlo, why", [
    ({}, "the capture holds no HLO for this program"),
    ({"jit_step(11)": dict.fromkeys(STEP_NAMES),
      "jit_chunk(22)": dict.fromkeys(CHUNK_NAMES)},
     "the program's HLO carries no op_name"),
    ({"jit_step(11)": {k: v and "jit(step)/add" for k, v in
                       STEP_NAMES.items()},
      "jit_chunk(22)": {k: "jit(chunk)/mul" for k in CHUNK_NAMES}},
     "executable carries no scopes"),
], ids=["no_hlo", "no_op_names", "stale_executable"])
def test_a_capture_with_no_scopes_says_so(hand_built, capsys, hlo, why):
    """Never a silent None: the unscoped share reads 100, the by-group
    metrics find nothing, and the log line says why."""
    hand_built(capture(), hlo)
    run = dict(RUN)
    assert read("program_unscoped_pct.serve", run, TRACE) == 100
    assert read("decode_kv_pages_ms", run, TRACE) is None
    assert read("chunk_dense_ms", run, TRACE) is None
    (line,) = [ln for ln in capsys.readouterr().err.splitlines()
               if ln.startswith("[scopes] ")]
    table = json.loads(line[len("[scopes] "):])
    assert table["decode_step"]["no_scopes"].startswith(why)
    assert table["decode_step"]["by_group"] == {"unscoped": 19}


def test_without_a_trace_nothing_is_read(monkeypatch):
    """An end-to-end run hands ``trace=None``: every reader returns None and
    opens no file (a stale capture on disk is not this run's)."""
    def never(*a, **k):
        raise AssertionError("a reader touched the capture")

    monkeypatch.setattr(program_spans, "newest_xplane", never)
    monkeypatch.setattr(program_spans, "traced", never)
    monkeypatch.setattr(program_scopes, "hlo_op_names", never)
    for name in NEW:
        assert read(name, dict(RUN), None) is None
    # a traced run whose capture is gone: said, not raised
    monkeypatch.setattr(program_spans, "traced", lambda: None)
    monkeypatch.setattr(program_spans, "newest_xplane", lambda: None)
    assert read("decode_dense_ms", dict(RUN), TRACE) is None


# ------------------------------------------------------------- the manifest
def test_every_new_metric_has_its_file_its_entry_and_its_cells():
    """Invariants, not a snapshot (``manifest_invariants.py``): they hold
    when a later PR appends, and ``test_manifest_grows.py`` shows it."""
    man = harness.manifest()
    manifest_invariants.the_scope_readers_metrics(man, harness.ROOT)
    manifest_invariants.what_the_benchmark_had_keeps_its_place(
        man, harness.ROOT)
