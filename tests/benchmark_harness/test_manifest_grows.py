"""The benchmark takes a configuration, a cell, a per-layer metric and a
device scope BY FILES ALONE, rehearsed against the real manifest: a copy of
``BENCHMARK.json`` and ``benchmark/`` grows as a later PR grows them (new
files, entries appended, no file the benchmark has edited), and then every
invariant the benchmark's tests hold the live manifest to
(``manifest_invariants.py``) holds on the copy, the new cell runs through the
unedited serving driver on the CPU, and the new metric reads the scope the new
configuration's file declares. Between PR 39 and PR 46 the tests pinned the
manifest at four cells, four configurations and 44 per-layer metrics, and
three PRs that brought a configuration fell on it (PERF.md section 7): run
``pytest tests/benchmark_harness -q`` before the first chip call.

The stand-in architecture is ``test_second_architecture.py``'s."""

import filecmp
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import manifest_invariants  # noqa: E402  (beside this file)
import test_program_scopes as hand  # noqa: E402  (its hand-built capture)
from test_second_architecture import rotary_cell  # noqa: E402
from benchmark import harness, program_scopes, program_spans  # noqa: E402

SEED = 2 ** 31 + 46
#: names no later PR will bring, so that the rehearsal runs on a manifest
#: that has grown since
CELL, CONFIG, MIX, METRIC = ("rehearsal-chat-steady", "rehearsal-stand-in",
                             "rehearsal-steady", "decode_rehearsal_ms")
#: the part of the step the stand-in's program would name, and a module
#: class it opens already, as its file says how to read them
SCOPE = "rehearsal/experts"
SCOPES = {SCOPE: "experts", "Router": "route"}
READER = '''"""Kernels: median device milliseconds a run of the decode-step program
under the routed experts (a scope the configuration's file declares)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "experts")
'''


def grow(root, config, mix):
    """What a later PR does to the checkout at ``root``: three new files and
    entries appended to ``BENCHMARK.json``. ``config`` and ``mix`` are the
    new configuration's and traffic's JSON."""
    bench = os.path.join(root, "benchmark")
    new = {os.path.join(bench, "configs", CONFIG + ".json"):
           json.dumps(dict(config, name=CONFIG, scopes=SCOPES), indent=1),
           os.path.join(bench, "traffic", MIX + ".json"):
           json.dumps(mix, indent=1),
           os.path.join(bench, "metrics", METRIC + ".py"): READER}
    for path, text in new.items():
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    man = manifest_invariants.load(root)
    served = set(manifest_invariants.SERVING)
    man["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": f"benchmark/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": "a later PR's configuration"})
    man["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "a later PR's cell"})
    # the cell under each metric a serving cell reports
    for m in man["end_to_end"] + man["per_layer"]:
        if served <= set(m.get("workloads", [])):
            m["workloads"].append(CELL)
    man["per_layer"].append({
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "itl_p95_ms",
        "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f, indent=1)
    return sorted(new)


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("grown"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = rotary_cell()      # registers the stand-in's adapter and reference
    added = grow(root, cell["config_json"], cell["traffic_json"])
    return root, added


@pytest.fixture
def program_names_a_part(monkeypatch):
    """The program's side of such a PR: one more scope in its vocabulary."""
    from bigdl_tpu.observability import tracing

    monkeypatch.setattr(tracing, "DEVICE_SCOPES",
                        tracing.DEVICE_SCOPES + (SCOPE,))


def test_the_copy_grew_by_new_files_and_appended_entries_alone(grown):
    root, added = grown
    diff = filecmp.dircmp(os.path.join(ROOT, "benchmark"),
                          os.path.join(root, "benchmark"),
                          ignore=["__pycache__"])

    def walk(d):
        yield d
        for sub in d.subdirs.values():
            yield from walk(sub)

    for d in walk(diff):
        assert not d.diff_files and not d.funny_files, d.right
        assert not d.left_only, d.right
    assert sorted(os.path.join(d.right, n) for d in walk(diff)
                  for n in d.right_only) == added
    was, now = harness.manifest(), manifest_invariants.load(root)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[key]) >= len(was[key])
        for a, b in zip(was[key], now[key]):
            lists = {k for k in a if isinstance(a[k], list)}
            assert {k: v for k, v in a.items() if k not in lists} == \
                {k: v for k, v in b.items() if k not in lists}
            assert all(b[k][:len(a[k])] == a[k] for k in lists)
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == was[key]
    assert len(now["configs"]) == len(was["configs"]) + 1
    assert len(now["workloads"]) == len(was["workloads"]) + 1
    assert len(now["per_layer"]) == len(was["per_layer"]) + 1


@pytest.mark.parametrize("invariant", manifest_invariants.ALL,
                         ids=lambda f: f.__name__)
def test_every_invariant_holds_on_the_grown_manifest(
        grown, program_names_a_part, invariant):
    root, _ = grown
    invariant(manifest_invariants.load(root), root)


def test_a_scope_the_program_names_and_no_file_declares_fails(
        grown, program_names_a_part, tmp_path):
    """The other half of the vocabulary's rule: the program may name a new
    part only when the configuration that runs it says how to read it."""
    root, _ = grown
    man = manifest_invariants.load(root)
    with pytest.raises(AssertionError):      # the live manifest declares none
        manifest_invariants.the_reader_knows_the_programs_vocabulary(
            harness.manifest(), ROOT)
    # and a file that re-groups what the reader knows is refused on loading
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "benchmark"), tmp_path / "benchmark")
    path = tmp_path / "benchmark" / "configs" / (CONFIG + ".json")
    held = json.loads(path.read_text())
    for scopes in ({"mlp": "experts"}, {"SpatialConvolution": "experts"},
                   {"optim/update": "other"}, {"a/b/c": "experts"},
                   {SCOPE: "unscoped"}, {SCOPE: "a b"}):
        path.write_text(json.dumps(dict(held, scopes=scopes)))
        with manifest_invariants.rooted(str(tmp_path)):
            with pytest.raises(harness.BenchmarkError, match="scope"):
                harness.load_cell(CELL, man)
        with pytest.raises(harness.BenchmarkError):
            manifest_invariants.the_reader_knows_the_programs_vocabulary(
                man, str(tmp_path))


def test_the_grown_cell_runs_and_its_metric_reads_the_declared_scope(
        grown, monkeypatch):
    import jax

    root, _ = grown
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
    with manifest_invariants.rooted(root):
        cell = harness.load_cell(CELL)
        serve = harness.load_module("drivers", "serve")
        out = serve.run(cell, SEED, 2.0, False, time.perf_counter())
        assert out["correct"] is True, out["checks"]
        assert out["failed"] == 0 and out["attempted"] > 8
        assert {m["name"] for m in cell["end_to_end"]} == set(out["values"])
        # the declaration rides on the run's record to the readers
        assert out["record"]["scopes"] == SCOPES
        names = [m["name"] for m in cell["per_layer"]]
        assert METRIC in names and "decode_dense_ms" in names
        run = dict(out["record"], peaks={})
        # nothing traced on a CPU: every device_trace reader returns nothing
        for m in cell["per_layer"]:
            if m["source"] == "device_trace":
                assert harness.load_module("metrics", m["name"]).value(
                    run, None) is None
        # a capture in which the MLP's 3 ms lie under the declared scope
        hlo = {"jit_step(11)": dict(
            hand.STEP_NAMES,
            **{"fusion.6": f"jit(step)/mlp/{SCOPE}/dot_general"}),
            "jit_chunk(22)": hand.CHUNK_NAMES}
        monkeypatch.setattr(program_spans, "traced", hand.capture)
        monkeypatch.setattr(program_spans, "newest_xplane",
                            lambda: os.path.join(root, "BENCHMARK.json"))
        monkeypatch.setattr(program_scopes, "hlo_op_names", lambda p: hlo)
        read = lambda name, run: harness.load_module("metrics", name).value(
            run, hand.TRACE)
        assert read(METRIC, run) == 3
        assert read("decode_dense_ms", run) is None
        assert read("decode_attend_ms", run) == 7
        # the same capture under a configuration that declares nothing
        plain = dict(out["record"], scopes={}, peaks={})
        assert read(METRIC, plain) is None
        assert read("decode_dense_ms", plain) == 3
