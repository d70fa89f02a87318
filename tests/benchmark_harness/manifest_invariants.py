"""What the benchmark's own tests hold ``BENCHMARK.json`` to, as functions of
a manifest and the root it sits in: the same code checks the live manifest
(``test_program_scopes.py``, ``test_benchmark_harness.py``) and a copy that
grew as a later PR grows it (``test_manifest_grows.py``). Every one of them
stays true when a configuration, a cell or a per-layer metric is APPENDED
with its files; none pins a count or a list's end. What the benchmark had
when these were written (PR 46) keeps its place and its order."""

import contextlib
import glob
import os
import re

from benchmark import harness, program_scopes

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: PR 46's manifest: a later PR's entries come after these, in any number
PER_LAYER = [
    "gen_late_p95_ms", "queue_wait_p95_ms", "ttft_p95_ms", "prefix_hit_pct",
    "kv_pages_peak_pct", "loop_host_pct", "decode_step_ms",
    "prefill_chunk_ms", "decode_step_roofline", "device_idle_pct.serve",
    "train_step_ms", "train_host_gap_ms", "train_step_mfu",
    "device_idle_pct.train", "train_data_wait_ms", "train_dispatch_ms",
    "train_bookkeeping_ms", "train_gc_pause_max_ms", "idle_named_pct.train",
    "loop_deliver_ms", "loop_observe_ms", "train_arguments_ms",
    "olmoh_decode_step_roofline", "olmoh_prefill_chunk_roofline",
    "state_restore_ms", "state_snapshot_ms", "prefix_resume_shortfall_pct",
    "sala_decode_step_roofline", "sala_prefill_chunk_roofline",
    "sparse_kv_read_pct", "sparse_decode_rows_pct", "decode_kv_pages_ms",
    "decode_attend_ms", "decode_dense_ms", "decode_recurrent_ms",
    "decode_select_ms", "chunk_kv_pages_ms", "chunk_attend_ms",
    "chunk_dense_ms", "chunk_recurrent_ms", "train_conv_ms", "train_bn_ms",
    "program_unscoped_pct.serve", "program_unscoped_pct.train"]
CELLS = ["gpt2l-chat-steady", "resnet50-local-b256", "olmoh-docqa-steady",
         "sala-longdoc-steady"]
CONFIGS = ["gpt2-large", "resnet50-imagenet", "olmo-hybrid-7b",
           "minicpm-sala"]
END_TO_END = [("itl_p95_ms", 0.05), ("serve_due_tok_per_s", 0.01),
              ("train_samples_per_s", 0.01), ("setup_s", 0.1)]
RUN_SECONDS = 51

SERVING = ["gpt2l-chat-steady", "olmoh-docqa-steady", "sala-longdoc-steady"]
LANES = SERVING[1:]
TRAIN = ["resnet50-local-b256"]
#: the scope reader's metrics (PR 39): metric -> (layer, moves, the cells
#: it lists at the least, its program's role, its group)
BY_SCOPE = {
    "decode_kv_pages_ms": ("kernels", "itl_p95_ms", SERVING, "decode_step",
                           "kv_pages"),
    "decode_attend_ms": ("kernels", "itl_p95_ms", SERVING, "decode_step",
                         "attend"),
    "decode_dense_ms": ("kernels", "itl_p95_ms", SERVING, "decode_step",
                        "dense"),
    "decode_recurrent_ms": ("kernels", "itl_p95_ms", LANES, "decode_step",
                            "recurrent"),
    "decode_select_ms": ("cache manager", "itl_p95_ms", SERVING[2:],
                         "decode_step", "select"),
    "chunk_kv_pages_ms": ("kernels", "itl_p95_ms", SERVING, "prefill_chunk",
                          "kv_pages"),
    "chunk_attend_ms": ("kernels", "itl_p95_ms", SERVING, "prefill_chunk",
                        "attend"),
    "chunk_dense_ms": ("kernels", "itl_p95_ms", SERVING, "prefill_chunk",
                       "dense"),
    "chunk_recurrent_ms": ("kernels", "itl_p95_ms", LANES, "prefill_chunk",
                           "recurrent"),
    "train_conv_ms": ("kernels", "train_samples_per_s", TRAIN, "train_step",
                      "conv"),
    "train_bn_ms": ("kernels", "train_samples_per_s", TRAIN, "train_step",
                    "bn"),
    "program_unscoped_pct.serve": ("device", "itl_p95_ms", SERVING, None,
                                   None),
    "program_unscoped_pct.train": ("device", "train_samples_per_s", TRAIN,
                                   None, None),
}
#: the driver whose cells run a role's program
DRIVER_OF_ROLE = {"decode_step": "serve", "prefill_chunk": "serve",
                  "train_step": "train"}
METRIC_KEYS = {"name", "unit", "better", "source", "layer", "moves",
               "workloads"}


def load(root):
    return harness.load_json(root, "BENCHMARK.json")


@contextlib.contextmanager
def rooted(root):
    """``harness`` finds every file from its ``ROOT`` and ``HERE``: inside,
    they are ``root``'s, so its ``load_cell`` and ``load_module`` open the
    files of a copy. The live root is left as it is."""
    before = harness.ROOT, harness.HERE
    harness.ROOT, harness.HERE = root, os.path.join(root, "benchmark")
    try:
        yield
    finally:
        harness.ROOT, harness.HERE = before


def config_files(man, root):
    """{configuration's name: its file's JSON}."""
    return {c["name"]: harness.load_json(root, c["file"])
            for c in man["configs"]}


def driver_of(man, root):
    """{cell: the driver its configuration's file names}."""
    files = config_files(man, root)
    return {w["name"]: files[w["config"]]["driver"] for w in man["workloads"]}


# ------------------------------------------------------------ the invariants
def names_units_and_files_resolve(man, root):
    """Every name is a name, every entry finds its files, every cell opens
    and reports enough, every reader returns nothing from nothing."""
    assert man["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= man["run_seconds"] <= 51
    metrics = man["end_to_end"] + man["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in e2e.values())
    cells = [w["name"] for w in man["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(
        1, len(cells) // 4)
    configs = [c["name"] for c in man["configs"]]
    assert len(set(configs)) == len(configs)
    assert {w["config"] for w in man["workloads"]} == set(configs)
    with rooted(root):
        for w in man["workloads"]:
            assert NAME.match(w["name"]) and len(w["why"]) <= 200
            assert os.path.exists(os.path.join(
                root, "benchmark", "traffic", w["traffic"] + ".json"))
            cell = harness.load_cell(w["name"], man)    # both files open
            assert cell["config_json"]["driver"] in ("serve", "train")
            assert os.path.exists(os.path.join(
                root, "benchmark", "drivers",
                cell["config_json"]["driver"] + ".py"))
            assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        files = [c["file"] for c in man["configs"]]
        assert len(set(files)) == len(files)
        for c in man["configs"]:
            assert c["file"].startswith("benchmark/configs/")
            held = harness.load_json(root, c["file"])
            assert held["name"] == c["name"]
            assert sorted(held["reduced"]) == sorted(c["reduced"])
        for m in man["per_layer"]:
            assert set(m) | {"workloads"} == METRIC_KEYS, m
            assert m["moves"] in e2e
            # each cell a metric lists reports the end-to-end metric it
            # moves (a metric that lists none is read in every such cell)
            assert set(m.get("workloads", [])) <= set(
                e2e[m["moves"]].get("workloads", cells)) <= set(cells), m
            reader = harness.load_module("metrics", m["name"])
            assert callable(reader.value)
            # a reader with nothing to read returns nothing
            assert reader.value({"programs": {}}, None) is None
    # every reader file has its entry: none is left behind, none unlisted
    on_disk = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(root, "benchmark", "metrics", "*.py"))}
    assert on_disk == {m["name"] for m in man["per_layer"]}


def what_the_benchmark_had_keeps_its_place(man, root):
    """Additions come after what PR 46's manifest held, which keeps its
    order; the window's length and the accepted bounds stay."""
    assert [m["name"] for m in man["per_layer"]][:len(PER_LAYER)] == PER_LAYER
    assert [w["name"] for w in man["workloads"]][:len(CELLS)] == CELLS
    assert [c["name"] for c in man["configs"]][:len(CONFIGS)] == CONFIGS
    assert [(m["name"], m["bound"]) for m in man["end_to_end"]][
        :len(END_TO_END)] == END_TO_END
    assert man["run_seconds"] == RUN_SECONDS


def the_scope_readers_metrics(man, root):
    """Each of the scope reader's metrics has its file, its entry and AT
    LEAST the cells it had; every cell it lists runs the program it reads
    (a serving role lists no training cell, and the other way round)."""
    entries = {m["name"]: m for m in man["per_layer"]}
    drivers = driver_of(man, root)
    for name, (layer, moves, cells, role, group) in BY_SCOPE.items():
        m = entries[name]
        path = os.path.join(root, "benchmark", "metrics", name + ".py")
        assert os.path.exists(path)
        assert (m["layer"], m["moves"]) == (layer, moves)
        assert set(cells) <= set(m["workloads"])
        assert len(set(m["workloads"])) == len(m["workloads"])
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["unit"] == ("%" if role is None else "ms")
        assert set(m) == METRIC_KEYS
        wanted = DRIVER_OF_ROLE[role] if role else name.rsplit(".", 1)[1]
        assert {drivers[w] for w in m["workloads"]} == {wanted}, name
        if role:
            with open(path) as f:
                assert f'"{role}", "{group}"' in f.read()


def the_reader_knows_the_programs_vocabulary(man, root):
    """Every scope the reader groups is one the program may open, and every
    scope the program may open is grouped: by ``GROUPS``, or by the file of
    a configuration that runs it. No declaration re-groups."""
    from bigdl_tpu.observability.tracing import DEVICE_SCOPES

    declared = {}
    for name, held in config_files(man, root).items():
        program_scopes.vocabulary(held.get("scopes"))   # raises if refused
        declared.update(held.get("scopes", {}))
    assert set(program_scopes.GROUPS) <= set(DEVICE_SCOPES)
    assert set(DEVICE_SCOPES) <= set(program_scopes.GROUPS) | set(declared)


ALL = (names_units_and_files_resolve, what_the_benchmark_had_keeps_its_place,
       the_scope_readers_metrics, the_reader_knows_the_programs_vocabulary)
