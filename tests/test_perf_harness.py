"""Perf harness smoke tests (≙ models/utils/LocalOptimizerPerf.scala's
throughput loop): the timed train step must run, report sane numbers, and
keep the RNG stream healthy."""

import jax.numpy as jnp
import pytest
import numpy as np

from bigdl_tpu.models.perf import _transformer_perf, run_perf


def test_run_perf_lenet_smoke():
    s = run_perf("lenet5", batch_size=4, iterations=2, warmup=1,
                 dtype=jnp.float32, log=lambda *a, **k: None)
    assert s["records_per_sec"] > 0
    assert np.isfinite(s["loss"])


def test_input_pipeline_perf_smoke():
    """records -> augments -> minibatch -> H2D feed bench runs both
    reader modes and reports sane records/sec."""
    from bigdl_tpu.models.perf import run_input_pipeline_perf

    rows = run_input_pipeline_perf(batch_size=8, n_records=32, image=64,
                                   crop=56, depths=(0, 2),
                                   log=lambda *a, **k: None)
    assert len(rows) >= 2  # python fallback always runs; native if built
    for r in rows:
        assert r["records"] == 32
        assert r["records_per_sec"] > 0
    assert any(not r["native_reader"] for r in rows)


def test_transformer_perf_tiny():
    s = _transformer_perf(batch_size=2, iterations=2, warmup=1,
                          dtype=jnp.float32, log=lambda *a, **k: None,
                          seq_len=16, vocab=50, embed_dim=16, layers=1,
                          heads=2, use_flash=False, master_f32=False)
    assert s["records_per_sec"] > 0
    # next-token CE on random tokens starts near ln(vocab)
    assert abs(s["loss"] - np.log(50)) < 1.0


@pytest.mark.parametrize("kv_heads", [None, 2])
def test_decode_perf_smoke(kv_heads):
    from bigdl_tpu.models.perf import run_decode_perf

    s = run_decode_perf(batch_size=2, num_kv_heads=kv_heads,
                        dtype=jnp.float32, log=lambda *a, **k: None)
    assert s["decode_tokens_per_sec"] > 0
    assert s["model"] == "transformer_lm_decode"
    assert s["num_kv_heads"] == (kv_heads or 4)  # CPU smoke uses 4 heads


def test_decode_perf_speculative_int8_draft():
    """The decode harness's int8-clone-draft path runs on CPU and reports
    its rate fields."""
    from bigdl_tpu.models.perf import run_decode_perf

    s = run_decode_perf(batch_size=2, dtype=jnp.float32,
                        spec_int8_draft=True, log=lambda *a, **k: None)
    assert s["speculative_draft_layers"] == "int8"
    assert s["spec_tokens_per_sec"] > 0
    assert 0.0 <= s["spec_accept_rate"] <= 1.0
    import pytest as _pytest

    with _pytest.raises(ValueError, match="pick one"):
        run_decode_perf(batch_size=2, speculative=1, spec_int8_draft=True,
                        log=lambda *a, **k: None)
    with _pytest.raises(ValueError, match="int8"):
        run_decode_perf(batch_size=2, int8=True, spec_int8_draft=True,
                        log=lambda *a, **k: None)


def test_generate_reuses_jitted_step_across_calls():
    # regression: generate() used to rebuild its jit wrappers per call,
    # recompiling every time (decode benchmarks measured compilation)
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(0)
    m = TransformerLM(32, embed_dim=16, num_heads=2, num_layers=1,
                      max_len=16)
    m.evaluate()
    prompt = jnp.ones((1, 4), jnp.int32)
    m.generate(prompt, 4)
    m.generate(prompt, 4)
    m.generate(prompt, 4, host_loop=True)
    m.generate(prompt, 4, host_loop=True)
    step_jit, prefill_jit, _chunk_jit, scan_jit = m._decode_fns()[:4]
    assert scan_jit._cache_size() == 1, scan_jit._cache_size()
    assert step_jit._cache_size() == 1, step_jit._cache_size()
    assert prefill_jit._cache_size() == 1


def _run_without_tpu(script, tmp_path, *args):
    """Run a repo entry point in a CPU child (no TPU visible), with the
    trend file and the compile cache pointed at tmp_path."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hist = tmp_path / "history.jsonl"
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu",
               BIGDL_BENCH_HISTORY=str(hist),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, script), *args],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)
    return proc, hist


def test_bench_training_headline_needs_a_tpu(tmp_path):
    """`python bench.py` without a TPU exits non-zero and emits no row —
    it never substitutes a model or a device."""
    proc, hist = _run_without_tpu("bench.py", tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr
    assert not hist.exists()


def test_chip_smoke_without_tpu_fails_with_ok_false(tmp_path):
    """chip_smoke.py without a TPU exits non-zero; its last line says
    ``"ok": false`` and no phase printed a result."""
    import json

    proc, _ = _run_without_tpu("chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, proc.stdout
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_fleet_bench_parent_opens_no_device_before_its_workers(tmp_path):
    """A chip belongs to one process: by the time `bench.py --serving
    --fleet` spawns its workers, the parent has imported the package and
    built its workload without opening any jax backend."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "from jax._src import xla_bridge\n"
        "import bench\n"
        "from bigdl_tpu.serving.fleet import benchmark\n"
        "class Reached(Exception): pass\n"
        "def spawn(*a, **k):\n"
        "    print('backend_open_at_spawn', bool(xla_bridge._backends))\n"
        "    raise Reached\n"
        "benchmark.spawn_worker_fleet = spawn\n"
        "try:\n"
        "    bench.main(['--serving', '--fleet', '2', '--requests', '6'])\n"
        "except Reached:\n"
        "    pass\n")
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               BIGDL_BENCH_HISTORY=str(tmp_path / "history.jsonl"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=300)
    assert "backend_open_at_spawn False" in proc.stdout, \
        proc.stdout + proc.stderr[-2000:]
