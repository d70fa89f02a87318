"""The programs name their parts: every program the serving engine and the
optimizer dispatch opens ``jax.named_scope``s from one vocabulary
(``bigdl_tpu.observability.tracing.DEVICE_SCOPES``) and ``Module.__call__``
opens the layer's class, so the compiled program's HLO says, instruction by
instruction, which part of the model it belongs to. Checked on the CPU from
``lower(...).compile().as_text()``, by the rule the benchmark's reader
charges an operation by (``benchmark.program_scopes.scope_of``)."""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.program_scopes import scope_of  # noqa: E402
from bigdl_tpu.observability.tracing import DEVICE_SCOPES  # noqa: E402

#: instructions that do no work of their own
TRIVIAL = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
INSTRUCTION = re.compile(r"\s*(?:ROOT )?%\S+ = .*?[\]})] ([a-z][a-z\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')

SERVING = ("embed", "attn/qkv", "attn/kv_write", "attn/kv_gather",
           "attn/out", "mlp", "norm", "head", "sample")


def op_names(text):
    """The ``op_name`` (or None) of every non-trivial instruction of a
    compiled module's text."""
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and m.group(1) not in TRIVIAL:
            name = OP_NAME.search(line)
            out.append(name.group(1) if name else None)
    return out


def unscoped_share(names):
    """Of the instructions the SOURCE traced (their ``op_name`` is a path
    from the jitted function down; what the CPU compiler adds of its own
    has none), the share under no scope."""
    traced = [n for n in names if n and n.startswith(("jit(", "pjit("))]
    return sum(scope_of(n) is None for n in traced) / len(traced)


def engine_texts(model, **kw):
    """The compiled text of the engine's own ``step`` and ``chunk`` at the
    geometry ``kw`` gives."""
    from bigdl_tpu.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(model, **kw)
    try:
        s, r, c = eng.max_slots, eng._policy.prefill_rows, eng._policy.chunk
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
        lane = eng._lane_state
        step = eng._step_jit.lower(
            eng._params, eng._buffers, i32(s), i32(s), eng._kv_pool,
            i32(s, eng._table_len), jax.random.PRNGKey(0), jnp.float32(1.0),
            *((jnp.ones((s,), bool),) if lane else ()))
        chunk = eng._chunk_jit.lower(
            eng._params, eng._buffers, i32(r, c), eng._kv_pool,
            i32(r, eng._table_len), i32(r), i32(r),
            *((i32(r),) if lane else ()))
        return {"step": step.compile().as_text(),
                "chunk": chunk.compile().as_text()}
    finally:
        eng.stop()


def tiny_lm():
    from bigdl_tpu.models.transformer import TransformerLM

    m = TransformerLM(32, embed_dim=16, num_heads=4, num_kv_heads=2,
                      num_layers=2, max_len=64)
    m.evaluate()
    return m, dict(max_slots=2, prefill_chunk=4, prefill_rows=2)


def tiny_gdn():
    import hybrid_tiny

    model, _ = hybrid_tiny.built(hybrid_tiny.tiny_config(positions=128), 7)
    return model, dict(max_slots=3, prefill_chunk=8, prefill_rows=2,
                       page_size=4)


def tiny_sala():
    import sala_tiny

    model, _ = sala_tiny.built(
        sala_tiny.tiny_config(positions=192, layers=5), 9)
    return model, dict(max_slots=3, prefill_chunk=4, prefill_rows=2,
                       page_size=4)


@pytest.mark.parametrize("build, step_only, chunk_only", [
    (tiny_lm, ("attn/attend",), ("attn/attend",)),
    (tiny_gdn, ("attn/attend", "gdn/step"), ("attn/attend", "gdn/chunk")),
    (tiny_sala, ("sparse/select", "sparse/attend", "lightning/step"),
     ("sparse/select", "sparse/attend", "lightning/chunk")),
], ids=["transformer", "gated_delta", "block_sparse"])
def test_the_engines_programs_name_their_parts(build, step_only, chunk_only):
    """Every vocabulary scope the model should show is in the engine's
    ``step`` and ``chunk``, and under 2 % of the instructions the source
    traced are under no scope."""
    model, kw = build()
    texts = engine_texts(model, **kw)
    for program, own in (("step", step_only), ("chunk", chunk_only)):
        names = op_names(texts[program])
        found = {scope_of(n) for n in names}
        want = set(SERVING) | set(own)
        if program == "chunk":
            want.discard("sample")      # the first token is sample0's
        assert want <= found, (program, sorted(want - found))
        assert unscoped_share(names) < 0.02, (
            program, sorted({n for n in names if n and n.startswith("jit(")
                             and scope_of(n) is None}))


def test_the_first_token_sampler_is_named():
    model, kw = tiny_lm()
    from bigdl_tpu.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(model, **kw)
    try:
        text = eng._sample0_jit.lower(
            jnp.zeros((2, 32)), jax.random.PRNGKey(0),
            jnp.float32(1.0)).compile().as_text()
    finally:
        eng.stop()
    assert "jit_sample0" in text            # the program keeps its name
    assert {scope_of(n) for n in op_names(text)
            if n and n.startswith("jit(")} == {"sample"}


def conv_net():
    from bigdl_tpu import nn

    return nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1),
        nn.SpatialBatchNormalization(8), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2), nn.Reshape([8 * 4 * 4]),
        nn.Linear(8 * 4 * 4, 10), nn.LogSoftMax())


def train_step(model):
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    ts = make_train_step(
        model, nn.ClassNLLCriterion(),
        SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4),
        grad_clip={"l2norm": 1.0}, compute_dtype=jnp.bfloat16)
    p, b = model.params_dict(), model.buffers_dict()
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, 3, 8, 8)), jnp.bfloat16)
    y = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    return ts, (p, b, ts.init_slots(p), x, y, ts.current_lrs(),
                jax.random.PRNGKey(0))


def test_the_train_step_names_layers_forward_and_backward():
    """Through ``make_train_step`` a layer's class is on its forward AND
    its backward instructions (``transpose(jvp(Class))``), the custom-vjp
    BatchNorm backward included; the criterion and the update carry
    theirs."""
    ts, args = train_step(conv_net())
    names = [n for n in op_names(jax.jit(ts.step_with_stats).lower(
        *args).compile().as_text()) if n]
    assert jax.jit(ts.step_with_stats).__name__ == "_core"
    for cls in ("SpatialConvolution", "SpatialBatchNormalization", "ReLU",
                "SpatialMaxPooling", "Linear", "LogSoftMax"):
        forward = [n for n in names if f"jvp({cls})" in n
                   and "transpose(" not in n]
        backward = [n for n in names if f"transpose(jvp({cls}))" in n]
        assert forward and backward, cls
        assert {scope_of(n) for n in forward + backward} == {cls}
    assert any("conv_general_dilated" in n for n in names
               if "transpose(jvp(SpatialConvolution))" in n)
    assert "optim/update" in {scope_of(n) for n in names}
    assert "optim/loss" in {scope_of(n) for n in names}
    # the masters' cast to the compute dtype goes with the update
    assert any(n.endswith("optim/update)/convert_element_type")
               or "/optim/update/convert_element_type" in n for n in names)
    assert unscoped_share(names) < 0.02


def run_with_scopes(scopes_on, monkeypatch):
    """The tiny engine's step and chunk outputs and the conv net's train
    step outputs, each as numpy trees, with the scopes' context managers
    in place or patched out (fresh jits either way)."""
    if not scopes_on:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    jax.clear_caches()
    from bigdl_tpu.serving import ContinuousBatchingEngine
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(11)
    model, kw = tiny_lm()
    eng = ContinuousBatchingEngine(model, **kw)
    try:
        text = eng._step_jit.lower(
            eng._params, eng._buffers, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), eng._kv_pool,
            jnp.zeros((2, eng._table_len), jnp.int32),
            jax.random.PRNGKey(0), jnp.float32(1.0)).as_text(
                debug_info=True)
        ids = jnp.asarray([[3, 4, 5, 6], [7, 8, 9, 10]], jnp.int32)
        tables = jnp.asarray(
            np.arange(1, 1 + 2 * eng._table_len).reshape(2, -1), jnp.int32)
        logits, pool = eng._chunk_jit(
            eng._params, eng._buffers, ids, eng._kv_pool, tables,
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 3, jnp.int32))
        nxt, pool = eng._step_jit(
            eng._params, eng._buffers, jnp.asarray([11, 12], jnp.int32),
            jnp.asarray([4, 4], jnp.int32), pool, tables,
            jax.random.PRNGKey(0), jnp.float32(1.0))
        served = jax.tree.map(np.asarray, (logits, nxt, pool))
    finally:
        eng.stop()
    rnd.set_seed(12)
    ts, args = train_step(conv_net())
    trained = jax.tree.map(np.asarray, jax.jit(ts.step_with_stats)(*args))
    return text, served, trained


def test_the_scopes_change_nothing_but_names(monkeypatch):
    """The programs' outputs are bit-identical with the scopes' context
    managers patched out: a scope is a name on the HLO and nothing else."""
    with monkeypatch.context() as mp:
        text_off, served_off, trained_off = run_with_scopes(False, mp)
    text_on, served_on, trained_on = run_with_scopes(True, monkeypatch)
    assert "attn/kv_gather" in text_on and "attn/" not in text_off
    for a, b in zip(jax.tree.leaves((served_on, trained_on)),
                    jax.tree.leaves((served_off, trained_off))):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b, equal_nan=True)


def test_every_scope_opened_in_the_source_is_of_the_vocabulary():
    """``grep named_scope``: no program file opens a name outside
    ``DEVICE_SCOPES`` (``Module.__call__`` opens the class)."""
    opened = set()
    for root, _, files in os.walk(os.path.join(ROOT, "bigdl_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f), encoding="utf-8") as fh:
                    src = fh.read()
                opened |= set(re.findall(
                    r'(?:named_scope|scoped)\(\s*"([^"]+)"', src))
    assert opened and opened <= set(DEVICE_SCOPES), (
        sorted(opened - set(DEVICE_SCOPES)))
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)


def test_the_cache_key_holds_the_names(tmp_path, monkeypatch):
    """``enable_persistent_cache`` puts the HLO's metadata into the
    persistent cache's key: an executable compiled under other scope names
    is never handed back for this source."""
    from bigdl_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    before = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key")}
    try:
        compile_cache.enable_persistent_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
