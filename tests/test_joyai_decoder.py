"""``HybridDecoderLM`` with latent-attention layers and routed experts (a
JoyAI-LLM-Flash-shaped model at a test's size, ``tests/joyai_tiny.py``)
against the plain reference on seeded weights: the whole forward pass, and
prefill then decode through pages at two chunkings; what the engine asks of
it and what its decode step hands out beside the tokens; its programs'
scopes; and a model without routed layers lowering to the step it had."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

import joyai_tiny  # noqa: E402
from benchmark.reference import joyai_llm_flash as reference  # noqa: E402

SEED, T, PAGE = 5, 64, 4


@pytest.fixture(scope="module")
def built():
    cfg = joyai_tiny.tiny_config(positions=T)
    model, w = joyai_tiny.built(cfg, SEED)
    ids = np.random.RandomState(3).randint(0, 120, (2, 48)).astype(np.int32)
    return cfg, model, w, ids, reference.forward(w, ids, cfg)


def test_the_whole_forward_pass_is_the_references(built):
    cfg, model, w, ids, want = built
    assert [b.kind for b in model._blocks()] == ["latent_attention"] * 3
    assert [b.routed for b in model._blocks()] == [False, True, True]
    np.testing.assert_allclose(np.asarray(model.forward(jnp.asarray(ids))),
                               want, atol=2e-4)
    # every planted fault moves the reference's own logits
    for fault in reference.FAULTS:
        off = reference.forward(w, ids[:1], cfg, fault=fault)
        assert np.abs(off - want[:1]).max() > 1e-3, fault
    with pytest.raises(ValueError, match="fault"):
        reference.forward(w, ids[:1], cfg, fault="none")


@pytest.mark.parametrize("chunk", [16, 8])
def test_prefill_then_decode_through_pages_is_the_references(built, chunk):
    cfg, model, w, ids, want = built
    n = T // PAGE
    pool = model.init_page_pool(1 + 2 * n, PAGE, dtype=jnp.float32)
    assert pool["lanes"] == [] and [p.shape for p in pool["pages"]] == [
        (1 + 2 * n, PAGE, 128)] * 3
    tables = jnp.asarray(1 + np.arange(2 * n, dtype=np.int32).reshape(2, n))
    got = []
    for c in range(0, 32, chunk):
        lg, pool = model.verify_chunk_paged(
            jnp.asarray(ids[:, c:c + chunk]), pool, tables,
            jnp.full((2,), c, jnp.int32))
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.concatenate(got, 1), want[:, :32],
                               atol=2e-4)
    step = jax.jit(lambda tok, pos, pool: model.decode_step_paged(
        tok, pos, pool, tables, active=jnp.ones((2,), bool), routing=True))
    for t in range(32, 48):
        lg, pool, counts = step(jnp.asarray(ids[:, t]),
                                jnp.full((2,), t, jnp.int32), pool)
        np.testing.assert_allclose(np.asarray(lg), want[:, t], atol=5e-4)
        held, touched, fullest, slots = (int(c) for c in counts)
        # two routed layers of 4 held experts; 2 rows x 4 choices a layer
        assert slots == 8 and 0 <= touched <= held <= 16
        assert touched <= 2 * fullest <= 2 * held or held == 0
    # a last chunk whose rows end at different tokens: logits at last_idx
    lg, _ = model.prefill_chunk_at_paged(
        jnp.asarray(ids[:, 32:48]), pool, tables,
        jnp.full((2,), 32, jnp.int32), jnp.asarray([15, 6], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg)[0], want[0, 47], atol=5e-4)
    np.testing.assert_allclose(np.asarray(lg)[1], want[1, 38], atol=5e-4)


def test_an_idle_row_takes_no_experts_slot(built):
    cfg, model, w, ids, _ = built
    n = T // PAGE
    pool = model.init_page_pool(1 + 2 * n, PAGE, dtype=jnp.float32)
    tables = jnp.asarray(1 + np.arange(2 * n, dtype=np.int32).reshape(2, n))
    tok, pos = jnp.asarray(ids[:, 0]), jnp.zeros((2,), jnp.int32)
    _, _, both = model.decode_step_paged(
        tok, pos, pool, tables, active=jnp.asarray([True, True]),
        routing=True)
    _, _, one = model.decode_step_paged(
        tok, pos, pool, tables, active=jnp.asarray([True, False]),
        routing=True)
    _, _, none = model.decode_step_paged(
        tok, pos, pool, tables, active=jnp.asarray([False, False]),
        routing=True)
    assert int(none[0]) == int(none[1]) == int(none[2]) == 0
    assert int(one[0]) <= int(both[0]) and int(both[3]) == 8


def test_what_the_engine_asks_of_the_model(built):
    cfg, model, _, _, _ = built
    assert model.has_lane_state is False and model.routed_layers == 2
    assert model.kv_token_elems() == 3 * 128
    assert model.decode_read_counts([5], 16) is None
    assert model.step_read_counts(np.array([5, 9]), PAGE, 16) == {
        "kv_read_tokens": 128, "kv_table_tokens": 128}
    # a table of 16 pages of 4 is one round of the chunk's key blocks
    assert model.prefill_read_counts(np.array([0]), 16, PAGE, 16) == {
        "kv_read_tokens": 64, "kv_table_tokens": 64}
    with pytest.raises(ValueError, match="latent layer's rows"):
        model.init_page_pool(8, PAGE, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="latent layer's leaf"):
        model.init_page_pool(8, PAGE, sharding=object())
    # the counts grow with the context by the latent rows alone, and a
    # token is charged the experts it is expected to choose, not all held
    d = model.embed_dim
    per_key = sum(2 * 4 * (20 + 16) for _ in range(3))
    assert model.analytic_flops(1, 100) - model.analytic_flops(1, 0) \
        == pytest.approx(per_key * 100)
    assert model.analytic_bytes(1, 100, 2) - model.analytic_bytes(1, 0, 2) \
        == pytest.approx(2 * 3 * 128 * 100)
    every = sum(int(a.size) for a in jax.tree.leaves(model.params_dict())) \
        - 120 * d
    held = 2 * 3 * 4 * 16 * d
    assert model.analytic_flops(1, 0) == pytest.approx(
        2 * (every - held * (1 - 4 / 16)))


def test_the_engine_serves_it_and_records_the_routing(built):
    """Through ``ContinuousBatchingEngine``: pages alone (no lane state, no
    snapshot store), a shared prefix is a hit on latent pages, the tokens
    are the reference's greedy choices, and every decode span carries the
    step's routing counts, summed into the instruments."""
    from bigdl_tpu import observability as obs
    from bigdl_tpu.serving import ContinuousBatchingEngine

    cfg, model, w, _, _ = built
    reg = obs.MetricRegistry()
    before = obs.set_default_registry(reg)
    try:
        eng = ContinuousBatchingEngine(
            model, max_slots=3, page_size=PAGE, max_pages=1 + 3 * 16 + 24,
            prefill_chunk=16, prefill_rows=2, service_name="joyai")
    finally:
        obs.set_default_registry(before)
    assert eng._lane_state is False and eng._routed == 2
    assert eng._snap_store is None
    rng = np.random.RandomState(9)
    head = rng.randint(0, 120, 32).astype(np.int32)
    prompts = [np.concatenate([head, rng.randint(0, 120, n).astype(np.int32)])
               for n in (5, 9)] + [rng.randint(0, 120, 21).astype(np.int32)]
    began = time.time_ns()
    eng.start()
    try:
        first = eng.submit(prompts[0], 6)
        out = [np.asarray(first.result(timeout=120))]
        rest = [eng.submit(p, 6) for p in prompts[1:]]
        out += [np.asarray(h.result(timeout=120)) for h in rest]
        assert rest[0].prefix_tokens == 32 and first.prefix_tokens == 0
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["jit_compiles"] == 4
    assert "state" not in stats["paging"]
    for prompt, served in zip(prompts, out):
        logits = reference.forward(w, served[None], cfg)[0]
        n = len(prompt)
        np.testing.assert_array_equal(
            logits[n - 1:len(served) - 1].argmax(-1), served[n:])
    spans = [r["attrs"] for r in obs.trace.export()
             if r["name"] == "serving/decode_dispatch"
             and r["start_ns"] >= began and "expert_slots" in r["attrs"]]
    assert len(spans) >= 10
    for a in spans:
        assert a["expert_slots"] == 8 and a["kv_read_tokens"] == 3 * 64
        assert a["experts_touched"] <= a["assignments_held"] <= 8 * a["rows"]
        assert a["expert_load_max"] <= 2 * a["rows"]
    total = lambda name: reg.get(
        f"bigdl_serving_routed_{name}_total").labels(service="joyai").get()
    for name in ("assignments_held", "experts_touched", "expert_load_max",
                 "expert_slots"):
        assert total(name) == sum(a[name] for a in spans), name


def _serve_tiny(model, prompts, new_tokens, on_tpu, page):
    """(rows served, stats()["paging"], the decode spans' attributes) of an
    engine over the tiny model with ``page`` tokens a page; ``on_tpu``: told
    at construction that its backend is a TPU, which is where it decides
    how the decode step reads its pages."""
    from unittest import mock

    from bigdl_tpu import observability as obs
    from bigdl_tpu.serving import ContinuousBatchingEngine

    began = time.time_ns()
    with mock.patch.object(jax, "default_backend",
                           (lambda: "tpu") if on_tpu
                           else jax.default_backend):
        eng = ContinuousBatchingEngine(model, max_slots=3, page_size=page,
                                       prefill_chunk=16, prefill_rows=2)
    with eng:
        handles = [eng.submit(p, new_tokens) for p in prompts]
        rows = [np.asarray(h.result(timeout=300)) for h in handles]
        paging = eng.stats()["paging"]
    spans = [r["attrs"] for r in obs.trace.export(
        names=["serving/decode_dispatch"]) if r["start_ns"] >= began]
    return rows, paging, spans


def test_engine_told_it_is_on_a_tpu_reads_the_latent_pages_by_the_kernel(
        built):
    """A latent layer's pool is one bare leaf; where the kernel can read it
    as it lies (whole tiles of floats: 8 float32 tokens a page, rows of 128)
    an engine on a TPU takes ``"kernel"`` for the whole model, serves the
    tokens the rows form serves (here through the interpreter), and its
    decode spans count each lane's pages up to its position under what the
    tables hold. On the CPU, and for pages of 4 tokens, it keeps
    ``"rows"`` and reads every table."""
    cfg, model, _, _, _ = built
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 120, n).astype(np.int32) for n in (21, 9)]
    want, paging, spans = _serve_tiny(model, prompts, 10, False, 8)
    whole = 3 * paging["table_len"] * 8
    assert paging["decode_attention"] == "rows"
    assert spans and all(a["kv_read_tokens"] == whole == a["kv_table_tokens"]
                         for a in spans)
    rows, paging, spans = _serve_tiny(model, prompts, 10, True, 8)
    assert paging["decode_attention"] == "kernel"
    for got, ref in zip(rows, want):
        np.testing.assert_array_equal(got, ref)
    reads = [a["kv_read_tokens"] for a in spans]
    assert spans and all(a["kv_table_tokens"] == whole for a in spans)
    # an idle lane one page, a live lane the pages up to where it stands
    assert all(r % 8 == 0 and 3 * 8 <= r < whole for r in reads), reads
    both = [a for a in spans if a["rows"] == 2]
    assert both and all(a["kv_read_tokens"] >= 8 + 16 + 24 for a in both)
    assert paging["decode_kv_read_tokens"] == sum(reads) \
        < paging["decode_kv_table_tokens"] == len(spans) * whole
    _, paging, _ = _serve_tiny(model, prompts[:1], 2, True, 4)
    assert paging["decode_attention"] == "rows"


def test_the_decode_form_is_decided_for_the_model_as_a_whole(built, caplog):
    """One word reaches every layer that takes it, so one pool entry the
    kernel cannot read as it lies keeps the whole model on the gathered
    form, and the engine's log names that entry: a pair beside a latent
    leaf, both of whole tiles, is the kernel's; a narrow pair, a leaf of 576
    columns, or int8 codes beside a good leaf is not. A selecting layer's
    pool (a dict) has its own step and no say."""
    from unittest import mock

    from bigdl_tpu.serving import ContinuousBatchingEngine

    _, model, _, _, _ = built
    eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                   prefill_chunk=16, prefill_rows=2)
    sd = lambda cols, dtype="bfloat16", page=16: jax.ShapeDtypeStruct(
        (9, page, cols), jnp.dtype(dtype))
    pair, leaf, select = (sd(1280), sd(1280)), sd(640), {"k": sd(64)}
    cases = [([leaf, leaf], "kernel"), ([pair, leaf], "kernel"),
             ([select, leaf, pair], "kernel"), ([pair], "kernel"),
             ([(sd(64), sd(64)), leaf], "rows"), ([pair, sd(576)], "rows"),
             ([leaf, sd(640, page=8)], "rows"),
             ([(sd(1280, "int8"),) * 4, leaf], "rows"),
             ([sd(640, "int8")], "rows"), ([select], "rows"), ([], "rows")]
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            for pages, want in cases:
                caplog.clear()
                assert eng._decode_form(pages) == want, pages
                said = [r.getMessage() for r in caplog.records]
                # ... and only a model with such an entry is spoken of
                assert len(said) == (want == "rows" and any(
                    not isinstance(g, dict) for g in pages)), said
                assert eng._decode_form(
                    {"pages": pages, "lanes": []}) == want, pages
            caplog.clear()
            eng._decode_form([select, pair, sd(576)])
            assert "pool entry 1 of 2 as it lies (bfloat16[9, 16, 576])" \
                in caplog.records[0].getMessage()
        assert eng._decode_form([leaf]) == "rows"           # the CPU
    finally:
        eng.stop()


def test_the_programs_name_the_new_parts(built):
    from benchmark import harness, program_scopes
    from bigdl_tpu.serving import ContinuousBatchingEngine
    from test_device_scopes import SERVING, op_names

    cfg, model, _, _, _ = built
    declared = harness.load_json(
        harness.HERE, "configs", "joyai-llm-flash.json")["scopes"]
    groups = program_scopes.vocabulary(declared)
    eng = ContinuousBatchingEngine(model, max_slots=3, prefill_chunk=16,
                                   prefill_rows=2, page_size=PAGE)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    texts = {
        # a routed model's step is told which rows are live
        "step": eng._step_jit.lower(
            eng._params, eng._buffers, i32(3), i32(3), eng._kv_pool,
            i32(3, eng._table_len), jax.random.PRNGKey(0), jnp.float32(1.0),
            jnp.ones((3,), bool)).compile().as_text(),
        "chunk": eng._chunk_jit.lower(
            eng._params, eng._buffers, i32(2, 16), eng._kv_pool,
            i32(2, eng._table_len), i32(2), i32(2)).compile().as_text()}
    own = {"step": {"mla/absorb", "moe/route", "moe/experts", "moe/shared",
                    "attn/attend", "sample"},
           "chunk": {"mla/expand", "moe/route", "moe/experts", "moe/shared",
                     "attn/attend"}}
    for program, want in own.items():
        names = [n for n in op_names(texts[program])
                 if n and n.startswith(("jit(", "pjit("))]
        found = {program_scopes.scope_of(n, groups) for n in names}
        want = want | set(SERVING) - {"sample"}
        assert want <= found, (program, sorted(want - found))
        loose = [n for n in names
                 if program_scopes.scope_of(n, groups) is None]
        assert len(loose) / len(names) < 0.02, sorted(set(loose))
    assert {program_scopes.group_of(s, groups) for s in declared} == {
        "route", "experts", "attend"}


def test_a_model_without_routed_layers_lowers_to_the_step_it_had():
    """The engine's decode step of a model with no routed layer is, text
    for text, the program PR 47's engine built: ``decode_step_paged``
    called as it was, the sampled tokens alone handed out."""
    import hybrid_tiny
    from bigdl_tpu.nn.module import bind
    from bigdl_tpu.serving import ContinuousBatchingEngine

    model, _ = hybrid_tiny.built(hybrid_tiny.tiny_config(theta=10000.0), 3)
    assert model.routed_layers == 0 and model.has_lane_state is True
    eng = ContinuousBatchingEngine(model, max_slots=3, page_size=4,
                                   max_pages=80, prefill_chunk=8,
                                   prefill_rows=2)
    attend = eng._decode_attention

    def step(p, bufs, tok, pos, pool, tables, rng, temperature, *active):
        with bind(model, p, bufs, False, None):
            logits, pool = model.decode_step_paged(
                tok, pos, pool, tables, decode_attention=attend,
                active=active[0])
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    z = jnp.zeros((3,), jnp.int32)
    args = (eng._params, eng._buffers, z, z, eng._kv_pool,
            eng._slot_tables(), jax.random.PRNGKey(0), eng._temp(),
            jnp.zeros((3,), bool))
    had = jax.jit(step, donate_argnums=(4,)).lower(*args).as_text()
    assert eng._step_jit.lower(*args).as_text() == had
    assert "moe/" not in had
