"""``HybridDecoderLM`` against the benchmark's plain reference at a small
size (seeded random weights through the adapter's own mapping): the full
forward, and prefill then decode through the paged cache and the lane state.
Beside it, what the model asked of ``Module``: gradients allocated on first
use, and construction as shapes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.nn.module import abstract_init  # noqa: E402
from hybrid_tiny import built, tiny_config  # noqa: E402


@pytest.mark.parametrize("theta", [None, 10000.0])
def test_full_forward_equals_the_reference(theta):
    from benchmark.reference import olmo_hybrid as ref

    config = tiny_config(theta)
    model, w = built(config, 3)
    ids = np.random.RandomState(0).randint(0, 120, (2, 50))
    want = ref.forward(w, ids, config)
    got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # position matters with and without rotation: the convolutions and
    # decays carry it
    rolled = ref.forward(w, np.roll(ids, 1, axis=1), config)
    assert np.abs(rolled[:, 1:] - want[:, :-1]).max() > 1e-2 * np.abs(want).max()


def test_prefill_then_decode_through_the_cache_equals_the_reference():
    """Ragged rows: prompts of 19 and 13 in chunks of 8 (the last chunk
    right-padded) into lanes 2 and 0 of a 4-lane pool, then 6 decode steps
    with lane 1 inactive; logits at every position against the reference's
    full forward over the same tokens."""
    from benchmark.reference import olmo_hybrid as ref

    config = tiny_config()
    model, w = built(config, 4)
    rng = np.random.RandomState(1)
    lens, new = [19, 13], 6
    ids = rng.randint(0, 120, (2, 19 + new))
    want = ref.forward(w, ids, config)
    pool = model.init_page_pool(1 + 2 * 16, 4, lanes=4)
    assert set(pool) == {"pages", "lanes"}
    assert len(pool["pages"]) == 1 and len(pool["lanes"]) == 3
    tables = jnp.asarray(1 + np.arange(32).reshape(2, 16), jnp.int32)
    lanes = jnp.asarray([2, 0], jnp.int32)
    for c in range(0, 24, 8):
        chunk = np.zeros((2, 8), np.int32)
        last = np.zeros((2,), np.int32)
        for r, n in enumerate(lens):
            m = max(0, min(8, n - c))
            chunk[r, :m] = ids[r, c:c + m]
            last[r] = max(m - 1, 0)
        done = [n <= c for n in lens]
        before = pool
        logits, pool = model.prefill_chunk_at_paged(
            jnp.asarray(chunk), pool, tables, jnp.full((2,), c, jnp.int32),
            jnp.asarray(last),
            lanes=jnp.where(jnp.asarray(done), 3, lanes))   # 3: scratch
        for r, n in enumerate(lens):
            if c < n <= c + 8:      # the row's last real position
                assert np.abs(np.asarray(logits[r]) - want[r, n - 1]).max() \
                    < 1e-4 * np.abs(want).max()
            if done[r]:             # a finished row's lane is not touched
                for old, now in zip(before["lanes"], pool["lanes"]):
                    assert np.array_equal(old[0][int(lanes[r])],
                                          now[0][int(lanes[r])])
    # decode: row r is lane r, so move the states where the engine's slots
    # would have them: lane 0 <- row 1's (already there), lane 2 <- row 0's
    active = jnp.asarray([True, False, True, False])
    order = [1, None, 0, None]          # lane -> row
    pos = np.asarray([lens[1], 0, lens[0], 0])
    step_tables = jnp.zeros((4, 16), jnp.int32).at[0].set(tables[1]) \
        .at[2].set(tables[0])
    idle = [np.asarray(s[1]) for s, _ in pool["lanes"]]
    for i in range(new):
        tok = np.zeros((4,), np.int32)
        for lane, r in enumerate(order):
            if r is not None:
                tok[lane] = ids[r, lens[r] + i] if lens[r] + i < ids.shape[1] \
                    else 0
        logits, pool = model.decode_step_paged(
            jnp.asarray(tok), jnp.asarray(pos + i), pool, step_tables,
            active=active)
        for lane, r in enumerate(order):
            if r is not None and lens[r] + i < ids.shape[1]:
                assert np.abs(np.asarray(logits[lane])
                              - want[r, lens[r] + i]).max() \
                    < 1e-4 * np.abs(want).max()
    for before, (s, _) in zip(idle, pool["lanes"]):
        assert np.array_equal(before, np.asarray(s[1]))   # bit for bit


def test_verify_chunk_gives_logits_at_every_position():
    from benchmark.models import olmo_hybrid as adapter
    from benchmark.reference import olmo_hybrid as ref

    config = tiny_config()
    model, w = built(config, 5)
    rows = np.random.RandomState(2).randint(0, 120, (3, 64))
    want = ref.forward(w, rows, config)
    got = adapter.paged_logits(model, None, config, rows)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_what_the_pool_refuses():
    model, _ = built(tiny_config(), 6)
    with pytest.raises(ValueError, match="kv_dtype"):
        model.init_page_pool(9, 4, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="mesh"):
        model.kv_page_pool_sharding(None)
    assert model.kv_token_elems() == 2 * 1 * 4 * 8
    assert model.analytic_flops(4, 10) > 0 and model.analytic_bytes(4, 10) > 0


# ----------------------------------------------------------------- Module
def test_gradients_are_allocated_by_the_training_path_only():
    m = nn.Sequential(nn.Linear(4, 3), nn.Tanh(), nn.Linear(3, 2))
    held = lambda: [g for _, c in m.named_modules()
                    for g in c._gradients.values()]
    assert held() and all(g is None for g in held())
    m.evaluate()
    x = jnp.ones((5, 4))
    m(x)
    m.zero_grad_parameters()
    assert all(g is None for g in held())          # serving never pays
    out = m(x)
    m.backward(x, jnp.ones_like(out))
    assert all(g is not None for g in held())
    ws, gs = m.parameters()
    assert [g.shape for g in gs] == [w.shape for w in ws]
    first = np.asarray(gs[0])
    m.backward(x, jnp.ones_like(out))              # accumulates
    assert np.allclose(np.asarray(m.parameters()[1][0]), 2 * first)
    m.zero_grad_parameters()
    assert float(jnp.abs(m.parameters()[1][0]).max()) == 0.0
    # a fresh model's flat view and update still read zeros
    fresh = nn.Linear(2, 2)
    w0 = np.asarray(fresh.weight)
    assert float(jnp.abs(fresh.get_parameters()[1]).max()) == 0.0
    fresh.update_parameters(0.5)
    assert np.array_equal(np.asarray(fresh.weight), w0)
    assert set(fresh.grads_dict()["~params"]) == {"weight", "bias"}


def test_abstract_init_builds_shapes_and_moves_no_stream():
    from bigdl_tpu.models.hybrid import HybridDecoderLM
    from bigdl_tpu.utils import random as rnd

    kinds = ("linear_attention", "full_attention")
    make = lambda: HybridDecoderLM(50, 16, 2, kinds, 24, 32,
                                   linear_heads=2, linear_key_dim=4,
                                   linear_value_dim=8)
    rnd.set_seed(11)
    before = np.asarray(rnd.RNG.peek_key())
    shapes = abstract_init(make)
    assert np.array_equal(before, np.asarray(rnd.RNG.peek_key()))
    leaves = jax.tree.leaves(shapes.params_dict())
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
    real = make()
    assert jax.tree.structure(real.params_dict()) == jax.tree.structure(
        shapes.params_dict())
    assert [a.shape for a in jax.tree.leaves(real.params_dict())] == \
        [a.shape for a in leaves]
    # loaded in another dtype, it holds that dtype and nothing float32
    served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), real.params_dict())
    shapes.load_params_dict(served)
    shapes.evaluate(), real.evaluate()
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 50, (1, 9)))
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree.leaves(shapes.params_dict()))
    assert shapes(ids).dtype == jnp.float32    # products are kept float32
    real.load_params_dict(served)
    assert np.array_equal(np.asarray(shapes(ids), np.float32),
                          np.asarray(real(ids), np.float32))
    # a leaf left as a shape fails at first use, not silently
    broken = abstract_init(make)
    with pytest.raises(Exception):
        broken(ids)
    # nothing of the trace leaks: ordinary construction still works after
    assert isinstance(nn.Linear(2, 2).weight, jax.Array)


# ------------------------------------------- the four kinds and two styles
def test_an_olmo_model_gives_the_logits_it_gave_before_the_class_took_more_kinds():
    """Pinned from the commit before ``HybridDecoderLM`` learned the
    lightning and sparse kinds, the pre-norm block and the muP scales (the
    same script ran there and here, bit for bit equal on this host): the
    full forward, a ragged prefill chunk and a decode step."""
    model, _ = built(tiny_config(theta=10000.0), 11)
    ids = np.random.RandomState(5).randint(0, 120, (2, 40))
    out = np.asarray(model(jnp.asarray(ids)))
    np.testing.assert_allclose(
        out[1, 39, :4], [1.0124329328536987, 1.200801968574524,
                         -1.2062780857086182, -0.007069682236760855],
        rtol=2e-5, atol=2e-6)
    assert float(np.abs(out).sum()) == pytest.approx(8832.3662109375, rel=1e-5)
    pool = model.init_page_pool(1 + 2 * 16, 4, lanes=3)
    tables = jnp.asarray(1 + np.arange(32).reshape(2, 16), jnp.int32)
    lg, pool = model.prefill_chunk_at_paged(
        jnp.asarray(ids[:, :8]), pool, tables, jnp.zeros((2,), jnp.int32),
        jnp.asarray([7, 5]), lanes=jnp.asarray([0, 1]))
    np.testing.assert_allclose(
        np.asarray(lg).ravel()[:3], [-0.29622891545295715, 0.8449987769126892,
                                     1.556283950805664], rtol=2e-5, atol=2e-6)
    lg2, pool = model.decode_step_paged(
        jnp.asarray(ids[:, 8]), jnp.asarray([8, 6]), pool, tables,
        active=jnp.asarray([True, True]))
    assert np.isfinite(np.asarray(lg2)).all()
    assert model.block0.style == "post_norm" and model.embed_scale == 1.0


def test_sala_full_forward_equals_the_reference():
    from benchmark.reference import minicpm_sala as ref
    from sala_tiny import built as sala_built, tiny_config as sala_config

    config = sala_config()
    model, w = sala_built(config, 3)
    assert [b.kind for b in model._blocks()] == [
        "lightning_attention", "sparse_attention", "lightning_attention",
        "lightning_attention"]
    assert model.block0.style == "pre_norm"
    assert model.block0.residual_scale == pytest.approx(1.4 / 8 ** 0.5)
    assert model.embed_scale == 12 and model.logit_scale == 8 / 32
    ids = np.random.RandomState(0).randint(0, 120, (2, 150))
    want = ref.forward(w, ids, config)
    got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # the sparse layers' rule is seen: the reference that attends densely
    # everywhere and the one that takes the forced blocks only read apart
    for rule in ("dense", "forced"):
        other = ref.forward(w, ids, config, sparse_rule=rule)
        assert np.abs(other[:, :64] - want[:, :64]).max() \
            < 1e-5 * np.abs(want).max()            # under dense_len: the same
        assert np.abs(other - want).max() > 1e-3 * np.abs(want).max(), rule


def test_sala_prefill_then_decode_through_the_cache_equals_the_reference():
    """Ragged rows: prompts of 101 and 77 in chunks of 16 (the last chunk
    right-padded) into lanes 2 and 0 of a 4-lane pool, then 24 decode steps
    with lane 1 inactive; past ``dense_len`` (64) and ``topk`` (96 tokens);
    logits at every position against the reference's full forward."""
    from benchmark.reference import minicpm_sala as ref
    from sala_tiny import built as sala_built, tiny_config as sala_config

    config = sala_config()
    model, w = sala_built(config, 4)
    rng = np.random.RandomState(1)
    lens, new = [101, 77], 24
    ids = rng.randint(0, 120, (2, 101 + new))
    want = ref.forward(w, ids, config)
    tol = 1e-4 * np.abs(want).max()
    pages = 32
    pool = model.init_page_pool(1 + 2 * pages, 4, lanes=4)
    assert set(pool) == {"pages", "lanes"}
    assert len(pool["pages"]) == 1 and len(pool["lanes"]) == 3
    assert set(pool["pages"][0]) == {"k", "v", "ck"}
    tables = jnp.asarray(1 + np.arange(2 * pages).reshape(2, pages), jnp.int32)
    lanes = jnp.asarray([2, 0], jnp.int32)
    chunk_fn = jax.jit(model.prefill_chunk_at_paged)
    step_fn = jax.jit(model.decode_step_paged)
    for c in range(0, 112, 16):
        chunk = np.zeros((2, 16), np.int32)
        last = np.zeros((2,), np.int32)
        for r, n in enumerate(lens):
            m = max(0, min(16, n - c))
            chunk[r, :m] = ids[r, c:c + m]
            last[r] = max(m - 1, 0)
        done = [n <= c for n in lens]
        logits, pool = chunk_fn(
            jnp.asarray(chunk), pool, tables, jnp.full((2,), c, jnp.int32),
            jnp.asarray(last), lanes=jnp.where(jnp.asarray(done), 3, lanes))
        for r, n in enumerate(lens):
            if c < n <= c + 16:
                assert np.abs(np.asarray(logits[r]) - want[r, n - 1]).max() < tol
    active = jnp.asarray([True, False, True, False])
    order = [1, None, 0, None]          # lane -> row
    pos = np.asarray([lens[1], 0, lens[0], 0])
    step_tables = jnp.zeros((4, pages), jnp.int32).at[0].set(tables[1]) \
        .at[2].set(tables[0])
    idle = [np.asarray(jax.tree.leaves(s)[0][1]) for s in pool["lanes"]]
    for i in range(new):
        tok = np.zeros((4,), np.int32)
        for lane, r in enumerate(order):
            if r is not None:
                tok[lane] = ids[r, lens[r] + i]
        logits, pool = step_fn(jnp.asarray(tok), jnp.asarray(pos + i), pool,
                               step_tables, active=active)
        for lane, r in enumerate(order):
            if r is not None:
                assert np.abs(np.asarray(logits[lane])
                              - want[r, lens[r] + i]).max() < tol
    for before, s in zip(idle, pool["lanes"]):
        assert np.array_equal(before, np.asarray(jax.tree.leaves(s)[0][1]))


def test_sala_verify_chunk_and_what_the_engine_asks():
    from benchmark.models import minicpm_sala as adapter
    from benchmark.reference import minicpm_sala as ref
    from sala_tiny import built as sala_built, tiny_config as sala_config

    config = sala_config(positions=128)
    model, w = sala_built(config, 5)
    rows = np.random.RandomState(2).randint(0, 120, (3, 128))
    want = ref.forward(w, rows, config)
    got = adapter.paged_logits(model, None, config, rows)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # one sparse layer: K and V of 2 KV heads of 8, and a quarter of a
    # compressed key a token (one of 16 elements a page of 4)
    assert model.kv_token_elems() == 2 * 2 * 8 + 2 * 8 / 4
    # topk 6 blocks hold dense_len's 4: one gather of 6 blocks a row
    assert model.decode_read_counts([10, 63, 64, 200], 32) == {
        "attended_tokens": 11 + 64 + 65 + (96 - 7),
        "gathered_tokens": 4 * 6 * 16,
        "cached_tokens": 11 + 64 + 65 + 201, "selecting_rows": 2}
    assert built(tiny_config(), 6)[0].decode_read_counts([5], 32) is None
    # the selected tokens are what the analytic counts charge: a query
    # over 4000 cached tokens reads K and V of 96, over 100 of 84 (six
    # blocks less the 12 tokens ahead of it), and every compressed key
    far, near = model.analytic_bytes(1, 4000), model.analytic_bytes(1, 100)
    assert far - near == pytest.approx(
        2 * (2 * 2 * 8 * (96 - 84) + 2 * 8 * (4000 - 100) / 4))
    assert model.analytic_flops(1, 4000) > model.analytic_flops(1, 100)


def test_what_the_class_refuses_of_its_configuration():
    from bigdl_tpu.models.hybrid import HybridDecoderLM

    with pytest.raises(ValueError, match="layer type"):
        HybridDecoderLM(50, 16, 2, ("window_attention",), 24, 32)
    with pytest.raises(ValueError, match="block style"):
        HybridDecoderLM(50, 16, 2, ("full_attention",), 24, 32,
                        block_style="sandwich")
