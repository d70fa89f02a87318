"""``nn.LatentAttention``: the expanded chunk and the weight-absorbed decode
step over ONE page leaf against the layer's whole-sequence form and against
the plain reference's attention; the step through the paged-attention kernel
(the TPU interpreter) against its gathered rows form; the leaf's shape and
what it holds."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import joyai_llm_flash as reference  # noqa: E402
from bigdl_tpu import nn  # noqa: E402
from bigdl_tpu.utils import random as rnd  # noqa: E402

D, H, QR, KR, NOPE, ROPE, V = 32, 4, 24, 16, 8, 4, 8
PAGE, T = 4, 48


@pytest.fixture(scope="module")
def layer():
    rnd.set_seed(11)
    m = nn.LatentAttention(D, H, QR, KR, NOPE, ROPE, V, rope_theta=10000.0)
    tree = m.params_dict()
    rng = np.random.default_rng(5)
    shake = lambda a: (0.3 * rng.standard_normal(a.shape) if a.ndim == 2
                       else 1 + 0.4 * rng.standard_normal(a.shape))
    m.load_params_dict(jax.tree.map(
        lambda a: jnp.asarray(shake(a), jnp.float32), tree))
    return m.evaluate()


@pytest.fixture(scope="module")
def rows():
    return jnp.asarray(np.random.default_rng(7).standard_normal((2, T, D)),
                       jnp.float32)


def tables_and_leaf(layer, batch=2):
    n = T // PAGE
    tables = jnp.asarray(1 + np.arange(batch * n, dtype=np.int32)
                         .reshape(batch, n))
    return tables, layer.init_page_pool(1 + batch * n, PAGE)


def test_the_leaf_is_one_array_of_whole_lanes_without_a_head_axis(layer):
    leaf = layer.init_page_pool(9, PAGE, jnp.bfloat16)
    assert layer.row_elems == KR + ROPE == 20 and layer.row_width == 128
    assert leaf.shape == (9, PAGE, 128) and leaf.dtype == jnp.bfloat16
    wide = nn.LatentAttention(2048, 32, 1536, 512, 128, 64, 128)
    assert (wide.row_elems, wide.row_width) == (576, 640)
    assert wide.scale == pytest.approx(192 ** -0.5)


def test_the_whole_form_is_the_references_attention(layer, rows):
    p = layer.params_dict()
    w = {"mixer_norm_g": jnp.ones((D,)),
         "q_a_w": p["q_a"]["~params"]["weight"],
         "q_a_norm_g": p["q_a_norm"]["~params"]["weight"],
         "q_b_w": p["q_b"]["~params"]["weight"],
         "kv_a_w": p["kv_a"]["~params"]["weight"],
         "kv_a_norm_g": p["kv_a_norm"]["~params"]["weight"],
         "kv_b_w": p["kv_b"]["~params"]["weight"],
         "o_w": p["out_proj"]["~params"]["weight"]}
    z = {"num_attention_heads": H, "qk_nope_head_dim": NOPE,
         "qk_rope_head_dim": ROPE, "v_head_dim": V, "kv_lora_rank": KR,
         "rms_norm_eps": 1e-6, "rope_theta": 10000.0}
    # the reference norms the stream first; feed it unit-rms rows and the
    # layer the same rows normed
    x = rows[0] / jnp.sqrt(jnp.mean(rows[0] ** 2, -1, keepdims=True) + 1e-6)
    want = np.asarray(reference.attention(rows[0], w, z)) \
        - np.asarray(rows[0])
    got = np.asarray(layer(x[None]))[0]
    np.testing.assert_allclose(got, want, atol=3e-5)
    for fault in ("no_rope_score", "latent_int8"):
        off = np.asarray(reference.attention(rows[0], w, z, fault)) \
            - np.asarray(rows[0])
        assert np.abs(off - want).max() > 1e-3, fault


@pytest.mark.parametrize("chunk", [48, 16, 8])
def test_the_expanded_chunk_over_pages_is_the_whole_form(layer, rows, chunk):
    want = np.asarray(layer(rows))
    tables, leaf = tables_and_leaf(layer)
    got = []
    for c in range(0, T, chunk):
        y, leaf = layer.forward_chunk_paged(
            rows[:, c:c + chunk], leaf, tables, jnp.full((2,), c, jnp.int32))
        got.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(got, 1), want, atol=3e-5)
    # what the leaf holds: the normed latent and the rotated key, zeros
    # behind, and nothing on the scratch page
    held = np.asarray(leaf)[np.asarray(tables)].reshape(2, T, -1)
    assert np.abs(held[..., :20]).min() > 0
    assert not held[..., 20:].any() and not np.asarray(leaf)[0].any()


def test_the_absorbed_step_agrees_with_the_expanded_chunk_on_one_cache(
        layer, rows):
    """Prefill 20 tokens expanded, then every further token both ways over
    the SAME leaf: absorbed, one token a row, and as an expanded chunk of
    one page at its positions."""
    want = np.asarray(layer(rows))
    tables, leaf = tables_and_leaf(layer)
    _, leaf = layer.forward_chunk_paged(rows[:, :20], leaf, tables,
                                        jnp.zeros((2,), jnp.int32))
    step = jax.jit(layer.forward_step_paged)
    for t in range(20, T):
        pos = jnp.full((2,), t, jnp.int32)
        y, leaf = step(rows[:, t], leaf, tables, pos)
        np.testing.assert_allclose(np.asarray(y), want[:, t], atol=5e-5)
    # ragged positions: row 1 stands 7 tokens behind row 0
    tables, leaf = tables_and_leaf(layer)
    _, leaf = layer.forward_chunk_paged(rows[:, :20], leaf, tables,
                                        jnp.zeros((2,), jnp.int32))
    y, leaf = layer.forward_chunk_paged(
        jnp.stack([rows[0, 20:28], rows[1, 20:28]]), leaf, tables,
        jnp.full((2,), 20, jnp.int32))
    pos = jnp.asarray([28, 21], jnp.int32)
    y, _ = layer.forward_step_paged(
        jnp.stack([rows[0, 28], rows[1, 21]]), leaf, tables, pos)
    np.testing.assert_allclose(np.asarray(y)[0], want[0, 28], atol=5e-5)
    np.testing.assert_allclose(np.asarray(y)[1], want[1, 21], atol=5e-5)


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5),
                                        ("bfloat16", 4e-2)])
def test_the_step_through_the_kernel_is_the_rows_form(layer, rows, dtype,
                                                      atol):
    """One pool of whole tiles (8 float32 or 16 bfloat16 tokens a page, rows
    of 128), prefilled, then the absorbed step both ways at ragged
    positions: ``"kernel"`` reads each row's pages from the leaf where they
    lie (the rows at a page's edge, mid-page, at the table's last key, and
    an idle row on the scratch page), the rows form gathers every table;
    both write the same row and return the same leaf. Pages no row holds
    are NaN, and the kernel's tables name one in every slot past a row's
    position."""
    page = 32 // jnp.dtype(dtype).itemsize
    n = T // page
    pos = np.asarray([T - 1, page - 1, page + 3, 0], np.int32)
    tables = np.zeros((4, n), np.int32)
    tables[:3] = 1 + np.arange(3 * n).reshape(3, n)
    leaf = layer.init_page_pool(2 + 3 * n, page, jnp.dtype(dtype))
    x = jnp.concatenate([rows, rows[:1] * 0.5], 0)            # three rows
    _, leaf = layer.forward_chunk_paged(x, leaf, jnp.asarray(tables[:3]),
                                        jnp.zeros((3,), jnp.int32))
    leaf = leaf.at[-1].set(jnp.nan)
    past = tables.copy()
    for i, p in enumerate(pos):
        past[i, p // page + 1:] = leaf.shape[0] - 1
    x_t = jnp.asarray(np.random.default_rng(3).standard_normal((4, D)),
                      jnp.float32)
    want, leaf_rows = layer.forward_step_paged(
        x_t, leaf, jnp.asarray(tables), jnp.asarray(pos))
    got, leaf_kernel = layer.forward_step_paged(
        x_t, leaf, jnp.asarray(past), jnp.asarray(pos),
        decode_attention="kernel")
    assert got.dtype == want.dtype and not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
    np.testing.assert_array_equal(np.asarray(leaf_kernel, np.float32),
                                  np.asarray(leaf_rows, np.float32))
    with pytest.raises(ValueError, match="decode_attention"):
        layer.forward_step_paged(x_t, leaf, jnp.asarray(tables),
                                 jnp.asarray(pos), decode_attention="pages")


def test_the_host_arithmetic_of_what_is_read(layer):
    # the gathered step reads every slot of every table, the kernel the
    # pages up to each row's position (an idle row one page); the chunk
    # walks to the furthest position of the dispatch in rounds of 256 keys
    whole = {"kv_read_tokens": 3 * 1024, "kv_table_tokens": 3 * 1024}
    pos = np.array([5, 900, 0])
    assert layer.step_read_counts(pos, 16, 64) == whole
    assert layer.step_read_counts(pos, 16, 64, "rows") == whole
    assert layer.step_read_counts(pos, 16, 64, "kernel") == {
        "kv_read_tokens": (1 + 57 + 1) * 16, "kv_table_tokens": 3 * 1024}
    full = layer.step_read_counts(np.array([1023]), 16, 64, "kernel")
    assert full["kv_read_tokens"] == full["kv_table_tokens"] == 1024
    assert layer.chunk_read_counts(np.array([0, 512]), 256, 16, 1024) == {
        "kv_read_tokens": 2 * 768, "kv_table_tokens": 2 * 16384}


def test_an_odd_rotary_width_is_refused():
    with pytest.raises(ValueError, match="PAIRS"):
        nn.LatentAttention(D, H, QR, KR, NOPE, 3, V)
