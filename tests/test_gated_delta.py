"""The gated delta-rule layer's three forms agree with each other and with
the benchmark's token-by-token reference (``benchmark/reference/
olmo_hybrid.py``, an independent formulation: a ``lax.scan`` over time), and
the small modules beside it (RMSNorm, the gated MLP, QK-norm) compute what
they say. Gains, ``A_log`` and ``dt_bias`` are random too, so a leaf mapped
to the wrong place shows."""

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
from bigdl_tpu.nn.gated_delta import (  # noqa: E402
    SUB, GatedDeltaNet, GatedMLP, _inverse_unit_lower, gated_delta_chunk,
    gated_delta_step,
)

D, H, DK, DV, F = 24, 3, 8, 16, 40


def layer_weights(seed, neg=True):
    """One linear layer in the benchmark's layout, everything random."""
    rng = np.random.default_rng(seed)
    mat = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    gain = lambda n: jnp.asarray(1 + 0.2 * rng.standard_normal(n), jnp.float32)
    return {"q_w": mat(H * DK, D), "k_w": mat(H * DK, D),
            "v_w": mat(H * DV, D), "a_w": mat(H, D), "b_w": 3 * mat(H, D),
            "g_w": mat(H * DV, D), "o_w": mat(D, H * DV),
            "conv_q": mat(H * DK, 4), "conv_k": mat(H * DK, 4),
            "conv_v": mat(H * DV, 4),
            "A_log": jnp.asarray(rng.uniform(-3, 1.5, H), jnp.float32),
            "dt_bias": jnp.asarray(rng.uniform(-4, 0, H), jnp.float32),
            "o_norm_g": gain(DV), "mixer_norm_g": gain(D),
            "mlp_norm_g": gain(D), "gate_w": mat(F, D), "up_w": mat(F, D),
            "down_w": mat(D, F)}


def block_from(w, neg=True):
    """A program block loaded through the benchmark adapter's own mapping."""
    from benchmark.models import olmo_hybrid as adapter
    from bigdl_tpu.models.hybrid import LINEAR, HybridBlock

    blk = HybridBlock(LINEAR, D, 2, F, None, H, DK, DV, 4, neg, 1e-6, None)
    blk.evaluate()
    blk.load_params_dict(adapter.layer_tree(LINEAR, w))
    return blk


def reference_block(x, w, neg=True, state_dtype=jnp.float32):
    from benchmark.reference import olmo_hybrid as ref

    return jnp.stack([ref._linear_layer(row, w, H, DK, DV, neg, 1e-6,
                                        jnp.dtype(state_dtype))
                      for row in x])


def test_inverse_unit_lower_is_the_inverse():
    rng = np.random.default_rng(0)
    a = np.tril(0.3 * rng.standard_normal((5, SUB, SUB)), -1).astype(np.float32)
    inv = np.asarray(_inverse_unit_lower(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(SUB) + a.astype(np.float64))
    assert np.abs(inv - want).max() < 2e-5 * np.abs(want).max()
    # lower triangular with a unit diagonal, as its argument
    assert np.abs(np.triu(inv, 1)).max() == 0
    assert np.abs(np.diagonal(inv, axis1=-2, axis2=-1) - 1).max() < 1e-6


@pytest.mark.parametrize("t", [1, 37, SUB, 150])
@pytest.mark.parametrize("neg", [True, False])
def test_chunked_equals_single_step_equals_reference(t, neg):
    """The whole block, a prompt that is not a multiple of the sub-chunk
    among the lengths, beta above 1 under ``allow_neg_eigval``."""
    w = layer_weights(3)
    blk = block_from(w, neg)
    x = jax.random.normal(jax.random.PRNGKey(t), (2, t, D))
    want = np.asarray(reference_block(x, w, neg))
    full = np.asarray(blk(x))
    state = blk.mixer.init_state(2)
    steps = []
    for i in range(t):
        y, state = blk.mixer.forward_step(x[:, i], state)
        steps.append(blk._rest(x[:, i], y))
    steps = np.asarray(jnp.stack(steps, 1))
    scale = np.abs(want).max()
    assert np.abs(full - want).max() < 2e-5 * scale
    assert np.abs(steps - want).max() < 2e-5 * scale
    _, beta = blk.mixer._gates(x)
    assert (float(beta.max()) > 1.0) == neg and float(beta.max()) < 2.0


def test_carried_chunks_with_a_padded_last_chunk_leave_the_steps_state():
    """Prefill as the engine runs it: chunks of 16 from a carried state, the
    last one right-padded and masked by ``n_valid``; state and convolution
    tail come out as token-by-token leaves them."""
    w = layer_weights(5)
    mixer = block_from(w).mixer
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 43, D))
    lens = np.asarray([43, 29])
    by_step = []
    for r in range(2):
        st = mixer.init_state(1)
        for i in range(lens[r]):
            _, st = mixer.forward_step(x[r:r + 1, i], st)
        by_step.append(st)
    st = mixer.init_state(2)
    outs = []
    for c in range(0, 48, 16):
        xc = jnp.pad(x, ((0, 0), (0, 5), (0, 0)))[:, c:c + 16]
        y, new = mixer.forward_chunk(xc, st, jnp.clip(lens - c, 0, 16))
        # a row with nothing left in this chunk keeps its state bit for bit
        for r in range(2):
            if lens[r] <= c:
                assert np.array_equal(new[0][r], st[0][r])
                assert np.array_equal(new[1][r], st[1][r])
        st = new
        outs.append(y)
    for r in range(2):
        assert np.abs(st[0][r] - by_step[r][0][0]).max() < 1e-5
        assert np.abs(st[1][r] - by_step[r][1][0]).max() < 1e-6
    full = mixer(x[:1])
    got = jnp.concatenate(outs, 1)[:1, :43]
    assert np.abs(got - full).max() < 2e-5 * np.abs(full).max()


def test_inactive_rows_of_a_step_keep_their_state_bit_for_bit():
    mixer = block_from(layer_weights(6)).mixer
    x = jax.random.normal(jax.random.PRNGKey(3), (3, D))
    st = mixer.init_state(3)
    _, st = mixer.forward_step(x, st)
    active = jnp.asarray([True, False, True])
    _, new = mixer.forward_step(x + 1, st, active)
    for old, now in zip(st, new):
        assert np.array_equal(old[1], now[1])
        assert not np.array_equal(old[0], now[0])


def test_core_forms_on_raw_inputs_and_masked_tokens():
    """``gated_delta_chunk`` against ``gated_delta_step`` alone, and a token
    with g = 0, beta = 0 leaves the state where it was."""
    rng = np.random.default_rng(1)
    t = 2 * SUB
    q, k = (jnp.asarray(rng.standard_normal((1, t, 2, 4)), jnp.float32)
            for _ in range(2))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(rng.standard_normal((1, t, 2, 6)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0, 0.5, (1, t, 2)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (1, t, 2)), jnp.float32)
    dead = jnp.arange(t)[None, :, None] >= t - 10
    g, beta = jnp.where(dead, 0.0, g), jnp.where(dead, 0.0, beta)
    s0 = jnp.asarray(rng.standard_normal((1, 2, 4, 6)), jnp.float32)
    o, s = gated_delta_chunk(q, k, v, g, beta, s0)
    st, outs = s0, []
    for i in range(t):
        oi, st = gated_delta_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                  beta[:, i], st)
        outs.append(oi)
        if i == t - 11:
            before_dead = st
    assert np.abs(o - jnp.stack(outs, 1)).max() < 1e-4
    assert np.abs(s - st).max() < 1e-5
    assert np.array_equal(before_dead, st)


def test_rmsnorm_and_gated_mlp_compute_what_they_say():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((4, 10)).astype(np.float32)
    norm = nn.RMSNorm(10, eps=1e-6)
    g = rng.standard_normal(10).astype(np.float32)
    norm.load_params_dict({"~params": {"weight": jnp.asarray(g)}})
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g
    assert np.abs(np.asarray(norm(jnp.asarray(x))) - want).max() < 1e-5
    mlp = GatedMLP(10, 7)
    gate, up, down = (np.asarray(m.weight) for m in (mlp.gate, mlp.up, mlp.down))
    a = x @ gate.T
    want = (a / (1 + np.exp(-a)) * (x @ up.T)) @ down.T
    assert np.abs(np.asarray(mlp(jnp.asarray(x))) - want).max() < 1e-5
    assert set(mlp.params_dict()) == {"gate", "up", "down"}


def test_qk_norm_is_an_option_and_every_cached_path_carries_it():
    from bigdl_tpu.utils import random as rnd

    rnd.set_seed(4)
    plain = nn.MultiHeadAttention(16, 4, causal=True)
    assert set(plain.params_dict()) == {"qkv", "out_proj"}   # GPT-2's tree
    attn = nn.MultiHeadAttention(16, 4, causal=True, num_kv_heads=2,
                                 with_bias=False, qk_norm=True)
    attn.evaluate()
    rng = np.random.default_rng(0)
    for name in ("q_norm", "k_norm"):
        m = getattr(attn, name)
        m.load_params_dict({"~params": {"weight": jnp.asarray(
            1 + 0.3 * rng.standard_normal(m.n_output), jnp.float32)}})
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    full = attn(x)
    # the norm is over the WHOLE projection, before the heads are split
    qkv = np.asarray(attn.qkv(x))
    q = qkv[..., :16]
    qn = q / np.sqrt((q ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(attn.q_norm.weight)
    got_q, _, _ = attn._split_kv_step(jnp.asarray(qkv))
    assert np.abs(np.asarray(got_q).transpose(0, 2, 1, 3).reshape(2, 12, 16)
                  - qn).max() < 1e-5
    # paged prefill in two chunks then one paged decode step
    pool = attn.init_page_pool(9, 4)
    tables = jnp.asarray(1 + np.arange(8).reshape(2, 4), jnp.int32)
    outs = []
    for c in (0, 8):
        o, pool = attn.forward_chunk_paged(
            x[:, c:c + 8] if c == 0 else jnp.pad(x[:, 8:11], ((0, 0), (0, 5), (0, 0))),
            pool, tables, jnp.full((2,), c, jnp.int32))
        outs.append(o)
    got = jnp.concatenate(outs, 1)[:, :11]
    assert np.abs(got - full[:, :11]).max() < 1e-5
    o, pool = attn.forward_step_paged(x[:, 11:12], pool, tables,
                                      jnp.full((2,), 11, jnp.int32))
    assert np.abs(o[:, 0] - full[:, 11]).max() < 1e-5


def test_reference_with_a_bfloat16_state_is_a_different_result():
    """The precision control's lever works: the state rounded to bfloat16
    after every token moves the block's output far more than float32
    rounding does."""
    w = layer_weights(3)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 200, D))
    want = np.asarray(reference_block(x, w))
    low = np.asarray(reference_block(x, w, state_dtype=jnp.bfloat16))
    prog = np.asarray(block_from(w)(x))
    assert np.abs(low - want).max() > 100 * np.abs(prog - want).max()
