"""The lightning-attention layer's three forms agree with each other and with
the benchmark's token-by-token reference (``benchmark/reference/
minicpm_sala.py``, an independent formulation: a ``lax.scan`` over time).
Gains are random, so a leaf mapped to the wrong norm shows; the sub-chunk is
cut to 8 tokens so that a short row crosses several."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bigdl_tpu.nn import lightning_attention as la  # noqa: E402
from bigdl_tpu.nn.lightning_attention import (  # noqa: E402
    LightningAttention, decay_slopes, lightning_chunk, lightning_step)

D, H, HD, F = 24, 4, 8, 40


def layer_weights(seed):
    """One lightning layer in the benchmark's layout, everything random."""
    rng = np.random.default_rng(seed)
    mat = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    gain = lambda n: jnp.asarray(1 + 0.2 * rng.standard_normal(n), jnp.float32)
    return {"q_w": mat(H * HD, D), "k_w": mat(H * HD, D),
            "v_w": mat(H * HD, D), "g_w": mat(H * HD, D),
            "o_w": mat(D, H * HD), "q_norm_g": gain(HD), "k_norm_g": gain(HD),
            "o_norm_g": gain(H * HD), "mixer_norm_g": gain(D),
            "mlp_norm_g": gain(D), "gate_w": mat(F, D), "up_w": mat(F, D),
            "down_w": mat(D, F)}


def mixer_from(w, theta=10000.0):
    """The program's mixer loaded through the benchmark adapter's mapping."""
    from benchmark.models import minicpm_sala as adapter

    m = LightningAttention(D, H, HD, rotary_base=theta)
    m.evaluate()
    m.load_params_dict(adapter.layer_tree(adapter.LIGHTNING, w)["mixer"])
    return m


def reference_mixer(x, w, theta=10000.0, state_dtype=jnp.float32):
    """The reference's ``x + mixer(rmsnorm(x))`` less ``x``, over the rows,
    with the norm's gain taken out of the way."""
    from benchmark.reference import minicpm_sala as ref

    w = dict(w, mixer_norm_g=jnp.ones((D,), jnp.float32))
    return jnp.stack([ref._lightning_mixer(
        row, w, H, HD, theta, 1e-6, 1.0, jnp.dtype(state_dtype)) - row
        for row in x])


def normed(x):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)


@pytest.fixture()
def small_sub(monkeypatch):
    monkeypatch.setattr(la, "SUB", 8)


def test_the_decay_is_the_published_one():
    s = decay_slopes(32)
    assert s[0] == pytest.approx(2.0 ** -0.25) and s[-1] == 2.0 ** -8
    assert np.all(np.diff(s) < 0)
    # the fastest head's lam^256 underflows float32; its inverse overflows
    assert np.exp(-s[0] * 256) < 1e-90


@pytest.mark.parametrize("theta", [10000.0, None])
@pytest.mark.parametrize("t", [1, 7, 8, 29])
def test_chunked_equals_single_step_equals_reference(small_sub, t, theta):
    w = layer_weights(3)
    m = mixer_from(w, theta)
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.standard_normal((2, t, D)), jnp.float32)
    want = np.asarray(reference_mixer(x, w, theta))
    n = normed(x)
    whole = np.asarray(m(n))
    assert np.abs(whole - want).max() < 2e-5 * np.abs(want).max()
    state = m.init_state(2)
    steps = []
    for i in range(t):
        y, state = m.forward_step(n[:, i], state, jnp.full((2,), i))
        steps.append(np.asarray(y))
    assert np.abs(np.stack(steps, 1) - want).max() < 2e-5 * np.abs(want).max()
    # the chunk form leaves the state the steps leave
    _, chunked = m.forward_chunk(n, m.init_state(2), jnp.zeros((2,), jnp.int32))
    assert np.abs(np.asarray(chunked[0]) - np.asarray(state[0])).max() \
        < 2e-5 * np.abs(np.asarray(state[0])).max()
    if theta is not None and t > 1:
        # the rotation is there (relative: it shows against no rotation)
        plain = np.asarray(mixer_from(w, None)(n))
        assert np.abs(plain - want).max() > 1e-3 * np.abs(want).max()


def test_chunks_carry_the_state_across_boundaries_and_padding_leaves_it(small_sub):
    """A row of 37 tokens in chunks of 16 (the last right-padded, 5 real),
    beside a row of 20 (done after the second chunk): outputs at the real
    positions and the final states equal one pass over each row."""
    w = layer_weights(5)
    m = mixer_from(w)
    rng = np.random.default_rng(0)
    lens = [37, 20]
    x = jnp.asarray(rng.standard_normal((2, 48, D)), jnp.float32)
    want = np.asarray(reference_mixer(x, w))
    n = normed(x)
    state = m.init_state(2)
    for c in range(0, 48, 16):
        valid = jnp.asarray([max(0, min(16, ln - c)) for ln in lens])
        y, state = m.forward_chunk(n[:, c:c + 16], state,
                                   jnp.full((2,), c), valid)
        for r, ln in enumerate(lens):
            real = max(0, min(16, ln - c))
            assert np.abs(np.asarray(y[r, :real]) - want[r, c:c + real]).max(
                initial=0) < 2e-5 * np.abs(want).max()
    for r, ln in enumerate(lens):
        _, alone = m.forward_chunk(n[r:r + 1, :ln], m.init_state(1),
                                   jnp.zeros((1,), jnp.int32))
        assert np.abs(np.asarray(state[0][r]) - np.asarray(alone[0][0])).max() \
            < 2e-5 * np.abs(np.asarray(alone[0])).max()


def test_an_inactive_row_keeps_its_state_bit_for_bit():
    m = mixer_from(layer_weights(7))
    rng = np.random.default_rng(1)
    s0 = (jnp.asarray(rng.standard_normal((3, H, HD, HD)), jnp.float32),)
    x = jnp.asarray(rng.standard_normal((3, D)), jnp.float32)
    _, s1 = m.forward_step(x, s0, jnp.asarray([4, 9, 2]),
                           jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(s1[0][1]), np.asarray(s0[0][1]))
    assert not np.array_equal(np.asarray(s1[0][0]), np.asarray(s0[0][0]))


def test_the_core_functions_agree_where_the_fast_heads_underflow():
    """32 heads at the published slopes over 256 tokens in one sub-chunk:
    ``D_ij`` formed from the difference stays finite where ``lam^-j`` would
    overflow."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 32, 4)), jnp.float32)
               for _ in range(3))
    ld = jnp.asarray(-decay_slopes(32), jnp.float32)
    s = jnp.zeros((1, 32, 4, 4), jnp.float32)
    o, s_chunk = lightning_chunk(q, k, v, ld, s, jnp.asarray([256]))
    assert np.isfinite(np.asarray(o)).all()
    outs = []
    for i in range(256):
        o_i, s = lightning_step(q[:, i], k[:, i], v[:, i], ld, s)
        outs.append(np.asarray(o_i))
    want = np.stack(outs, 1)
    assert np.abs(np.asarray(o) - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(np.asarray(s_chunk) - np.asarray(s)).max() \
        < 1e-4 * np.abs(np.asarray(s)).max()
