"""``nn.RoutedExperts``: one chip's share of a routed feed-forward layer
against the plain reference (``benchmark/reference/joyai_llm_flash.py``) and
against hand-set routing: the selection bias selects and does not weigh, the
gates are normalised over ALL the chosen, the scaling factor, ties, no token
dropped at any imbalance (several rounds), rows that are no token take no
slot, and the shares add up to the uncut layer."""

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

D, F, N, K = 32, 16, 16, 4
Z = {"rms_norm_eps": 1e-6, "num_experts_per_tok": K,
     "routed_scaling_factor": 2.5, "experts_held": [0, N]}


def layer_weights(seed, bias=0.2, router=0.5, N=N):
    """The reference's layout for ONE routed layer, all ``N`` experts."""
    rng = np.random.default_rng(seed)
    f = lambda *s, std=0.3: jnp.asarray(std * rng.standard_normal(s),
                                        jnp.float32)
    return {"mlp_norm_g": jnp.ones((D,)),
            "router_w": f(N, D, std=router), "select_bias": f(N, std=bias),
            "experts_gate_w": f(N, F, D), "experts_up_w": f(N, F, D),
            "experts_down_w": f(N, D, F), "shared_gate_w": f(F, D),
            "shared_up_w": f(F, D), "shared_down_w": f(D, F)}


def share(w, held, shared=True, **kw):
    """The program's layer holding experts ``held`` of ``w``."""
    first, count = held
    m = nn.RoutedExperts(D, F, w["router_w"].shape[0], K, held=held,
                         n_shared=1 if shared else 0, scaling=2.5, **kw)
    p = lambda a: {"~params": {"weight": a}}
    cut = lambda a: a[first:first + count]
    tree = {"~params": {"router": w["router_w"],
                        "select_bias": w["select_bias"],
                        "w_gate": cut(w["experts_gate_w"]),
                        "w_up": cut(w["experts_up_w"]),
                        "w_down": cut(w["experts_down_w"])}}
    if shared:
        tree["shared"] = {"gate": p(w["shared_gate_w"]),
                          "up": p(w["shared_up_w"]),
                          "down": p(w["shared_down_w"])}
    m.load_params_dict(tree)
    return m.evaluate()


def unit_rows(seed, t):
    x = np.random.default_rng(seed).standard_normal((t, D))
    return jnp.asarray(x / np.sqrt((x ** 2).mean(-1, keepdims=True)),
                       jnp.float32)


def ref_branch(x, w, held, shared=True, fault=None):
    """The reference's branch over the experts ``held`` of ``w`` (it norms
    its input: unit gains over unit-rms rows change them by eps alone)."""
    first, count = held
    cut = dict(w, **{k: w[k][first:first + count] for k in (
        "experts_gate_w", "experts_up_w", "experts_down_w")})
    return np.asarray(reference.routed_branch(x, cut, Z, held=held,
                                              shared=shared, fault=fault))


@pytest.mark.parametrize("held", [(0, N), (4, 4), (12, 4), (0, 1)])
def test_a_share_is_the_references_share(held):
    w, x = layer_weights(1), unit_rows(2, 24)
    got = np.asarray(share(w, held)(x))
    np.testing.assert_allclose(got, ref_branch(x, w, held), atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of four experts each, with the
    shared expert counted once, are the reference's uncut layer."""
    w, x = layer_weights(3), unit_rows(4, 40)
    parts = sum(np.asarray(share(w, (4 * i, 4), shared=False)(x))
                for i in range(4))
    whole = ref_branch(x, w, (0, N))
    only_shared = whole - ref_branch(x, w, (0, N), shared=False)
    np.testing.assert_allclose(parts + only_shared, whole, atol=3e-5)
    # and a share that holds none of a token's chosen experts gives that
    # token the shared expert alone
    idx, _ = share(w, (0, 4)).route(x)
    none_here = np.asarray((idx >= 4).all(-1))
    assert none_here.any()
    np.testing.assert_allclose(
        np.asarray(share(w, (0, 4))(x))[none_here], only_shared[none_here],
        atol=2e-5)


def test_the_bias_selects_and_does_not_weigh():
    w, x = layer_weights(5, bias=0.3), unit_rows(6, 64)
    m = share(w, (0, N))
    idx, g = (np.asarray(a) for a in m.route(x))
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(w["router_w"]).T))
    biased = s + np.asarray(w["select_bias"])
    # the chosen are the top of s + b ...
    np.testing.assert_array_equal(np.sort(idx, -1),
                                  np.sort(np.argsort(-biased, -1)[:, :K], -1))
    # ... which is not the top of s for some token (the bias matters) ...
    assert (np.sort(idx, -1) != np.sort(np.argsort(-s, -1)[:, :K], -1)).any()
    # ... and the gates are s over the sum of ALL the chosen s, times 2.5
    chosen = np.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(g, 2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(g.sum(-1), 2.5, rtol=1e-5)
    # each planted departure is told apart from the layer by the reference
    sound = ref_branch(x, w, (4, 4))
    for fault in ("bias_ignored", "gates_from_biased", "sum_over_held",
                  "no_scaling"):
        assert np.abs(ref_branch(x, w, (4, 4), fault=fault)
                      - sound).max() > 1e-3, fault
    np.testing.assert_allclose(np.asarray(share(w, (4, 4))(x)), sound,
                               atol=2e-5)


def test_the_sum_runs_over_all_the_chosen_held_here_or_not():
    w, x = layer_weights(7), unit_rows(8, 32)
    _, g = share(w, (0, N)).route(x)
    _, g_few = share(w, (0, 2)).route(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_few))
    np.testing.assert_allclose(np.asarray(g_few).sum(-1), 2.5, rtol=1e-5)


def test_ties_go_to_the_lower_index():
    w = layer_weights(9, bias=0.0)
    w["router_w"] = jnp.zeros((N, D))           # every score 0.5
    idx, g = share(w, (0, N)).route(unit_rows(10, 5))
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.tile(np.arange(K), (5, 1)))
    np.testing.assert_allclose(np.asarray(g), 2.5 / K, rtol=1e-6)
    # a bias lifts one expert over the tie; the gates stay even
    w["select_bias"] = jnp.zeros((N,)).at[9].set(0.1)
    idx, g = share(w, (0, N)).route(unit_rows(10, 5))
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.tile([9, 0, 1, 2], (5, 1)))
    np.testing.assert_allclose(np.asarray(g), 2.5 / K, rtol=1e-6)


@pytest.mark.parametrize("tokens", [8, 96])
def test_no_token_is_dropped_when_every_token_chooses_the_same_experts(
        tokens):
    """The selection bias sends every token to experts 4..7 of 64: the
    fullest expert takes every token (three rounds of 32 slots at 96
    tokens, where the mean load is 6), and the result is still the
    reference's."""
    w = layer_weights(11, bias=0.0, N=64)
    w["select_bias"] = jnp.zeros((64,)).at[4:8].set(5.0)
    x = unit_rows(12, tokens)
    m = share(w, (4, 4))
    assert m.round_slots(tokens) == min(tokens, 32)
    got, counts = m.forward_counted(x)
    np.testing.assert_array_equal(np.asarray(counts),
                                  [4 * tokens, 4, tokens, 4])
    np.testing.assert_allclose(np.asarray(got), ref_branch(x, w, (4, 4)),
                               atol=3e-5)
    # under jit too (the rounds are a traced trip count)
    from bigdl_tpu.nn.module import bind

    def bound(p, x):
        with bind(m, p, {}, False, None):
            return m.forward_counted(x)

    got2, _ = jax.jit(bound)(m.params_dict(), x)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got), atol=1e-6)


def test_rows_that_are_no_token_take_no_slot():
    w, x = layer_weights(13), unit_rows(14, 16)
    m = share(w, (0, 8))
    live = jnp.arange(16) % 2 == 0
    got, counts = m.forward_counted(x, live)
    whole, all_counts = m.forward_counted(x)
    # the live rows are what they were, the others hold the shared expert
    np.testing.assert_allclose(np.asarray(got)[::2], np.asarray(whole)[::2],
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got)[1::2],
        (ref_branch(x, w, (0, 8))
         - ref_branch(x, w, (0, 8), shared=False))[1::2], atol=2e-5)
    idx, _ = m.route(x)
    here = np.asarray(idx < 8)
    assert int(counts[0]) == here[::2].sum() < int(all_counts[0]) \
        == here.sum()
    assert int(counts[3]) == 8 and int(counts[1]) <= 8
    # shapes with leading dimensions go through as they came
    out = m(x.reshape(2, 8, D))
    np.testing.assert_allclose(np.asarray(out).reshape(16, D),
                               np.asarray(whole), atol=1e-6)


def test_the_constructor_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="not a run"):
        nn.RoutedExperts(D, F, N, K, held=(14, 4))
    with pytest.raises(ValueError, match="top_k"):
        nn.RoutedExperts(D, F, N, N + 1)
