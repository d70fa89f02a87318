"""Token-choice routed experts beside a shared expert (the feed-forward
layer of the DeepSeek-V3 family: Liu et al., arXiv:2412.19437 section 2.1.2,
with the auxiliary-loss-free selection bias of its ``noaux_tc`` top-k), as
ONE CHIP'S SHARE of an expert-parallel layer computes it.

A token's router scores are ``s = sigmoid(W_r x)`` over ALL ``n_routed``
experts, in float32. It chooses the ``top_k`` of ``s + b`` (``b``: the
selection bias; ties go to the lower index), weighs each chosen expert by
its own ``s_i`` (never ``s_i + b_i``), normalises over ALL the chosen
(``g_i = scaling * s_i / (sum_chosen s_j + 1e-20)``), and adds what the
shared expert gives::

    FF(x) = sum_{i chosen and held} g_i E_i(x) + E_shared(x)
    E(x)  = W_down (silu(W_gate x) * W_up x)

``held = (first, count)`` names the experts whose weights live here. The
router keeps its whole width, so the sum that normalises the gates runs
over every chosen expert, held or not; what the absent experts would add is
left out (on a mesh it would arrive through the exchange; on one chip the
layer runs without it). No token is ever dropped: an expert takes as many
tokens as choose it.

The held experts' products are batched: the assignments that fall here are
sorted by expert and laid into ``(count, slots)`` tables (a token reaches
its slot and comes back through a one-hot matrix product), ``slots`` rows an
expert a round, as many rounds as the fullest expert needs (a traced trip
count; the first round is unrolled, and with ``slots`` at four times the
mean load a second one is rare). A call of up to ``MIN_SLOTS`` tokens (a
decode step) takes one round of as many slots as tokens, whatever the
routing: every held expert's weights are read once.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as bt_init
from bigdl_tpu.nn.gated_delta import GatedMLP, project
from bigdl_tpu.nn.module import Module

#: a call of no more tokens than this gives every expert a slot a token
#: (one round at any imbalance)
MIN_SLOTS = 32
#: slots a round, over the mean load of an expert
SLOTS_OVER_MEAN = 4


class RoutedExperts(Module):
    """``embed_dim`` -> ``embed_dim`` over ``n_routed`` experts of width
    ``expert_dim`` (``held`` of them here), ``top_k`` a token, and
    ``n_shared`` shared experts fused into one gated MLP. The router and
    its selection bias are float32 parameters whatever the experts' dtype;
    float32 out."""

    def __init__(self, embed_dim: int, expert_dim: int, n_routed: int,
                 top_k: int, held: Optional[Tuple[int, int]] = None,
                 n_shared: int = 1, scaling: float = 1.0):
        super().__init__()
        first, count = held or (0, n_routed)
        if not (0 <= first and count >= 1 and first + count <= n_routed):
            raise ValueError(f"held {held!r} is not a run of the "
                             f"{n_routed} experts")
        if not 1 <= top_k <= n_routed:
            raise ValueError(f"top_k {top_k} of {n_routed} experts")
        self.embed_dim, self.expert_dim = embed_dim, expert_dim
        self.n_routed, self.top_k = n_routed, top_k
        self.held = (int(first), int(count))
        self.scaling = float(scaling)
        normal = bt_init.RandomNormal(0.0, 0.02)
        self.register_parameter("router", normal((n_routed, embed_dim)))
        self.register_parameter("select_bias", jnp.zeros((n_routed,)))
        self.register_parameter("w_gate",
                                normal((count, expert_dim, embed_dim)))
        self.register_parameter("w_up",
                                normal((count, expert_dim, embed_dim)))
        self.register_parameter("w_down",
                                normal((count, embed_dim, expert_dim)))
        self.shared = (GatedMLP(embed_dim, n_shared * expert_dim)
                       if n_shared else None)

    # -------------------------------------------------------------- routing
    def route(self, x):
        """``x`` (T, embed) -> the experts each token chooses (T, top_k)
        int32 and their gates (T, top_k) float32."""
        with jax.named_scope("moe/route"):
            s = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), self.router.astype(jnp.float32).T,
                precision=jax.lax.Precision.HIGHEST))
            # lax.top_k lists equal keys by rising index
            _, idx = jax.lax.top_k(
                s + self.select_bias.astype(jnp.float32), self.top_k)
            g = jnp.take_along_axis(s, idx, axis=-1)
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
            return idx.astype(jnp.int32), g * self.scaling

    def round_slots(self, tokens: int) -> int:
        """Rows an expert takes a round in a call of ``tokens`` tokens."""
        if tokens <= MIN_SLOTS:
            return tokens
        mean = tokens * self.top_k / self.n_routed
        return min(tokens, max(MIN_SLOTS, -(-int(SLOTS_OVER_MEAN * mean)
                                            // 8) * 8))

    # ------------------------------------------------------------- products
    def _held_experts(self, x, idx, gates, live):
        """The held experts' part of the sum for ``x`` (T, embed) and the
        tokens each takes (count,) int32. ``live`` (T,) bool or None: rows
        that are no token (an idle lane, a chunk's padding) take no slot.

        Tokens reach their slots and come back through one-hot matrix
        products (a slot's row of ``pick`` holds a single 1: the product
        moves the operand's values as they are): row gathers of this many
        rows cost a TPU more than the experts' own products (PERF.md,
        PR 48). The gate is applied to a slot's result in float32, before
        that result is rounded as the combining product's operand."""
        t, d = x.shape
        k = self.top_k
        first, e = self.held
        dtype = self.w_gate.dtype
        with jax.named_scope("moe/route"):
            local = idx - first
            here = (local >= 0) & (local < e)
            if live is not None:
                here = here & live[:, None]
            ea = jnp.where(here, local, e).reshape(-1)          # (T * k,)
            load = jnp.sum(ea[:, None] == jnp.arange(e)[None], axis=0,
                           dtype=jnp.int32)                     # (count,)
            # the assignments in the order of their experts
            order = jnp.argsort(ea, stable=True).astype(jnp.int32)
            start = jnp.cumsum(load) - load
            slots = self.round_slots(t)
            flat_gates = gates.reshape(-1)
            rounds = (jnp.max(load) + slots - 1) // slots
        x = x.astype(dtype)

        def one_round(r, out):
            with jax.named_scope("moe/experts"):
                j = r * slots + jnp.arange(slots)               # (slots,)
                a = jnp.take(order, start[:, None] + j[None], mode="clip")
                ok = j[None] < load[:, None]                    # (E, slots)
                pick = ((a // k).reshape(-1, 1) == jnp.arange(t)[None]) \
                    & ok.reshape(-1, 1)                         # (E * S, T)
                pick = pick.astype(dtype)
                xs = jnp.matmul(pick, x, preferred_element_type=jnp.float32
                                ).astype(dtype).reshape(e, slots, d)
                up = jnp.einsum("esd,efd->esf", xs, self.w_up,
                                preferred_element_type=jnp.float32)
                gate = jnp.einsum("esd,efd->esf", xs, self.w_gate,
                                  preferred_element_type=jnp.float32)
                y = jnp.einsum("esf,edf->esd",
                               (jax.nn.silu(gate) * up).astype(dtype),
                               self.w_down,
                               preferred_element_type=jnp.float32)
                y = y * jnp.where(ok, jnp.take(flat_gates, a, mode="clip"),
                                  0.0)[..., None]
                return out + jnp.matmul(
                    pick.T, y.astype(dtype).reshape(e * slots, d),
                    preferred_element_type=jnp.float32)

        out = one_round(0, jnp.zeros((t, d), jnp.float32))
        return jax.lax.fori_loop(1, rounds, one_round, out), load

    def _shared_expert(self, x):
        with jax.named_scope("moe/shared"):
            m = self.shared
            return project(m.down, jax.nn.silu(project(m.gate, x))
                           * project(m.up, x))

    def forward_counted(self, input, live=None):
        """``(FF(input), counts)``: ``counts`` int32 (4,): the assignments
        that fell on held experts, the held experts some token chose, the
        fullest expert's tokens, the experts held. ``live`` (shaped as
        ``input`` without its last dimension): rows that are no token."""
        lead = input.shape[:-1]
        x = input.reshape(-1, self.embed_dim).astype(jnp.float32)
        idx, gates = self.route(x)
        y, load = self._held_experts(
            x, idx, gates, None if live is None else live.reshape(-1))
        if self.shared is not None:
            y = y + self._shared_expert(x)
        counts = jnp.stack([jnp.sum(load), jnp.sum(load > 0), jnp.max(load),
                            jnp.int32(self.held[1])]).astype(jnp.int32)
        return y.reshape(lead + (self.embed_dim,)), counts

    def forward(self, input):
        return self.forward_counted(input)[0]
