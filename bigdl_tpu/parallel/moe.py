"""Mixture-of-Experts with expert parallelism (the 'ep' in dp/tp/pp/sp/ep).

No reference analog (SURVEY.md §2.5: the reference is DP-only) — this is
beyond-parity capability from the driver contract. The formulation is the
GShard/Mesh-TensorFlow dense-dispatch recipe, which is the TPU-native way
to route: top-1 (Switch) or top-2 (GShard) gating builds a
(tokens, experts, capacity) one-hot dispatch tensor and routing becomes
einsums (MXU work, static shapes) instead of gather/scatter. Tokens over
capacity are dropped (output 0 for the expert contribution), the standard
trade; the drop rate and per-expert load are exposed as routing stats
(``record_moe_metrics``).

Expert parallelism: inside ``shard_map`` over an 'expert' axis, each
device holds E/n experts and T/n tokens; ``moe_spmd`` dispatches with
``lax.all_to_all`` (source-shard buffers travel to the expert's owner and
back), the canonical MoE comm pattern over ICI.

``MoEMLP`` DROPS what an expert gets over its capacity and is reached from
``TransformerBlock`` alone (training-time routing with a softmax gate). It
is not the layer the paged engine serves: published sparse models choose
per token, drop nothing, score by sigmoid with a selection bias and add a
shared expert, and a chip of an expert-parallel deployment holds a share of
the experts. That layer is ``bigdl_tpu.nn.RoutedExperts``
(``nn/routed_experts.py``; ``models/hybrid.py`` puts it in a block); it runs
on one chip without an exchange, and ``moe_spmd``'s ``all_to_all`` is not
wired to it (ROADMAP Reach: experts across chips).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn import init as bt_init
from bigdl_tpu.nn.module import Module


def _topk_dispatch(gates, capacity, k: int = 1):
    """gates (T, E) -> (dispatch (T, E, C), combine (T, E, C), stats).

    GShard sequential assignment: choice j's positions within an expert's
    buffer start after ALL of choice j-1's assignments to that expert
    (GShard alg. 1); within a choice, position = rank of the token among
    tokens routed to that expert in token order. Tokens past capacity drop.
    For k > 1 the combine weights are the chosen gate probs normalized over
    the kept choices; for k == 1 they are the raw gate prob (Switch).

    stats: ``drop_rate`` (fraction of (token, choice) routes dropped) and
    ``expert_fraction`` (E,) (fraction of routes per expert, pre-drop)."""
    t, e = gates.shape
    remaining = gates
    counts = jnp.zeros((e,), gates.dtype)
    disps, weights = [], []
    kept_total = jnp.zeros((), gates.dtype)
    expert_fraction = jnp.zeros((e,), gates.dtype)
    for j in range(k):
        expert = jnp.argmax(remaining, axis=1)                 # (T,)
        onehot = jax.nn.one_hot(expert, e, dtype=gates.dtype)  # (T, E)
        if j > 0:
            # a saturated router can underflow every non-top gate to 0.0;
            # argmax would then re-pick arbitrarily — void such phantom
            # routes so they neither occupy capacity nor skew the stats
            valid = jnp.sum(remaining * onehot, axis=1) > 0
            onehot = onehot * valid[:, None].astype(gates.dtype)
        # position in the expert's buffer (exclusive cumsum + choice offset)
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        pos = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)  # (T,)
        routed = jnp.sum(onehot, axis=1) > 0                   # (T,)
        keep = (pos < capacity) & routed
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity),
                                capacity, dtype=gates.dtype)   # (T, C)
        disps.append(onehot[:, :, None] * pos_oh[:, None, :])  # (T, E, C)
        weights.append(jnp.sum(gates * onehot, axis=1)
                       * keep.astype(gates.dtype))
        counts = counts + jnp.sum(onehot, axis=0)
        kept_total = kept_total + jnp.sum(keep.astype(gates.dtype))
        expert_fraction = expert_fraction + jnp.mean(onehot, axis=0) / k
        remaining = remaining * (1.0 - onehot)
    dispatch = sum(disps)
    if k == 1:
        combine = disps[0] * weights[0][:, None, None]
    else:
        denom = sum(weights) + 1e-9
        combine = sum(d * (w / denom)[:, None, None]
                      for d, w in zip(disps, weights))
    stats = {"drop_rate": 1.0 - kept_total / (t * k),
             "expert_fraction": expert_fraction}
    return dispatch, combine, stats


def _top1_dispatch(gates, capacity):
    """Back-compat wrapper: top-1 (Switch) routing."""
    dispatch, combine, _ = _topk_dispatch(gates, capacity, 1)
    return dispatch, combine


class MoEMLP(Module):
    """Top-k gated mixture of expert MLPs (GELU, (D -> H -> D) each);
    ``n_top=1`` is Switch routing, ``n_top=2`` the GShard recipe with
    normalized combine weights.

    Eager/jit path runs all experts dense (dispatch einsums); inside
    ``shard_map`` over ``expert_parallel`` the experts and tokens are
    sharded and dispatch goes through all_to_all (``moe_spmd``)."""

    def __init__(self, embed_dim: int, hidden_dim: int, n_experts: int,
                 capacity_factor: float = 1.25,
                 expert_parallel: Optional[str] = None, n_top: int = 1):
        super().__init__()
        if n_top < 1 or n_top > n_experts:
            raise ValueError(f"n_top={n_top} must be in [1, {n_experts}]")
        self.embed_dim, self.hidden_dim = embed_dim, hidden_dim
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.expert_parallel = expert_parallel
        self.n_top = n_top
        xav = bt_init.Xavier()
        self.register_parameter("gate_w", xav((embed_dim, n_experts),
                                              fan_in=embed_dim,
                                              fan_out=n_experts))
        self.register_parameter(
            "w1", jnp.stack([xav((embed_dim, hidden_dim), fan_in=embed_dim,
                                 fan_out=hidden_dim)
                             for _ in range(n_experts)]))
        self.register_parameter("b1", jnp.zeros((n_experts, hidden_dim)))
        self.register_parameter(
            "w2", jnp.stack([xav((hidden_dim, embed_dim), fan_in=hidden_dim,
                                 fan_out=embed_dim)
                             for _ in range(n_experts)]))
        self.register_parameter("b2", jnp.zeros((n_experts, embed_dim)))

    #: Switch-style load-balancing loss from the LAST forward: add
    #: ``moe.l_aux`` (times a small coefficient) to the training objective
    #: to keep experts from collapsing. Computed from gates + the pre-
    #: capacity top-1 assignment, so it is identical in dense and spmd
    #: modes. Read it INSIDE the same trace/loss function that called
    #: forward (the intended use); after a jitted step returns, the stashed
    #: value is a dead tracer — rerun forward eagerly to refresh it.
    l_aux = 0.0

    #: Routing stats from the last eager forward (``forward_with_stats``
    #: returns them explicitly for jitted steps): drop_rate scalar +
    #: expert_fraction (E,). Feed to ``record_moe_metrics``.
    last_stats = None

    def _aux_loss(self, gates):
        me = jnp.mean(gates, axis=0)             # mean gate prob per expert
        assign = jax.nn.one_hot(jnp.argmax(gates, axis=1), self.n_experts,
                                dtype=gates.dtype)
        ce = jnp.mean(assign, axis=0)            # fraction routed per expert
        return self.n_experts * jnp.sum(me * ce)

    def expert_params(self) -> dict:
        """The expert-sharded params (leading dim = expert) as a dict —
        shard these over the 'expert' axis for ``moe_spmd``."""
        return {"w1": self.w1, "b1": self.b1, "w2": self.w2, "b2": self.b2}

    def forward_with_stats(self, input):
        """(output, l_aux, stats) WITHOUT any side-channel stash — safe
        inside ``jax.checkpoint``/remat regions, where a stashed inner
        tracer would outlive its trace and break clone/save later.
        stats: drop_rate scalar + expert_fraction (E,) — feed to
        ``record_moe_metrics`` outside the jitted step."""
        x = input
        shp = x.shape
        x2 = x.reshape(-1, self.embed_dim)
        t = x2.shape[0]
        gates = jax.nn.softmax(
            (x2 @ self.gate_w.astype(x2.dtype)).astype(jnp.float32), axis=-1)
        aux = self._aux_loss(gates)
        if self.expert_parallel is not None:
            # moe_spmd derives its own capacity from the LOCAL token count
            out, stats = moe_spmd(self.expert_params(), x2, gates,
                                  self.expert_parallel, self.capacity_factor,
                                  n_top=self.n_top, with_stats=True)
            return out.reshape(shp).astype(x.dtype), aux, stats
        capacity = max(1, math.ceil(self.n_top * t / self.n_experts
                                    * self.capacity_factor))
        dispatch, combine, stats = _topk_dispatch(gates, capacity, self.n_top)
        dispatch = dispatch.astype(x2.dtype)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x2)
        expert_out = _expert_fwd(self.expert_params(), expert_in)
        out = jnp.einsum("ecd,tec->td", expert_out,
                         combine.astype(expert_out.dtype))
        return out.reshape(shp).astype(x.dtype), aux, stats

    def forward_with_aux(self, input):
        """(output, l_aux) — see forward_with_stats."""
        out, aux, _ = self.forward_with_stats(input)
        return out, aux

    def forward(self, input):
        out, aux, stats = self.forward_with_stats(input)
        self.l_aux = aux
        self.last_stats = stats
        return out


def _expert_fwd(p: dict, inp):
    """inp (E, C, D) -> (E, C, D): every expert's GELU MLP on its buffer."""
    h = jnp.einsum("ecd,edh->ech", inp, p["w1"]) + p["b1"][:, None]
    h = jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, p["w2"]) + p["b2"][:, None]


def record_moe_metrics(metrics, stats, prefix: str = "moe") -> None:
    """Publish routing stats from the last (eager or returned) forward into
    an ``optim.metrics.Metrics`` table: drop rate + max expert fraction
    (1/E is perfectly balanced).

    These are dimensionless fractions — read them back with
    ``metrics.get(name)[0]``; ``Metrics.summary()`` assumes nanosecond
    timings and would scale them into nonsense."""
    metrics.set(f"{prefix} drop rate", float(stats["drop_rate"]))
    metrics.set(f"{prefix} max expert fraction",
                float(jnp.max(stats["expert_fraction"])))


def moe_spmd(expert_params: dict, x2, gates, axis_name: str,
             capacity_factor: float = 1.25, n_top: int = 1,
             with_stats: bool = False):
    """Expert-parallel dispatch inside shard_map over ``axis_name``.

    Device layout: tokens sharded (x2 is this device's (T/n, D) shard),
    experts sharded (``expert_params``' leading expert dim is the local
    E/n slice; global expert i lives on device i // (E/n)). Dispatch
    buffers (E, C, D) are built locally against ALL global experts, then
    ``all_to_all`` re-shards from expert-major to source-major so each
    device computes its own experts over every source's tokens; the
    reverse all_to_all brings results home."""
    n = lax.psum(1, axis_name)
    t_local = x2.shape[0]
    e_global = gates.shape[1]
    if e_global % n:
        raise ValueError(
            f"n_experts {e_global} not divisible by the {axis_name!r} axis "
            f"size {n}")
    e_local = e_global // n
    capacity = max(1, math.ceil(n_top * t_local / e_global * capacity_factor))
    dispatch, combine, stats = _topk_dispatch(gates, capacity, n_top)
    dispatch = dispatch.astype(x2.dtype)
    # (T/n, E, C) x (T/n, D) -> (E, C, D): buffers for every global expert
    buf = jnp.einsum("tec,td->ecd", dispatch, x2)
    buf = buf.reshape(n, e_local, capacity, buf.shape[-1])
    # exchange: device d receives the buffers targeting ITS experts from
    # every source shard -> (n_src, e_local, C, D)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    inp = jnp.moveaxis(buf, 0, 1).reshape(e_local, n * capacity, -1)
    out = _expert_fwd(expert_params, inp)
    out = jnp.moveaxis(out.reshape(e_local, n, capacity, -1), 1, 0)
    # send results back to the token owners
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(e_global, capacity, -1)
    res = jnp.einsum("ecd,tec->td", out, combine.astype(out.dtype))
    if with_stats:
        # average routing stats over the token shards
        stats = {"drop_rate": lax.pmean(stats["drop_rate"], axis_name),
                 "expert_fraction": lax.pmean(stats["expert_fraction"],
                                              axis_name)}
        return res, stats
    return res
