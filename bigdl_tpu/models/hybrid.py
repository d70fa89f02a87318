"""A decoder whose layers are of two kinds: gated delta-rule layers
(``nn.GatedDeltaNet``: a fixed recurrent state a sequence) and
full-attention layers (``nn.MultiHeadAttention``: keys and values that
grow with the sequence), each followed by a gated MLP, RMSNorm on each
branch's OUTPUT (``h = x + norm(mixer(x))``, ``out = h + norm(mlp(h))``),
a final RMSNorm and an untied head.

It speaks the paged engine's four entry points (``init_page_pool``,
``prefill_chunk_at_paged``, ``verify_chunk_paged``,
``decode_step_paged``). Its pool is two named sub-trees::

    {"pages": [(k, v) per full layer],       leaves lead with PAGES
     "lanes": [(S, tail) per linear layer]}  leaves lead with LANES

``pages`` is what ``TransformerLM.init_page_pool`` returns (one block
table indexes every full layer); ``lanes`` holds one recurrent state a
serving lane, whatever the sequence's length. The prefill entry points
are told which lane each row fills (``lanes``), the decode step which
lanes are decoding (``active``: the others keep their state bit for
bit). A row whose ``pos0`` is 0 starts from a zero state, so admitting
a sequence needs no reset program.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import MultiHeadAttention, RMSNorm
from bigdl_tpu.nn.gated_delta import GatedDeltaNet, GatedMLP, project
from bigdl_tpu.nn.module import Module

LINEAR, FULL = "linear_attention", "full_attention"


class HybridBlock(Module):
    """One layer: a mixer of either kind, then the gated MLP, each
    branch normalized on its way back into the residual stream, which
    is float32 whatever the weights' dtype (every matrix product takes
    its operand in the weights' dtype; nothing between two products is
    rounded to it a second time)."""

    def __init__(self, kind: str, embed_dim: int, num_heads: int,
                 mlp_dim: int, num_kv_heads: Optional[int],
                 linear_heads: int, linear_key_dim: int,
                 linear_value_dim: int, conv_kernel: int,
                 allow_neg_eigval: bool, eps: float,
                 rope_theta: Optional[float]):
        super().__init__()
        if kind not in (LINEAR, FULL):
            raise ValueError(f"layer type {kind!r}: expected {LINEAR!r} "
                             f"or {FULL!r}")
        self.kind = kind
        if kind == LINEAR:
            self.mixer = GatedDeltaNet(
                embed_dim, linear_heads, linear_key_dim, linear_value_dim,
                conv_kernel=conv_kernel, allow_neg_eigval=allow_neg_eigval,
                norm_eps=eps)
        else:
            self.mixer = MultiHeadAttention(
                embed_dim, num_heads, with_bias=False, causal=True,
                num_kv_heads=num_kv_heads, qk_norm=True, norm_eps=eps,
                rotary=rope_theta is not None,
                rotary_base=rope_theta or 10000.0)
        self.mixer_norm = RMSNorm(embed_dim, eps)
        self.mlp = GatedMLP(embed_dim, mlp_dim)
        self.mlp_norm = RMSNorm(embed_dim, eps)

    def _rest(self, x, mixed):
        h = x + self.mixer_norm(mixed.astype(jnp.float32))
        return h + self.mlp_norm(self.mlp(h))

    def _served(self, x):
        """The residual stream as the full-attention mixer takes it: in
        the weights' dtype (its pages are)."""
        return x.astype(self.mlp.down.weight.dtype)

    def forward(self, input):
        x = input.astype(jnp.float32)
        return self._rest(x, self.mixer(
            x if self.kind == LINEAR else self._served(x)))


class HybridDecoderLM(Module):
    """Input (batch, time) int32 ids, output (batch, time, vocab) logits.
    ``layer_types`` names each layer's kind in order. ``rope_theta``
    None means the full-attention layers do not rotate (the
    convolutions and decays of the linear layers carry position)."""

    #: the serving engine asks: some layers hold a state per lane
    has_lane_state = True

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 layer_types: Sequence[str], mlp_dim: int, max_len: int,
                 num_kv_heads: Optional[int] = None,
                 linear_heads: Optional[int] = None,
                 linear_key_dim: int = 64, linear_value_dim: int = 128,
                 conv_kernel: int = 4, allow_neg_eigval: bool = True,
                 eps: float = 1e-6, rope_theta: Optional[float] = None):
        super().__init__()
        self.vocab_size, self.embed_dim = vocab_size, embed_dim
        self.max_len = max_len
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = embed_dim // num_heads
        self.register_parameter(
            "tok_embed", nn.init.RandomNormal(0.0, 0.02)(
                (vocab_size, embed_dim)))
        for i, kind in enumerate(self.layer_types):
            setattr(self, f"block{i}", HybridBlock(
                kind, embed_dim, num_heads, mlp_dim, num_kv_heads,
                linear_heads or num_heads, linear_key_dim,
                linear_value_dim, conv_kernel, allow_neg_eigval, eps,
                rope_theta))
        self.norm_f = RMSNorm(embed_dim, eps)
        self.head = nn.Linear(embed_dim, vocab_size, with_bias=False)

    def _embed(self, ids):
        return jnp.take(self.tok_embed, ids, axis=0).astype(jnp.float32)

    def _blocks(self, kind=None):
        return [getattr(self, f"block{i}")
                for i, k in enumerate(self.layer_types)
                if kind is None or k == kind]

    def _logits(self, x):
        lead = x.shape[:-1]
        x = self.norm_f(x).reshape(-1, self.embed_dim)
        return project(self.head, x).reshape(lead + (self.vocab_size,))

    def forward(self, input):
        x = self._embed(input.astype(jnp.int32))
        for blk in self._blocks():
            x = blk(x)
        return self._logits(x)

    # ------------------------------------------------- what the engine asks
    def kv_token_elems(self) -> int:
        """K and V elements one cached token holds over every layer that
        holds pages."""
        return (2 * len(self._blocks(FULL)) * self.num_kv_heads
                * self.head_dim)

    def kv_page_pool_sharding(self, mesh, model_axis: str = "model"):
        raise NotImplementedError(
            "HybridDecoderLM has no mesh layout: its lane state (one "
            "recurrent state a lane in every linear layer) is not "
            "sharded yet (ROADMAP: lane state under a mesh)")

    def _matmul_params(self) -> int:
        return sum(int(leaf.size) for leaf in jax.tree.leaves(
            self.params_dict())) - self.vocab_size * self.embed_dim

    def analytic_flops(self, tokens: int, context: int) -> float:
        """Forward FLOPs for ``tokens`` positions over ``context``
        cached ones: two a matmul weight, the score and value products
        of the full layers, and per linear layer the state's decay,
        read, update and query (about ``6 dk dv`` a head and token)."""
        lin = self._blocks(LINEAR)
        state = sum(6 * b.mixer.num_heads * b.mixer.key_dim
                    * b.mixer.value_dim for b in lin)
        per_tok = (2.0 * self._matmul_params()
                   + 4.0 * len(self._blocks(FULL)) * self.embed_dim
                   * max(0, int(context)) + state)
        return float(per_tok * max(0, int(tokens)))

    def analytic_bytes(self, tokens: int, context: int,
                       dtype_bytes: int = 2) -> float:
        """HBM traffic of the same pass: every parameter once, K and V
        of the full layers written a token and read ``context`` deep,
        each row's recurrent state read and written once a pass (taken
        as one row a token, the decode step's case)."""
        param_bytes = sum(int(leaf.size) * leaf.dtype.itemsize
                          for leaf in jax.tree.leaves(self.params_dict()))
        t, c = max(0, int(tokens)), max(0, int(context))
        state = sum(8 * b.mixer.num_heads * b.mixer.key_dim
                    * b.mixer.value_dim for b in self._blocks(LINEAR))
        return float(param_bytes
                     + self.kv_token_elems() * dtype_bytes * t * (1 + c)
                     + state * t)

    # ------------------------------------------------------------ the pool
    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32, sharding=None, kv_dtype=None,
                       lanes: int = 1):
        """``{"pages": [...], "lanes": [...]}``: K and V page leaves for
        each full layer (``MultiHeadAttention.init_page_pool``) and
        ``(S, tail)`` for each linear layer, ``lanes`` of them (the
        engine passes its slots plus one scratch lane that idle prefill
        rows write)."""
        if sharding is not None:
            self.kv_page_pool_sharding(sharding)      # raises: no layout
        if kv_dtype is not None:
            raise ValueError(
                "HybridDecoderLM serves its pages in the weights' dtype; "
                f"kv_dtype={kv_dtype!r} is not implemented for it")
        return {
            "pages": [b.mixer.init_page_pool(max_pages, page_size, dtype)
                      for b in self._blocks(FULL)],
            "lanes": [b.mixer.init_state(lanes, dtype)
                      for b in self._blocks(LINEAR)],
        }

    def prefill_chunk_at_paged(self, ids, pool, tables, pos0, last_idx,
                               lanes=None):
        """Each row's chunk from its own position: K and V scattered
        through ``tables``, lane ``lanes[row]``'s state advanced over
        the tokens up to ``last_idx[row]`` (a row at ``pos0`` 0 starts
        from zero). Logits at ``last_idx``."""
        x, pool = self._chunk(ids, pool, tables, pos0, lanes, last_idx + 1)
        x = jnp.take_along_axis(
            x, last_idx[:, None, None].astype(jnp.int32), axis=1)
        return self._logits(x)[:, 0], pool

    def verify_chunk_paged(self, ids, pool, tables, pos0, lanes=None):
        """Logits at every position of the chunk (all of it real)."""
        x, pool = self._chunk(ids, pool, tables, pos0, lanes, None)
        return self._logits(x), pool

    def _chunk(self, ids, pool, tables, pos0, lanes, n_valid):
        b = ids.shape[0]
        lanes = jnp.arange(b) if lanes is None else lanes
        fresh = pos0 == 0
        x = self._embed(ids)
        pages, states = list(pool["pages"]), list(pool["lanes"])
        i_full = i_lin = 0
        for blk in self._blocks():
            if blk.kind == FULL:
                mixed, pages[i_full] = blk.mixer.forward_chunk_paged(
                    blk._served(x), pages[i_full], tables, pos0)
                i_full += 1
            else:
                s_all, tail_all = states[i_lin]
                s = jnp.where(fresh[:, None, None, None], 0.0, s_all[lanes])
                tail = jnp.where(fresh[:, None, None], 0,
                                 tail_all[lanes]).astype(tail_all.dtype)
                mixed, (s, tail) = blk.mixer.forward_chunk(
                    x, (s, tail), n_valid)
                states[i_lin] = (s_all.at[lanes].set(s),
                                 tail_all.at[lanes].set(tail))
                i_lin += 1
            x = blk._rest(x, mixed)
        return x, {"pages": pages, "lanes": states}

    def decode_step_paged(self, ids_t, pos, pool, tables,
                          decode_attention="rows", active=None):
        """One token a row; row ``i`` is lane ``i``. ``active`` (B,)
        bool: only those lanes' recurrent state moves."""
        b = ids_t.shape[0]
        x = self._embed(ids_t)
        pages, states = list(pool["pages"]), list(pool["lanes"])
        i_full = i_lin = 0
        for blk in self._blocks():
            if blk.kind == FULL:
                mixed, pages[i_full] = blk.mixer.forward_step_paged(
                    blk._served(x)[:, None], pages[i_full], tables, pos,
                    decode_attention=decode_attention)
                mixed = mixed[:, 0]
                i_full += 1
            else:
                # every lane of the pool goes through the mixer (the
                # scratch lane and any beyond the batch as inactive
                # rows), so the state is updated in place under its
                # mask and never sliced out and written back
                spare = states[i_lin][0].shape[0] - b
                live = jnp.ones((b,), bool) if active is None else active
                mixed, states[i_lin] = blk.mixer.forward_step(
                    jnp.pad(x, ((0, spare), (0, 0))), states[i_lin],
                    jnp.pad(live, (0, spare)))
                mixed = mixed[:b]
                i_lin += 1
            x = blk._rest(x, mixed)
        return self._logits(x), {"pages": pages, "lanes": states}
