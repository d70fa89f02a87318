"""A decoder whose layers are of five kinds, named in order by
``layer_types``:

- ``linear_attention``: gated delta-rule layers (``nn.GatedDeltaNet``: a
  fixed recurrent state a sequence, and a short convolution's tail);
- ``lightning_attention``: fixed-decay linear attention with rotary
  positions (``nn.LightningAttention``: a fixed recurrent state);
- ``full_attention``: softmax attention over all of a sequence's keys and
  values (``nn.MultiHeadAttention``), which grow with the sequence;
- ``sparse_attention``: grouped-query softmax attention over the blocks a
  query selects through a cache of compressed keys
  (``nn.BlockSparseAttention``), whose keys, values and compressed keys
  grow with the sequence;
- ``latent_attention``: multi-head latent attention (``nn.LatentAttention``),
  whose cache is one low-rank latent a token and no heads;

each followed by a feed-forward branch, a gated MLP or, from layer
``experts["first_dense"]`` on where ``experts`` is given, routed experts
beside a shared one (``nn.RoutedExperts``: this chip's share of an
expert-parallel layer), in one of two block styles taken from the
configuration: ``post_norm`` (RMSNorm on each branch's OUTPUT, ``h = x +
norm(mixer(x))``, ``out = h + norm(mlp(h))``) or ``pre_norm`` (``h = x +
s * mixer(norm(x))``, ``out = h + s * mlp(norm(h))`` with the residual
scale ``s``); the embedding and the logits each take a scale; a final
RMSNorm and an untied head.

It speaks the paged engine's four entry points (``init_page_pool``,
``prefill_chunk_at_paged``, ``verify_chunk_paged``,
``decode_step_paged``). Its pool is two named sub-trees::

    {"pages": [one entry a full, sparse or latent layer],  leaves lead with PAGES
     "lanes": [one entry a linear or lightning layer]}       ... with LANES

``pages`` holds ``(k, v)`` for a full layer (what
``TransformerLM.init_page_pool`` returns), ``{"k", "v", "ck"}`` for a
sparse one (``ck``: one compressed key a page) and one leaf ``(pages,
page_size, row_width)`` for a latent one (its row up to whole lanes); one
block table indexes them all. ``lanes`` holds one recurrent state a serving lane, whatever the
sequence's length. The prefill entry points are told which lane each row
fills (``lanes``), the decode step which lanes are decoding (``active``:
the others keep their state bit for bit). A row whose ``pos0`` is 0 starts
from a zero state, so admitting a sequence needs no reset program.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import RMSNorm, normed
from bigdl_tpu.nn.gated_delta import GatedMLP, project
from bigdl_tpu.nn.module import Module, scoped

LINEAR, FULL = "linear_attention", "full_attention"
LIGHTNING, SPARSE = "lightning_attention", "sparse_attention"
LATENT = "latent_attention"
#: kinds whose cache grows with the sequence (pages), and the rest (a
#: fixed state a lane)
PAGED = (FULL, SPARSE, LATENT)
POST_NORM, PRE_NORM = "post_norm", "pre_norm"


class HybridBlock(Module):
    """One layer: a mixer of any kind, then the feed-forward branch (a
    gated MLP, or routed experts where ``experts`` is given), each branch
    normalized on its way back into the residual stream (``post_norm``)
    or on its way out of it (``pre_norm``, the branch scaled by
    ``residual_scale``). The stream is float32 whatever the weights'
    dtype (every matrix product takes its operand in the weights' dtype;
    nothing between two products is rounded to it a second time)."""

    def __init__(self, kind: str, embed_dim: int, num_heads: int,
                 mlp_dim: int, num_kv_heads: Optional[int],
                 linear_heads: int, linear_key_dim: int,
                 linear_value_dim: int, conv_kernel: int,
                 allow_neg_eigval: bool, eps: float,
                 rope_theta: Optional[float], style: str = POST_NORM,
                 residual_scale: float = 1.0,
                 sparse: Optional[dict] = None,
                 latent: Optional[dict] = None,
                 experts: Optional[dict] = None):
        super().__init__()
        head_dim = embed_dim // num_heads
        mixers = {
            LINEAR: lambda: nn.GatedDeltaNet(
                embed_dim, linear_heads, linear_key_dim, linear_value_dim,
                conv_kernel=conv_kernel, allow_neg_eigval=allow_neg_eigval,
                norm_eps=eps),
            FULL: lambda: nn.MultiHeadAttention(
                embed_dim, num_heads, with_bias=False, causal=True,
                num_kv_heads=num_kv_heads, qk_norm=True, norm_eps=eps,
                rotary=rope_theta is not None,
                rotary_base=rope_theta or 10000.0),
            LIGHTNING: lambda: nn.LightningAttention(
                embed_dim, num_heads, head_dim, rotary_base=rope_theta,
                norm_eps=eps),
            SPARSE: lambda: nn.BlockSparseAttention(
                embed_dim, num_heads, num_kv_heads or num_heads, head_dim,
                norm_eps=eps, **(sparse or {})),
            LATENT: lambda: nn.LatentAttention(
                embed_dim, num_heads, rope_theta=rope_theta or 10000.0,
                norm_eps=eps, **(latent or {})),
        }
        if kind not in mixers:
            raise ValueError(f"layer type {kind!r}: expected one of "
                             f"{sorted(mixers)}")
        if style not in (POST_NORM, PRE_NORM):
            raise ValueError(f"block style {style!r}: expected "
                             f"{POST_NORM!r} or {PRE_NORM!r}")
        self.kind, self.style = kind, style
        self.residual_scale = residual_scale
        self.mixer = mixers[kind]()
        self.mixer_norm = RMSNorm(embed_dim, eps)
        #: the feed-forward branch routes its tokens over experts
        self.routed = experts is not None
        self.mlp = (nn.RoutedExperts(embed_dim, **experts) if self.routed
                    else GatedMLP(embed_dim, mlp_dim))
        self.mlp_norm = RMSNorm(embed_dim, eps)

    def _enter(self, x):
        """What the mixer takes of the float32 stream ``x``."""
        if self.style == PRE_NORM:
            x = normed(self.mixer_norm, x)
        # the full-attention mixer takes the weights' dtype (its pages are)
        return (x.astype(self.mixer.out_proj.weight.dtype)
                if self.kind == FULL else x)

    def _rest(self, x, mixed, tally=None):
        """The two residuals. ``tally`` (a routed block only): ``(live,
        counts)``: the rows that are tokens, and the list that takes this
        layer's routing counts (``RoutedExperts.forward_counted``)."""
        pre = self.style == PRE_NORM
        with jax.named_scope("attn/out"):       # the mixer's residual
            mixed = mixed.astype(jnp.float32)
            h = x + (self.residual_scale * mixed if pre
                     else normed(self.mixer_norm, mixed))
        with jax.named_scope("mlp"):            # and the MLP's
            n = normed(self.mlp_norm, h) if pre else h
            if self.routed and tally is not None:
                y, counts = self.mlp.forward_counted(n, tally[0])
                tally[1].append(counts)
            else:
                y = self.mlp(n)
            if pre:
                return h + self.residual_scale * y
            return h + normed(self.mlp_norm, y)

    def forward(self, input):
        x = input.astype(jnp.float32)
        return self._rest(x, self.mixer(self._enter(x)))


class HybridDecoderLM(Module):
    """Input (batch, time) int32 ids, output (batch, time, vocab) logits.
    ``layer_types`` names each layer's kind in order. ``rope_theta`` is
    the rotation of the full-attention and lightning layers (which take
    the attention's heads); None means they do not rotate (the
    convolutions and decays of the linear layers carry position). The
    sparse layers never rotate.
    ``sparse`` holds the sparse layers' sizes (``nn.BlockSparseAttention``'s
    keywords). ``block_style``, ``residual_scale``, ``embed_scale`` and
    ``logit_scale`` are the block's layout and the three scales of a
    muP-parametrized model (``x0 = embed_scale * E[id]``, ``logits =
    head(norm(x) * logit_scale)``). ``latent`` holds the latent layers'
    sizes (``nn.LatentAttention``'s keywords; they rotate by
    ``rope_theta``). ``experts`` makes the feed-forward branch of every
    layer from ``experts["first_dense"]`` on routed
    (``nn.RoutedExperts``'s keywords beside that key; the layers before it
    keep the gated MLP of ``mlp_dim``)."""

    def __init__(self, vocab_size: int, embed_dim: int, num_heads: int,
                 layer_types: Sequence[str], mlp_dim: int, max_len: int,
                 num_kv_heads: Optional[int] = None,
                 linear_heads: Optional[int] = None,
                 linear_key_dim: int = 64, linear_value_dim: int = 128,
                 conv_kernel: int = 4, allow_neg_eigval: bool = True,
                 eps: float = 1e-6, rope_theta: Optional[float] = None,
                 block_style: str = POST_NORM, residual_scale: float = 1.0,
                 embed_scale: float = 1.0, logit_scale: float = 1.0,
                 sparse: Optional[dict] = None,
                 latent: Optional[dict] = None,
                 experts: Optional[dict] = None):
        super().__init__()
        self.vocab_size, self.embed_dim = vocab_size, embed_dim
        self.max_len = max_len
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = embed_dim // num_heads
        self.embed_scale, self.logit_scale = embed_scale, logit_scale
        self.register_parameter(
            "tok_embed", nn.init.RandomNormal(0.0, 0.02)(
                (vocab_size, embed_dim)))
        routed = dict(experts or {})
        first_routed = routed.pop("first_dense", 0)
        for i, kind in enumerate(self.layer_types):
            setattr(self, f"block{i}", HybridBlock(
                kind, embed_dim, num_heads, mlp_dim, num_kv_heads,
                linear_heads or num_heads, linear_key_dim,
                linear_value_dim, conv_kernel, allow_neg_eigval, eps,
                rope_theta, block_style, residual_scale, sparse, latent,
                routed if experts and i >= first_routed else None))
        self.norm_f = RMSNorm(embed_dim, eps)
        self.head = nn.Linear(embed_dim, vocab_size, with_bias=False)

    @scoped("embed")
    def _embed(self, ids):
        x = jnp.take(self.tok_embed, ids, axis=0).astype(jnp.float32)
        return x if self.embed_scale == 1.0 else x * self.embed_scale

    def _blocks(self, *kinds):
        return [getattr(self, f"block{i}")
                for i, k in enumerate(self.layer_types)
                if not kinds or k in kinds]

    @property
    def has_lane_state(self) -> bool:
        """The serving engine asks: do some layers hold a state per lane
        (a model of paged kinds alone is served as pages are)."""
        return bool(self._blocks(LINEAR, LIGHTNING))

    @property
    def routed_layers(self) -> int:
        """The serving engine asks: layers whose feed-forward branch routes
        (its decode step then hands out their routing counts)."""
        return sum(b.routed for b in self._blocks())

    @scoped("head")
    def _logits(self, x):
        lead = x.shape[:-1]
        x = self.norm_f(x).reshape(-1, self.embed_dim)
        if self.logit_scale != 1.0:
            x = x * self.logit_scale
        return project(self.head, x).reshape(lead + (self.vocab_size,))

    def forward(self, input):
        x = self._embed(input.astype(jnp.int32))
        for blk in self._blocks():
            x = blk(x)
        return self._logits(x)

    # ------------------------------------------------- what the engine asks
    def kv_token_elems(self) -> float:
        """Cache elements one token holds over every layer that holds
        pages: K and V, a sparse layer's share of its page's compressed
        key, a latent layer's one row (whole lanes wide, as the leaf
        holds it)."""
        kv = 2 * self.num_kv_heads * self.head_dim
        return (len(self._blocks(FULL, SPARSE)) * kv
                + sum(kv / 2 / b.mixer.kernel_stride
                      for b in self._blocks(SPARSE))
                + sum(b.mixer.row_width for b in self._blocks(LATENT)))

    def kv_page_pool_sharding(self, mesh, model_axis: str = "model"):
        raise NotImplementedError(
            "HybridDecoderLM has no mesh layout: its lane state (one "
            "recurrent state a lane in every linear or lightning layer) "
            "is not sharded, a latent layer's leaf has no heads to shard "
            "by, and routed experts have no exchange on the served path "
            "yet (ROADMAP Reach: lane state, a latent leaf and experts "
            "under a mesh)")

    def _matmul_params(self) -> float:
        """Weights one token multiplies: every matrix but the embedding,
        and of a routed layer's held experts the ones it is expected to
        choose (``top_k`` of ``n_routed``, evenly)."""
        every = sum(int(leaf.size) for leaf in jax.tree.leaves(
            self.params_dict())) - self.vocab_size * self.embed_dim
        for b in self._blocks():
            if b.routed:
                held = 3 * b.mlp.held[1] * b.mlp.expert_dim * self.embed_dim
                every -= held * (1 - b.mlp.top_k / b.mlp.n_routed)
        return every

    def _state_elems(self) -> int:
        """Elements of the recurrent matrices one lane holds."""
        return (sum(b.mixer.num_heads * b.mixer.key_dim * b.mixer.value_dim
                    for b in self._blocks(LINEAR))
                + sum(b.mixer.num_heads * b.mixer.head_dim ** 2
                      for b in self._blocks(LIGHTNING)))

    def _attended(self, context: int) -> float:
        """Cached tokens the full and sparse layers read for one query
        over ``context`` cached ones, summed over those layers: all of
        them in a full layer, the selected ones in a sparse layer."""
        c = max(0, int(context))
        return (len(self._blocks(FULL)) * c
                + sum(min(c, int(b.mixer.attended_tokens(max(c - 1, 0))))
                      for b in self._blocks(SPARSE)))

    def decode_read_counts(self, positions, table_pages: int):
        """What a selecting layer reads for one decode token at each of
        ``positions`` through block tables of ``table_pages`` pages (host
        arithmetic for the engine's span and counters), summed over the
        rows: the tokens the rule attends, the tokens' worth of pages the
        step gathers for them, the tokens the rows hold, and the rows at
        or over the length from which the layers select; None when no
        layer selects."""
        sparse = self._blocks(SPARSE)
        if not sparse:
            return None
        t = np.asarray(positions, np.int64)
        mixer = sparse[0].mixer
        return {"attended_tokens": int(mixer.attended_tokens(t).sum()),
                "gathered_tokens": int(
                    mixer.gathered_tokens(t, table_pages).sum()),
                "cached_tokens": int((t + 1).sum()),
                "selecting_rows": int((t >= mixer.dense_len).sum())}

    def prefill_read_counts(self, pos0, chunk: int, page_size: int,
                            table_pages: int):
        """What one full-attention or latent layer of a prefill dispatch
        gathers of what its rows' tables hold (the mixer's
        ``chunk_read_counts``; host arithmetic for the engine's span and
        counters); None when no layer is of those kinds."""
        whole = self._blocks(FULL, LATENT)
        if not whole:
            return None
        return whole[0].mixer.chunk_read_counts(pos0, chunk, page_size,
                                                table_pages)

    def step_read_counts(self, pos, page_size: int, table_pages: int,
                         decode_attention: str = "rows"):
        """What one full-attention or latent layer of a decode dispatch
        reads of what its rows' tables hold (the mixer's
        ``step_read_counts``; host arithmetic for the engine's span and
        counters); None when no layer is of those kinds."""
        whole = self._blocks(FULL, LATENT)
        if not whole:
            return None
        return whole[0].mixer.step_read_counts(pos, page_size, table_pages,
                                               decode_attention)

    def analytic_flops(self, tokens: int, context: int) -> float:
        """Forward FLOPs for ``tokens`` positions over ``context``
        cached ones: two a matmul weight, the score and value products
        over the tokens the paged layers attend (all of them in a full
        layer, the selected ones and the compressed keys in a sparse
        one; a latent layer in its absorbed form: every head against the
        cached row, and against the latent again for the values), and per
        recurrent layer the state's decay, read, update and query (about
        6 an element and token)."""
        c = max(0, int(context))
        scored = sum(c / b.mixer.kernel_stride
                     for b in self._blocks(SPARSE))
        absorbed = sum(
            2.0 * b.mixer.num_heads * (b.mixer.row_elems
                                       + b.mixer.kv_lora_rank) * c
            for b in self._blocks(LATENT))
        per_tok = (2.0 * self._matmul_params()
                   + 4.0 * self.embed_dim * self._attended(context)
                   + 2.0 * self.embed_dim * scored + absorbed
                   + 6 * self._state_elems())
        return float(per_tok * max(0, int(tokens)))

    def analytic_bytes(self, tokens: int, context: int,
                       dtype_bytes: int = 2) -> float:
        """HBM traffic of the same pass: every parameter once, a token's
        cache elements written, K and V read over the tokens attended and
        a sparse layer's compressed keys and a latent layer's rows over
        the whole context, each row's recurrent state read and written
        once a pass (taken as one row a token, the decode step's case)."""
        param_bytes = sum(int(leaf.size) * leaf.dtype.itemsize
                          for leaf in jax.tree.leaves(self.params_dict()))
        t, c = max(0, int(tokens)), max(0, int(context))
        kv = 2 * self.num_kv_heads * self.head_dim
        compressed = sum(c * kv / 2 / b.mixer.kernel_stride
                         for b in self._blocks(SPARSE))
        latent = sum(c * b.mixer.row_width for b in self._blocks(LATENT))
        return float(param_bytes + dtype_bytes * t * (
            self.kv_token_elems() + kv * self._attended(c) + compressed
            + latent) + 8 * self._state_elems() * t)

    # ------------------------------------------------------------ the pool
    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32, sharding=None, kv_dtype=None,
                       lanes: int = 1):
        """``{"pages": [...], "lanes": [...]}``: the page leaves of each
        full, sparse or latent layer (its mixer's ``init_page_pool``) and
        the state of each linear or lightning layer, ``lanes`` of them
        (the engine passes its slots plus one scratch lane that idle
        prefill rows write)."""
        if sharding is not None:
            self.kv_page_pool_sharding(sharding)      # raises: no layout
        if kv_dtype is not None:
            raise ValueError(
                "HybridDecoderLM serves its pages (K and V, a sparse "
                "layer's compressed keys, a latent layer's rows) in the "
                f"weights' dtype; kv_dtype={kv_dtype!r} is not implemented "
                "for it")
        return {
            "pages": [b.mixer.init_page_pool(max_pages, page_size, dtype)
                      for b in self._blocks(*PAGED)],
            "lanes": [b.mixer.init_state(lanes, dtype)
                      for b in self._blocks(LINEAR, LIGHTNING)],
        }

    def prefill_chunk_at_paged(self, ids, pool, tables, pos0, last_idx,
                               lanes=None):
        """Each row's chunk from its own position: K and V scattered
        through ``tables``, lane ``lanes[row]``'s state advanced over
        the tokens up to ``last_idx[row]`` (a row at ``pos0`` 0 starts
        from zero). Logits at ``last_idx``."""
        x, pool = self._chunk(ids, pool, tables, pos0, lanes, last_idx)
        with jax.named_scope("head"):
            x = jnp.take_along_axis(
                x, last_idx[:, None, None].astype(jnp.int32), axis=1)
        return self._logits(x)[:, 0], pool

    def verify_chunk_paged(self, ids, pool, tables, pos0, lanes=None):
        """Logits at every position of the chunk (all of it real)."""
        x, pool = self._chunk(ids, pool, tables, pos0, lanes, None)
        return self._logits(x), pool

    def _chunk(self, ids, pool, tables, pos0, lanes, last_idx):
        """``last_idx`` (B,) or None: each row's last real token (behind it
        lies padding: a recurrent state stops there, and a routed layer
        gives the rest no expert slot)."""
        n_valid = None if last_idx is None else last_idx + 1
        b = ids.shape[0]
        lanes = jnp.arange(b) if lanes is None else lanes
        fresh = pos0 == 0
        x = self._embed(ids)
        pages, states = list(pool["pages"]), list(pool["lanes"])
        tally = None
        if self.routed_layers:
            with jax.named_scope("moe/route"):
                tally = (None if last_idx is None else
                         jnp.arange(ids.shape[1])[None] <= last_idx[:, None],
                         [])
        i_page = i_lane = 0
        for blk in self._blocks():
            inp = blk._enter(x)
            if blk.kind in PAGED:
                mixed, pages[i_page] = blk.mixer.forward_chunk_paged(
                    inp, pages[i_page], tables, pos0)
                i_page += 1
            else:
                # the rows' lanes, from zero where a row starts afresh
                # (the lanes' read and write go with the recurrence; the
                # mixer's own scopes inside are the innermost and win)
                with jax.named_scope("gdn/chunk" if blk.kind == LINEAR
                                     else "lightning/chunk"):
                    state = jax.tree.map(
                        lambda a: jnp.where(
                            fresh.reshape((b,) + (1,) * (a.ndim - 1)), 0,
                            a[lanes]).astype(a.dtype), states[i_lane])
                    if blk.kind == LINEAR:
                        mixed, state = blk.mixer.forward_chunk(
                            inp, state, n_valid)
                    else:
                        mixed, state = blk.mixer.forward_chunk(
                            inp, state, pos0, n_valid)
                    states[i_lane] = jax.tree.map(
                        lambda a, new: a.at[lanes].set(new),
                        states[i_lane], state)
                i_lane += 1
            x = blk._rest(x, mixed, tally)
        return x, {"pages": pages, "lanes": states}

    def decode_step_paged(self, ids_t, pos, pool, tables,
                          decode_attention="rows", active=None,
                          routing=False):
        """One token a row; row ``i`` is lane ``i``. ``active`` (B,)
        bool: only those lanes' recurrent state moves, and only those
        rows take slots of a routed layer's experts. ``routing`` (a model
        with routed layers): a third result, int32 (4,), summed over those
        layers: the assignments that fell on held experts, the held
        experts some row chose, the fullest expert's rows, the experts
        held."""
        b = ids_t.shape[0]
        x = self._embed(ids_t)
        pages, states = list(pool["pages"]), list(pool["lanes"])
        live = jnp.ones((b,), bool) if active is None else active
        tally = (active, []) if self.routed_layers else None
        i_page = i_lane = 0
        for blk in self._blocks():
            inp = blk._enter(x)
            if blk.kind == LATENT:
                mixed, pages[i_page] = blk.mixer.forward_step_paged(
                    inp, pages[i_page], tables, pos,
                    decode_attention=decode_attention)
                i_page += 1
            elif blk.kind == FULL:
                mixed, pages[i_page] = blk.mixer.forward_step_paged(
                    inp[:, None], pages[i_page], tables, pos,
                    decode_attention=decode_attention)
                mixed = mixed[:, 0]
                i_page += 1
            elif blk.kind == SPARSE:
                mixed, pages[i_page] = blk.mixer.forward_step_paged(
                    inp, pages[i_page], tables, pos)
                i_page += 1
            else:
                # every lane of the pool goes through the mixer (the
                # scratch lane and any beyond the batch as inactive
                # rows), so the state is updated in place under its
                # mask and never sliced out and written back
                spare = jax.tree.leaves(states[i_lane])[0].shape[0] - b
                with jax.named_scope("gdn/step" if blk.kind == LINEAR
                                     else "lightning/step"):
                    wide = jnp.pad(inp, ((0, spare), (0, 0)))
                    if blk.kind == LINEAR:
                        mixed, states[i_lane] = blk.mixer.forward_step(
                            wide, states[i_lane], jnp.pad(live, (0, spare)))
                    else:
                        mixed, states[i_lane] = blk.mixer.forward_step(
                            wide, states[i_lane], jnp.pad(pos, (0, spare)),
                            jnp.pad(live, (0, spare)))
                    mixed = mixed[:b]
                i_lane += 1
            x = blk._rest(x, mixed, tally)
        pool = {"pages": pages, "lanes": states}
        if routing:
            return self._logits(x), pool, sum(tally[1])
        return self._logits(x), pool
