"""Transformer language model — the long-context flagship.

Beyond-parity model (the reference's sequence stack is RNN-only,
models/rnn/SimpleRNN.scala); this is the workload that exercises ring
attention / Ulysses sequence parallelism and tensor parallelism on the
mesh. Decoder-only, pre-norm, GELU MLP, learned positions, weight-tied head.
"""

from __future__ import annotations

import weakref
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.attention import LayerNorm, TransformerBlock
from bigdl_tpu.nn.module import Module, scoped

# jitted decode fns cached per live model instance (weak: a saved/cloned
# model never carries a jit wrapper through pickle)
_DECODE_JIT = weakref.WeakKeyDictionary()
_BEAM_JIT = weakref.WeakKeyDictionary()
_BEAM_SCAN_JIT = weakref.WeakKeyDictionary()
_SPEC_JIT = weakref.WeakKeyDictionary()


def _tree_leaves(tree):
    """Array leaves of a nested params/buffers dict (cost helpers)."""
    return jax.tree_util.tree_leaves(tree)


def _filter_logits(logits, temperature, top_k, top_p):
    """Tempered logits with standard top-k / nucleus (top-p) filtering
    applied (in that order, HF-style) — disallowed tokens get -inf so
    ``jax.random.categorical`` never samples them."""
    x = logits.astype(jnp.float32) / temperature
    v = x.shape[-1]
    if top_k is not None and top_k < v:
        kth = jax.lax.top_k(x, top_k)[0][..., -1:]
        x = jnp.where(x < kth, -jnp.inf, x)
    if top_p is not None and top_p < 1.0:
        probs = jax.nn.softmax(x)
        order = jnp.argsort(-probs, axis=-1)          # descending
        sp = jnp.take_along_axis(probs, order, axis=-1)
        cum = jnp.cumsum(sp, axis=-1)
        # smallest prefix whose mass reaches top_p; the top token is kept
        # unconditionally (min_tokens_to_keep=1) so no top_p value can
        # mask the whole vocabulary into a NaN distribution
        keep_sorted = (cum - sp < top_p).at[..., 0].set(True)
        # scatter the keep-mask back through the sort indices (inverse
        # permutation = argsort of the order): exactly the sorted prefix
        # survives — a tie AT the nucleus boundary no longer admits every
        # equal-probability token outside the prefix (HF semantics)
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        x = jnp.where(keep, x, -jnp.inf)
    return x


def _validate_sampling(sampled: bool, top_k, top_p):
    """The sampling-config API contract, shared by generate /
    generate_ragged (and mirrored by GenerationService)."""
    if not sampled and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p filter the SAMPLED distribution; pass "
            "temperature > 0 (greedy decoding would silently ignore "
            "them)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def _sample_next(logits, rng, done, sampled, temperature, eos_id,
                 top_k, top_p):
    """One sampling decision, shared by the scanned and host decode
    loops (identical key schedule: exactly one split per sampled token).
    Rows already ``done`` keep emitting ``eos_id``."""
    if sampled:
        rng, sub = jax.random.split(rng)
        nxt = jax.random.categorical(
            sub, _filter_logits(logits, temperature, top_k, top_p),
            axis=-1).astype(jnp.int32)
    else:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if eos_id is not None:
        nxt = jnp.where(done, eos_id, nxt)
        done = done | (nxt == eos_id)
    return nxt, rng, done


@jax.jit
def _spec_accept(p_logits, q_logits, props, temperature, rng):
    """Speculative-sampling acceptance (Leviathan et al. 2023, Thm 1):
    given target logits ``p_logits`` (B, g+1, V) at positions
    pos..pos+g, draft logits ``q_logits`` (B, g, V) and sampled
    proposals ``props`` (B, g), return per-proposal acceptance
    (U < p(x)/q(x)), a residual sample from norm(max(p - q, 0)) for
    every position (used at each row's first rejection), and a bonus
    sample from p at position g (used on full acceptance). Taking the
    proposal where accepted and the residual where rejected is
    distributed EXACTLY as p — the identity a unit test pins
    empirically."""
    b, g = props.shape
    p = jax.nn.softmax(p_logits.astype(jnp.float32) / temperature, axis=-1)
    q = jax.nn.softmax(q_logits.astype(jnp.float32) / temperature, axis=-1)
    p_at = jnp.take_along_axis(p[:, :g], props[..., None], axis=-1)[..., 0]
    q_at = jnp.take_along_axis(q, props[..., None], axis=-1)[..., 0]
    r_accept, r_resid, r_bonus = jax.random.split(rng, 3)
    u = jax.random.uniform(r_accept, (b, g))
    accept = u * q_at < p_at          # U < p/q without the 0/0 division
    resid = jnp.maximum(p[:, :g] - q, 0.0)
    mass = jnp.sum(resid, axis=-1, keepdims=True)
    # p == q -> empty residual; that position always accepts, so the
    # fallback (sample from p) is never USED, it just keeps gumbel finite
    resid = jnp.where(mass > 0.0, resid / jnp.maximum(mass, 1e-30),
                      p[:, :g])
    resid_toks = jax.random.categorical(
        r_resid, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1
    ).astype(jnp.int32)
    bonus = jax.random.categorical(
        r_bonus, jnp.log(jnp.maximum(p[:, g], 1e-30)), axis=-1
    ).astype(jnp.int32)
    return accept, resid_toks, bonus


def _gather_beam_lineage(caches, idx, b, k):
    """Reorder (B*K, ...) KV caches so row j follows beam j's surviving
    lineage: ``idx[b, j]`` names the parent beam whose cache the new
    beam j extends (shared by the scanned and per-step beam paths)."""
    return jax.tree.map(
        lambda c: jax.vmap(lambda cb, ix: cb[ix])(
            c.reshape(b, k, *c.shape[1:]), idx
        ).reshape(b * k, *c.shape[1:]), caches)


class TransformerLM(Module):
    """Decoder-only LM. Input: (batch, time) int32 token ids (0-based).
    Output: (batch, time, vocab) logits."""

    #: summed MoE load-balancing loss of the last forward (0.0 until a
    #: forward runs, and always 0.0 for dense models)
    l_aux = 0.0

    #: Routing stats (drop_rate, expert_fraction) averaged over the MoE
    #: blocks of the last forward — same trace-lifetime rules as l_aux.
    last_moe_stats = None

    def __init__(self, vocab_size: int, embed_dim: int = 256,
                 num_heads: int = 8, num_layers: int = 4,
                 max_len: int = 1024, mlp_ratio: int = 4,
                 dropout: float = 0.0, causal: bool = True,
                 sequence_parallel: Optional[str] = None,
                 tie_embeddings: bool = True, use_flash: bool = False,
                 remat: bool = False, n_experts: int = 0,
                 expert_parallel: Optional[str] = None,
                 num_kv_heads: Optional[int] = None,
                 use_rope: bool = False):
        super().__init__()
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.sequence_parallel = sequence_parallel
        self.tie_embeddings = tie_embeddings
        # RoPE replaces the learned positional table (rotations happen
        # inside each attention layer); max_len then only bounds caches
        self.use_rope = use_rope
        self.max_len = max_len
        self.register_parameter(
            "tok_embed", nn.init.RandomNormal(0.0, 0.02)((vocab_size, embed_dim)))
        if not use_rope:
            self.register_parameter(
                "pos_embed", nn.init.RandomNormal(0.0, 0.02)((max_len, embed_dim)))
        for i in range(num_layers):
            setattr(self, f"block{i}",
                    TransformerBlock(embed_dim, num_heads, mlp_ratio=mlp_ratio,
                                     dropout=dropout, causal=causal,
                                     sequence_parallel=sequence_parallel,
                                     use_flash=use_flash, n_experts=n_experts,
                                     expert_parallel=expert_parallel,
                                     num_kv_heads=num_kv_heads,
                                     rotary=use_rope))
        self.ln_f = LayerNorm(embed_dim)
        if not tie_embeddings:
            self.head = nn.Linear(embed_dim, vocab_size, with_bias=False)
        self.num_layers = num_layers
        self.n_experts = n_experts
        #: rematerialize each block in backward (jax.checkpoint): activation
        #: memory drops from O(layers * T * D) to O(T * D) at ~1.3x FLOPs —
        #: the standard long-context trade. Key-splitting happens at trace
        #: time, so dropout masks replay identically in the recompute.
        self.remat = remat

    def forward(self, input):
        ids = input.astype(jnp.int32)
        b, t = ids.shape
        with jax.named_scope("embed"):
            x = jnp.take(self.tok_embed, ids, axis=0)
            if not self.use_rope:  # RoPE rotates inside each attention layer
                if self.sequence_parallel is not None:
                    # each device holds sequence block axis_index: offset pos
                    idx = jax.lax.axis_index(self.sequence_parallel)
                    pos0 = idx * t
                else:
                    pos0 = 0
                pos = jax.lax.dynamic_slice_in_dim(self.pos_embed, pos0, t,
                                                   axis=0)
                x = x + pos[None]
        aux_total = 0.0
        moe_stats = []
        for i in range(self.num_layers):
            blk = getattr(self, f"block{i}")
            if self.remat:
                # the block's RNG draws must cross the checkpoint boundary as
                # an explicit ARGUMENT and the MoE aux loss + routing stats
                # as explicit OUTPUTS: stashing any of them through global/
                # module state inside the remat trace would leak its tracers
                from bigdl_tpu.utils import random as bt_random

                moe = blk.n_experts > 0

                def run(t, kk, b=blk, moe=moe):
                    bt_random.RNG.push_key(kk)
                    try:
                        # NO module-state stash inside the checkpoint trace;
                        # aux + stats leave as explicit outputs
                        out, aux, stats = b.forward_with_aux_stats(t)
                    finally:
                        bt_random.RNG.pop_key()
                    return (out, aux, stats) if moe else out

                res = jax.checkpoint(run)(x, bt_random.next_key())
                if moe:
                    x, aux, stats = res
                    aux_total = aux_total + aux
                    moe_stats.append(stats)
                else:
                    x = res
            else:
                # same explicit aux routing as the remat path — one
                # convention, no side-channel dependency
                x, aux, stats = blk.forward_with_aux_stats(x)
                if blk.n_experts > 0:
                    aux_total = aux_total + aux
                    moe_stats.append(stats)
        if self.n_experts > 0:
            # summed MoE load-balancing loss of this forward; read it inside
            # the same trace (add ``model.l_aux`` to the objective). Valid in
            # both remat modes — unlike block.mlp.l_aux, which holds a dead
            # inner tracer under remat. Routing stats are averaged over the
            # MoE blocks and stashed the same way (feed record_moe_metrics).
            self.l_aux = aux_total
            n = len(moe_stats)
            self.last_moe_stats = jax.tree.map(
                lambda *leaves: sum(leaves) / n, *moe_stats)
        with jax.named_scope("head"):
            x = self.ln_f(x)
            if self.tie_embeddings:
                logits = jnp.einsum("btc,vc->btv", x, self.tok_embed)
            else:
                logits = self.head(x.reshape(b * t, -1)).reshape(b, t, -1)
        return logits

    # ------------------------------------------------- KV-cache decoding
    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   sharding=None, kv_dtype=None):
        """Per-block attention KV caches for incremental decoding;
        ``sharding`` allocates each buffer directly with that layout.
        ``kv_dtype="int8"`` allocates the QUANTIZED per-block form
        ``(k_q, v_q, k_scale, v_scale)`` — int8 codes plus f32 scale
        sidecars (see ``MultiHeadAttention.init_cache``); every
        prefill / decode / verify entry point detects the form per
        block, so callers treat both cache trees opaquely."""
        return [getattr(self, f"block{i}").attn.init_cache(
                    batch, max_len, dtype, sharding=sharding,
                    kv_dtype=kv_dtype)
                for i in range(self.num_layers)]

    @property
    def num_kv_heads(self) -> int:
        """KV head count of the attention stack (uniform across blocks
        — the constructor builds every block from one config). The
        dimension tensor-parallel serving shards the KV pools along."""
        return self.block0.attn.num_kv_heads

    def kv_token_elems(self) -> int:
        """K and V elements one cached token holds over every layer."""
        return (2 * self.num_layers * self.num_kv_heads
                * self.block0.attn.head_dim)

    def kv_page_pool_sharding(self, mesh, model_axis: str = "model"):
        """NamedSharding for this model's ``init_page_pool`` buffers on
        a tensor-parallel ``mesh``: leaves ``(max_pages, page_size,
        H_kv * D)`` shard their LAST dimension, the heads, along
        ``model_axis`` — the layout the column-parallel QKV projection
        (``transformer_tp_rules``) writes with no collective, because
        each device computes exactly its own heads' K/V. Every compiled
        prefill / decode / verify entry point then runs SPMD from the
        input shardings alone (GSPMD places the row-parallel
        all-reduces); raises when the head count does not divide the
        axis size."""
        from bigdl_tpu.parallel.tp import kv_page_pool_sharding

        return kv_page_pool_sharding(mesh, self.num_kv_heads,
                                     model_axis=model_axis)

    # ------------------------------------------------ analytic cost model
    def param_count(self) -> int:
        """Total parameter count (all leaves of ``params_dict``)."""
        import math

        total = 0
        for leaf in _tree_leaves(self.params_dict()):
            total += int(math.prod(leaf.shape)) if leaf.shape else 1
        return total

    def matmul_param_count(self) -> int:
        """Parameters that participate in per-token matmuls: everything
        except the embedding tables (token lookup is a gather, learned
        positions are an add), **plus** the tied output head when
        ``tie_embeddings`` re-uses ``tok_embed`` as a ``D x V``
        projection — the analytic-FLOPs numerator."""
        emb = self.vocab_size * self.embed_dim
        pos = 0 if self.use_rope else self.max_len * self.embed_dim
        mat = self.param_count() - emb - pos
        if self.tie_embeddings:
            mat += emb  # tok_embed doubles as the output projection
        return mat

    def analytic_flops(self, tokens: int, context: int) -> float:
        """Analytic forward FLOPs for ``tokens`` positions attending
        over ``context`` cached positions: the standard transformer
        estimate ``2 x matmul-params`` per token plus the attention
        score/value matmuls ``4 x layers x embed_dim x context`` per
        token.  Spec-aware by construction — a verify pass is just
        ``tokens = rows x (gamma + 1)`` at the same context; a decode
        step is ``tokens = rows`` — and the fallback when XLA's
        ``cost_analysis`` reports nothing."""
        per_tok = (2.0 * self.matmul_param_count()
                   + 4.0 * self.num_layers * self.embed_dim
                   * max(0, int(context)))
        return float(per_tok * max(0, int(tokens)))

    def analytic_bytes(self, tokens: int, context: int,
                       dtype_bytes: int = 4) -> float:
        """Analytic HBM traffic for the same pass: one read of every
        parameter, plus KV-cache traffic — one K/V write per new token
        and a ``context``-deep K/V read per token attended."""
        param_bytes = 0
        for leaf in _tree_leaves(self.params_dict()):
            param_bytes += int(getattr(leaf, "nbytes", 0) or 0)
        head_dim = self.embed_dim // self.block0.attn.num_heads
        kv_tok = 2 * self.num_layers * self.num_kv_heads * head_dim \
            * dtype_bytes
        t, c = max(0, int(tokens)), max(0, int(context))
        return float(param_bytes + kv_tok * t * (1 + c))

    def prefill(self, ids, caches, pos0: int = 0):
        """Batched prompt prefill: one causal pass over ids (B, T0) that
        populates every block's KV cache and returns the LAST position's
        logits — O(T0²) once vs T0 masked full-cache steps.

        ``pos0`` (static int) makes it a CONTINUATION prefill: the chunk
        attends over the cached ``[0, pos0)`` prefix too — the building
        block for chunked long-prompt prefill (bounded O(chunk·T) score
        memory) and multi-turn serving (feed each turn as a chunk)."""
        return self._prefill_impl(ids, caches, pos0, chunked=False)

    def prefill_chunk(self, ids, caches, pos0):
        """One fixed-length chunk of a chunked prefill (TRACED ``pos0`` —
        one compilation serves every offset). Returns the chunk's last
        position's logits + updated caches. Caller contract: ``pos0 +
        chunk <= cache length`` (see MultiHeadAttention.forward_chunk —
        a traced offset cannot be bounds-checked at trace time)."""
        return self._prefill_impl(ids, caches, pos0, chunked=True)

    def prefill_chunk_at(self, ids, caches, pos0, last_idx):
        """``prefill_chunk`` variant returning the logits at per-row
        position ``last_idx`` (B,) WITHIN the chunk instead of the
        chunk's final position — the continuous-batching engine's
        admission path (bigdl_tpu/serving/engine.py), whose final chunk
        is RIGHT-padded so the true last prompt token sits mid-chunk.
        ``pos0`` may be a (B,) vector of per-row offsets (the RAGGED
        batched-prefill path: each row is an independent chunked
        prefill at its own depth — see
        MultiHeadAttention.forward_chunk). The gather happens before
        the head: O(B), not O(B*T), vocab projections. Same caller
        contract as ``prefill_chunk``."""
        return self._prefill_impl(ids, caches, pos0, chunked=True,
                                  gather_last=last_idx)

    def verify_chunk(self, ids, caches, pos0):
        """Chunked forward (traced ``pos0``) returning logits at EVERY
        chunk position, (B, T, V) — the speculative-decoding verifier:
        one pass scores all draft proposals at once. Writes the chunk
        tokens' KV like prefill_chunk (same caller contract).

        ``pos0`` may be a (B,) vector of per-row offsets — the BATCHED
        RAGGED verify entry point: each row's gamma+1-token proposal
        chunk is scored at that row's OWN cache depth in one dispatch
        (rows at different sequence positions, the continuous-batching
        engine's slot-pooled speculative decode — see
        ``bigdl_tpu.serving.engine``). Rides the same
        ``forward_chunk`` ragged machinery as batched prefill, so one
        compiled program serves every mix of per-row depths; caller
        contract is per-row: ``pos0[r] + T <= cache length`` (an
        overflowing row would silently clamp-corrupt its prefix)."""
        return self._prefill_impl(ids, caches, pos0, chunked=True,
                                  all_logits=True)

    def _prefill_impl(self, ids, caches, pos0, chunked: bool,
                      all_logits: bool = False, gather_last=None):
        """``gather_last`` (B,) selects ONE hidden state per row (before
        the head — O(B) vocab projections, not O(B*T)): the ragged
        prefill's per-row last-valid position."""
        b, t = ids.shape
        with jax.named_scope("embed"):
            x = jnp.take(self.tok_embed, ids, axis=0)
            if not self.use_rope:
                if chunked and jnp.ndim(pos0) == 1:
                    # ragged chunk: per-row positional rows, (B, T, C)
                    x = x + jnp.take(self.pos_embed,
                                     pos0[:, None] + jnp.arange(t)[None],
                                     axis=0)
                else:
                    pe = (jax.lax.dynamic_slice_in_dim(
                              self.pos_embed, pos0, t, 0)
                          if chunked else self.pos_embed[pos0:pos0 + t])
                    x = x + pe[None]
        new_caches = []
        for i in range(self.num_layers):
            blk = getattr(self, f"block{i}")
            x, c = (blk.forward_chunk(x, caches[i], pos0) if chunked
                    else blk.forward_prefill(x, caches[i], pos0))
            new_caches.append(c)
        logits = self._chunk_logits(x, all_logits, gather_last)
        if all_logits and gather_last is None:
            return logits, new_caches
        return logits[:, 0], new_caches

    @scoped("head")
    def _chunk_logits(self, x, all_logits: bool, gather_last):
        """Final norm and logits of a chunk's hidden states (B, T, C): at
        every position, at each row's ``gather_last``, or at the last."""
        if gather_last is not None:
            x = jnp.take_along_axis(
                x, gather_last[:, None, None].astype(jnp.int32), axis=1)
        elif not all_logits:
            x = x[:, -1:]
        x = self.ln_f(x)
        if self.tie_embeddings:
            return jnp.einsum("btc,vc->btv", x, self.tok_embed)
        return self.head(x.reshape(-1, x.shape[-1])).reshape(
            x.shape[0], x.shape[1], -1)

    @scoped("head")
    def _step_logits(self, x):
        """Final norm and logits of one token a row: (B, 1, C) ->
        (B, 1, V)."""
        x = self.ln_f(x)
        if self.tie_embeddings:
            return jnp.einsum("btc,vc->btv", x, self.tok_embed)
        return self.head(x.reshape(x.shape[0], -1))[:, None, :]

    def init_page_pool(self, max_pages: int, page_size: int,
                       dtype=jnp.float32, sharding=None, kv_dtype=None):
        """Per-block PAGE-POOL buffers for paged serving
        (bigdl_tpu/serving/paging.py): the ``init_cache`` tree forms
        in the pool's own layout, each leaf ``(max_pages, page_size,
        H_kv * D)`` — page and offset lead so the KV write lands in
        place (``MultiHeadAttention.init_page_pool``). One block table
        indexes EVERY layer — page ``p`` names slice ``p`` of each
        block's buffers — so a request's pages are one id list, not
        one per layer."""
        return [getattr(self, f"block{i}").attn.init_page_pool(
                    max_pages, page_size, dtype, sharding=sharding,
                    kv_dtype=kv_dtype)
                for i in range(self.num_layers)]

    def prefill_read_counts(self, pos0, chunk: int, page_size: int,
                            table_pages: int) -> dict:
        """What one attention layer of a prefill dispatch gathers of
        what its rows' tables hold, the rows' chunks of ``chunk`` tokens
        starting at ``pos0`` (host arithmetic for the engine's span and
        counters: ``MultiHeadAttention.chunk_read_counts``)."""
        return self.block0.attn.chunk_read_counts(pos0, chunk, page_size,
                                                  table_pages)

    def step_read_counts(self, pos, page_size: int, table_pages: int,
                         decode_attention: str = "rows") -> dict:
        """What one attention layer of a decode dispatch reads of what
        its rows' tables hold, the rows standing at ``pos`` (host
        arithmetic for the engine's span and counters:
        ``MultiHeadAttention.step_read_counts``)."""
        return self.block0.attn.step_read_counts(pos, page_size,
                                                 table_pages,
                                                 decode_attention)

    def prefill_chunk_at_paged(self, ids, pools, tables, pos0, last_idx):
        """Paged twin of :meth:`prefill_chunk_at`: each row's chunk
        scatters its KV into the pool pages its block-table row names
        and attends them by key blocks (``pos0`` is always the (B,)
        ragged form — the paged engine has no lockstep path). Same
        caller contract per row: every written position must fall
        inside the row's reserved pages."""
        return self._prefill_impl_paged(ids, pools, tables, pos0,
                                        gather_last=last_idx)

    def verify_chunk_paged(self, ids, pools, tables, pos0):
        """Paged twin of :meth:`verify_chunk` (ragged (B,) ``pos0``):
        logits at every chunk position, KV written through the block
        tables — the paged engine's speculative verifier."""
        return self._prefill_impl_paged(ids, pools, tables, pos0,
                                        all_logits=True)

    def _prefill_impl_paged(self, ids, pools, tables, pos0,
                            all_logits: bool = False, gather_last=None):
        b, t = ids.shape
        with jax.named_scope("embed"):
            x = jnp.take(self.tok_embed, ids, axis=0)
            if not self.use_rope:
                x = x + jnp.take(self.pos_embed,
                                 pos0[:, None] + jnp.arange(t)[None],
                                 axis=0)
        new_pools = []
        for i in range(self.num_layers):
            blk = getattr(self, f"block{i}")
            x, c = blk.forward_chunk_paged(x, pools[i], tables, pos0)
            new_pools.append(c)
        logits = self._chunk_logits(x, all_logits, gather_last)
        if all_logits and gather_last is None:
            return logits, new_pools
        return logits[:, 0], new_pools

    def decode_step_paged(self, ids_t, pos, pools, tables,
                          decode_attention="rows"):
        """Paged twin of :meth:`decode_step` (ragged (B,) ``pos``
        only): one token per row, KV scattered into and gathered from
        the page pool through ``tables`` inside the same dispatch —
        compiled shape depends on the pool geometry and the table
        length, never on any request's span. ``decode_attention`` is
        ``"kernel"`` on one TPU chip (nothing gathered: a kernel reads
        the pages in place), ``"rows"`` on any other single device and
        ``"heads"`` under a mesh that shards heads
        (``MultiHeadAttention.forward_step_paged``)."""
        with jax.named_scope("embed"):
            x = jnp.take(self.tok_embed, ids_t, axis=0)[:, None, :]  # (B,1,C)
            if not self.use_rope:
                x = x + jnp.take(self.pos_embed, pos, axis=0)[:, None]
        new_pools = []
        for i in range(self.num_layers):
            x, c = getattr(self, f"block{i}").forward_step_paged(
                x, pools[i], tables, pos,
                decode_attention=decode_attention)
            new_pools.append(c)
        return self._step_logits(x)[:, 0], new_pools

    def decode_step(self, ids_t, pos, caches):
        """One token in, next-token logits out. ids_t (B,) int, ``pos`` a
        traced scalar position — or a (B,) vector for RAGGED batches
        (each row at its own depth); caches from ``init_cache`` (static
        shapes — the whole step jits once and is reused for every
        position)."""
        with jax.named_scope("embed"):
            x = jnp.take(self.tok_embed, ids_t, axis=0)[:, None, :]  # (B,1,C)
            if not self.use_rope:
                if jnp.ndim(pos) == 1:
                    x = x + jnp.take(self.pos_embed, pos, axis=0)[:, None]
                else:
                    x = x + jax.lax.dynamic_slice_in_dim(self.pos_embed, pos,
                                                         1, 0)[None]
        new_caches = []
        for i in range(self.num_layers):
            x, c = getattr(self, f"block{i}").forward_step(x, caches[i], pos)
            new_caches.append(c)
        return self._step_logits(x)[:, 0], new_caches

    def decode_scan(self, logits, pos0, caches, rng, temperature, n: int,
                    sampled: bool = False, eos_id=None, top_k=None,
                    top_p=None):
        """Generate ``n`` tokens ON DEVICE as one ``lax.scan`` over the KV
        cache — one dispatch for the whole decode instead of n host
        round-trips (the reference re-dispatched its RecurrentDecoder
        host loop every timestep, nn/RecurrentDecoder.scala:48).
        ``n``/``sampled``/``eos_id``/``top_k``/``top_p`` must be
        trace-static; ``temperature`` may be traced. Returns (n, B) int32
        tokens. Callers jit this (see _decode_fns) with the caches
        donated — the scan's in-place cache updates then never copy.

        Token 0 samples straight from the prefill ``logits``; the scan
        then runs step->sample n-1 times — exactly n-1 decode steps for
        n tokens (no wasted trailing step), with one key split per
        sampled token in token order (bit-parity with the host loop).
        With ``eos_id``, finished rows keep emitting eos and the decode
        step is skipped entirely (``lax.cond``) once EVERY row has
        finished — the scan still runs n-1 iterations but the remaining
        ones cost a predicate, not a transformer forward."""
        b, v = logits.shape
        done = jnp.zeros((b,), bool)
        tok0, rng, done = _sample_next(logits, rng, done, sampled,
                                       temperature, eos_id, top_k, top_p)

        def body(carry, _):
            tok, pos, caches, rng, done = carry
            if eos_id is not None:
                logits, caches = jax.lax.cond(
                    jnp.all(done),
                    # all rows finished: skip the transformer forward;
                    # the sampled token is overwritten with eos anyway
                    lambda tok, pos, caches: (
                        jnp.zeros((b, v), self.tok_embed.dtype), caches),
                    lambda tok, pos, caches: self.decode_step(
                        tok, pos, caches),
                    tok, pos, caches)
            else:
                logits, caches = self.decode_step(tok, pos, caches)
            nxt, rng, done = _sample_next(logits, rng, done, sampled,
                                          temperature, eos_id, top_k, top_p)
            return (nxt, pos + 1, caches, rng, done), nxt

        carry = (tok0, jnp.asarray(pos0, jnp.int32), caches, rng, done)
        _, toks = jax.lax.scan(body, carry, None, length=n - 1)
        return jnp.concatenate([tok0[None], toks], axis=0)

    def _beam_scan_fn(self, b: int, k: int, n: int, eos_id):
        """Cached jitted ONE-DISPATCH beam search for this (model, batch,
        beams, length, eos). One compile (and one retained executable)
        per distinct key — length-varying beam callers should pick a
        fixed serving ``max_new_tokens`` or use ``host_loop=True``."""
        per_model = _BEAM_SCAN_JIT.setdefault(self, {})
        key = (b, k, n, eos_id)
        fn = per_model.get(key)
        if fn is not None:
            return fn
        fn = jax.jit(self._beam_scan_closure(b, k, n, eos_id),
                     donate_argnums=(4,))
        per_model[key] = fn
        return fn

    def _beam_scan_closure(self, b: int, k: int, n: int, eos_id):
        """The UNJITTED one-dispatch beam-search program (shared by
        _beam_scan_fn and the TPU-lowering export): the whole
        select->step loop is a ``lax.scan`` emitting (token, parent)
        pairs, and the winning sequences are materialized afterwards by
        a reverse scan over the parent pointers — O(n*k) backtracking
        instead of the host loop's re-gather of every prefix token each
        step (O(n^2*k))."""
        from bigdl_tpu.nn.module import bind

        def beam_scan(p, bufs, logits, pos0, caches, length_penalty):
            with bind(self, p, bufs, False, None):
                v = logits.shape[-1]
                logp = jax.nn.log_softmax(logits.astype(jnp.float32))
                scores, first = jax.lax.top_k(logp, k)            # (B, K)
                first = first.astype(jnp.int32)
                # beams share the prompt cache: tile to (B*K, ...)
                caches = jax.tree.map(lambda c: jnp.repeat(c, k, axis=0),
                                      caches)
                alive = jnp.ones((b, k), bool) if eos_id is None \
                    else first != eos_id
                lengths = jnp.ones((b, k), jnp.float32)
                frozen = None
                if eos_id is not None:  # finished beams emit eos, free
                    frozen = jnp.full((v,), -jnp.inf).at[eos_id].set(0.0)
                ident = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32),
                                         (b, k))

                def body(carry, _):
                    tok, gidx, scores, alive, lengths, caches, pos = carry
                    caches = _gather_beam_lineage(caches, gidx, b, k)
                    logits, caches = self.decode_step(
                        tok.reshape(b * k), pos, caches)
                    logp = jax.nn.log_softmax(
                        logits.astype(jnp.float32)).reshape(b, k, v)
                    if eos_id is not None:
                        logp = jnp.where(alive[..., None], logp, frozen)
                    cand = scores[..., None] + logp               # (B, K, V)
                    scores, flat = jax.lax.top_k(cand.reshape(b, k * v), k)
                    parent = (flat // v).astype(jnp.int32)
                    tok = (flat % v).astype(jnp.int32)
                    was_alive = jnp.take_along_axis(alive, parent, axis=1)
                    lengths = jnp.take_along_axis(lengths, parent, axis=1) \
                        + was_alive.astype(jnp.float32)
                    if eos_id is not None:
                        alive = was_alive & (tok != eos_id)
                    else:
                        alive = was_alive
                    return (tok, parent, scores, alive, lengths, caches,
                            pos + 1), (tok, parent)

                carry = (first, ident, scores, alive, lengths, caches,
                         jnp.asarray(pos0, jnp.int32))
                (_, _, scores, _, lengths, _, _), ys = jax.lax.scan(
                    body, carry, None, length=n - 1)

                # Backtrack: walk parent pointers from the final beams to
                # the first token (reverse scan aligns outputs with steps).
                def back(idx, y):
                    tok_row, parent_row = y
                    return (jnp.take_along_axis(parent_row, idx, axis=1),
                            jnp.take_along_axis(tok_row, idx, axis=1))

                idx, rev_toks = jax.lax.scan(back, ident, ys, reverse=True)
                first_tok = jnp.take_along_axis(first, idx, axis=1)
                gen = jnp.concatenate([first_tok[None], rev_toks], axis=0)
                norm = scores / lengths ** length_penalty
                best = jnp.argmax(norm, axis=1)                   # (B,)
                gen_best = jnp.take_along_axis(
                    gen, jnp.broadcast_to(best[None, :, None], (n, b, 1)),
                    axis=2)[..., 0]                               # (n, B)
                return gen_best.T

        return beam_scan

    def _beam_step_fn(self, b: int, k: int):
        """Cached jitted beam step for this (model, batch, beams): the
        surviving-beam cache gather is folded into the donated jit."""
        per_model = _BEAM_JIT.setdefault(self, {})
        fn = per_model.get((b, k))
        if fn is not None:
            return fn
        from bigdl_tpu.nn.module import bind

        def beam_step(p, bufs, tok, pos, caches, beam_idx):
            caches = _gather_beam_lineage(caches, beam_idx, b, k)
            with bind(self, p, bufs, False, None):
                return self.decode_step(tok, pos, caches)

        fn = jax.jit(beam_step, donate_argnums=(4,))
        per_model[(b, k)] = fn
        return fn

    def _decode_fns(self):
        """Per-model-instance jitted (step, prefill) pair, created ONCE and
        cached in a module-level weak map — jax.jit caches compilations per
        wrapper object, so rebuilding the closures every generate() call
        would recompile every call. Kept off the module itself so
        clone/pickle (save_module) never sees a jit wrapper. Buffers travel
        as an argument so the cache never staleness-traps them."""
        cached = _DECODE_JIT.get(self)
        if cached is not None:
            return cached
        from bigdl_tpu.nn.module import bind

        def step(p, bufs, ids_t, pos, caches):
            with bind(self, p, bufs, False, None):
                return self.decode_step(ids_t, pos, caches)

        def prefill_fn(p, bufs, ids, caches, pos0=0):
            with bind(self, p, bufs, False, None):
                return self.prefill(ids, caches, pos0)

        def chunk_fn(p, bufs, ids, caches, pos0):
            with bind(self, p, bufs, False, None):
                return self.prefill_chunk(ids, caches, pos0)

        def scan_fn(p, bufs, logits, pos0, caches, rng, temperature, n,
                    sampled, eos_id, top_k, top_p):
            # the one-dispatch n-token decode loop (see decode_scan);
            # n/sampled/eos/top-k/top-p static -> one compile per config.
            # pos0 may be () or a (B,) per-row vector (ragged batches) —
            # jax traces each shape once through the same wrapper
            with bind(self, p, bufs, False, None):
                return self.decode_scan(logits, pos0, caches, rng,
                                        temperature, n, sampled, eos_id,
                                        top_k, top_p)

        def ragged_prefill_fn(p, bufs, ids, lengths, caches):
            # RIGHT-padded mixed-length prompts: one causal pass (pads
            # sit at later positions than any valid query, so the causal
            # mask already excludes them); per-row last-valid hidden
            # state gathered BEFORE the head — O(B), not O(B*T), vocab
            # projections
            with bind(self, p, bufs, False, None):
                return self._prefill_impl(ids, caches, 0, chunked=False,
                                          gather_last=lengths - 1)

        fns = (jax.jit(step, donate_argnums=(4,)),
               jax.jit(prefill_fn, donate_argnums=(3,),
                       static_argnums=(4,)),
               jax.jit(chunk_fn, donate_argnums=(3,)),
               jax.jit(scan_fn, donate_argnums=(2, 4),
                       static_argnums=(7, 8, 9, 10, 11)),
               jax.jit(ragged_prefill_fn, donate_argnums=(4,)))
        _DECODE_JIT[self] = fns
        return fns

    def _decode_setup(self, prompt_ids, max_new_tokens, max_len,
                      prefill_chunk=None, kv_cache_sharding=None):
        """Shared decoding preamble for generate/beam_search: coerce +
        validate the prompt, fetch the cached jitted fns, run the batched
        prefill. Returns (prompt_ids, b, t0, params, buffers, step_jit,
        last_logits, caches); logits/caches are None when no new tokens
        are requested (prefill skipped).

        ``prefill_chunk`` bounds the prefill's score memory: the prompt
        feeds in fixed-length chunks through the traced-offset chunk fn
        (one compile per chunk length; a leading remainder chunk goes
        through the one-shot prefill — at most two compilations)."""
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        if prompt_ids.ndim == 1:
            prompt_ids = prompt_ids[None]
        b, t0 = prompt_ids.shape
        total = t0 + max_new_tokens
        max_len = max_len or total
        if total > max_len:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len {max_len}: the cache and positional "
                "lookups would silently clamp")
        if max_len > self.max_len:
            # non-rope: the positional table has max_len rows; rope: the
            # model was built (and trained) for this context bound
            raise ValueError(f"max_len {max_len} exceeds the model's "
                             f"context length {self.max_len}")
        params, buffers = self.params_dict(), self.buffers_dict()
        step_jit, prefill_jit, chunk_jit = self._decode_fns()[:3]
        if max_new_tokens == 0:
            return prompt_ids, b, t0, params, buffers, step_jit, None, None
        # cache dtype follows the params (bf16 serving -> bf16 kv cache);
        # a kv_cache_sharding allocates the (B, H_kv, T, D) buffers
        # DIRECTLY with that layout (long-context serving: a context
        # larger than one chip's HBM must never materialize on one
        # device, and the allocation is compile-free — jnp.zeros with a
        # device=, not a traced program); GSPMD partitions every
        # downstream attention contraction + softmax reduction from the
        # sharding alone
        caches = self.init_cache(b, max_len, dtype=self.tok_embed.dtype,
                                 sharding=kv_cache_sharding)
        if prefill_chunk and t0 > prefill_chunk:
            rem = t0 % prefill_chunk
            pos = 0
            if rem:  # leading remainder: one-shot prefill at offset 0
                logits, caches = prefill_jit(params, buffers,
                                             prompt_ids[:, :rem], caches)
                pos = rem
            while pos < t0:
                logits, caches = chunk_jit(
                    params, buffers,
                    prompt_ids[:, pos:pos + prefill_chunk],
                    caches, jnp.int32(pos))
                pos += prefill_chunk
        else:
            logits, caches = prefill_jit(params, buffers, prompt_ids, caches)
        return prompt_ids, b, t0, params, buffers, step_jit, logits, caches

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, rng=None, max_len=None,
                 prefill_chunk=None, host_loop: bool = False,
                 bucket_tokens=None, eos_id=None, top_k=None,
                 top_p=None, kv_cache_sharding=None, on_token=None):
        """Autoregressive generation with a KV cache (the transformer
        analog of the reference's RecurrentDecoder, nn/RecurrentDecoder
        .scala): batched prefill over the prompt, then the ENTIRE
        sample->step decode loop runs on device as one ``lax.scan``
        dispatch — throughput is set by the chip, not by
        ``max_new_tokens`` host round-trips. Sampling is greedy
        (``temperature == 0``) or from the tempered softmax, optionally
        filtered by ``top_k`` and/or nucleus ``top_p`` (HF-style order).
        With ``eos_id``, rows that emit eos keep emitting eos, and the
        decode skips the transformer forward once every row finished
        (the host loop breaks out entirely). Returns
        (B, len(prompt) + max_new_tokens) ids. ``prefill_chunk`` bounds
        long-prompt prefill memory (see _decode_setup). ``host_loop=True``
        forces the one-dispatch-per-token path (the scan parity oracle;
        also what a caller streaming tokens as they land would use;
        ``on_token(step_tokens)`` fires per generated (B,) step there —
        asking for streaming implies the host loop, so passing
        ``on_token`` without ``host_loop=True`` raises).

        The scan compiles once per decode length; serving callers with
        per-request lengths should set ``bucket_tokens=B`` to round the
        compiled length up to a multiple of B (one program per bucket,
        not per length). The first ``max_new_tokens`` tokens are
        IDENTICAL either way — token i depends only on steps < i and the
        key schedule splits in token order — the tail is computed and
        discarded.

        ``kv_cache_sharding``: a NamedSharding for the (B, H_kv, T, D)
        caches — shard T over the mesh to decode with a context larger
        than one chip's HBM (GSPMD partitions the attention and its
        softmax reductions; tokens match the unsharded run, tested)."""
        from bigdl_tpu.utils import random as bt_random

        sampled = temperature > 0.0
        _validate_sampling(sampled, top_k, top_p)
        if on_token is not None and not host_loop:
            raise ValueError("on_token streams per-step tokens, which "
                             "only the host loop materializes; pass "
                             "host_loop=True")
        (prompt_ids, b, t0, params, buffers, step_jit,
         logits, caches) = self._decode_setup(prompt_ids, max_new_tokens,
                                              max_len, prefill_chunk,
                                              kv_cache_sharding)
        if max_new_tokens == 0:
            return prompt_ids
        if sampled and rng is None:
            rng = bt_random.next_key()
        if not host_loop:
            n = max_new_tokens
            if bucket_tokens:
                n = -(-n // bucket_tokens) * bucket_tokens
            scan_jit = self._decode_fns()[3]
            toks = scan_jit(params, buffers, logits, jnp.int32(t0), caches,
                            rng if sampled else jax.random.PRNGKey(0),
                            jnp.float32(temperature if sampled else 1.0),
                            n, sampled, eos_id, top_k, top_p)
            return jnp.concatenate([prompt_ids,
                                    toks[:max_new_tokens].T], axis=1)
        ids = [prompt_ids[:, i] for i in range(t0)]
        done = jnp.zeros((b,), bool)
        for i in range(max_new_tokens):
            nxt, rng, done = _sample_next(
                logits, rng, done, sampled,
                temperature if sampled else 1.0, eos_id, top_k, top_p)
            ids.append(nxt)
            if on_token is not None:
                on_token(nxt)  # streaming: the (B,) tokens of step i
            if eos_id is not None and bool(jnp.all(done)):
                # every row finished: pad the rest with eos (what the
                # scan path's done-masking emits) and stop dispatching
                pad = jnp.full((b,), eos_id, jnp.int32)
                ids.extend([pad] * (max_new_tokens - 1 - i))
                break
            if i < max_new_tokens - 1:
                logits, caches = step_jit(params, buffers, nxt,
                                          jnp.int32(t0 + i), caches)
        return jnp.stack(ids, axis=1)

    def _propose_fn(self, b: int, gamma: int, sampled: bool = False,
                    cache_sharding=None, repl_sharding=None):
        """Cached jitted draft proposer: gamma step->choose iterations as
        ONE lax.scan dispatch (argmax when greedy, tempered categorical
        when ``sampled``), writing the input tokens' KV as it goes.
        Returns ((gamma, B) proposals, (gamma, B, V) step logits — the
        sampled verifier's q distributions, ignored by the greedy
        caller — and the caches). ``pos0`` may be scalar or a (B,)
        per-row position vector (``decode_step`` is ragged-aware and
        the scan carry just holds the vector) — the serving engine
        proposes for every live slot at its own depth through this
        same program. One factory for both modes so the proposal scan
        can never diverge between them. ``cache_sharding`` (with
        ``repl_sharding`` for the token/logit outputs) PINS the
        output layouts for SPMD callers — the sharded serving engine's
        draft caches then cycle through the scan in one stable layout
        instead of whatever GSPMD would pick per compile."""
        per_model = _SPEC_JIT.setdefault(self, {})
        key = ("propose", b, gamma, sampled, cache_sharding)
        fn = per_model.get(key)
        if fn is not None:
            return fn
        from bigdl_tpu.nn.module import bind

        def propose(p, bufs, tok, pos0, caches, rng, temperature):
            with bind(self, p, bufs, False, None):
                def body(carry, _):
                    tok, pos, caches, rng = carry
                    logits, caches = self.decode_step(tok, pos, caches)
                    if sampled:
                        rng, sub = jax.random.split(rng)
                        nxt = jax.random.categorical(
                            sub, logits.astype(jnp.float32) / temperature,
                            axis=-1).astype(jnp.int32)
                    else:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, pos + 1, caches, rng), (nxt, logits)

                carry = (tok, jnp.asarray(pos0, jnp.int32), caches, rng)
                (_, _, caches, _), (toks, qlogits) = jax.lax.scan(
                    body, carry, None, length=gamma)
                return toks, qlogits, caches

        kw = {}
        if cache_sharding is not None:
            kw["out_shardings"] = (repl_sharding, repl_sharding,
                                   cache_sharding)
        fn = jax.jit(propose, donate_argnums=(4,), **kw)
        per_model[key] = fn
        return fn

    def _propose_fn_paged(self, b: int, gamma: int, table_len: int,
                          sampled: bool = False, cache_sharding=None,
                          repl_sharding=None, decode_attention="rows"):
        """Paged twin of :meth:`_propose_fn`: the gamma-step proposal
        scan over ``decode_step_paged`` — the draft's page pool cycles
        through the scan carry while the block tables ride as a loop
        constant (a request's pages are fixed for its whole flight, so
        the tables never change inside one proposal). Signature gains
        ``tables`` after the pool; donation moves with the pool.
        ``decode_attention`` is ``decode_step_paged``'s: the engine
        hands every paged program it builds the same one."""
        per_model = _SPEC_JIT.setdefault(self, {})
        key = ("propose_paged", b, gamma, table_len, sampled,
               cache_sharding, decode_attention)
        fn = per_model.get(key)
        if fn is not None:
            return fn
        from bigdl_tpu.nn.module import bind

        def propose(p, bufs, tok, pos0, pools, tables, rng, temperature):
            with bind(self, p, bufs, False, None):
                def body(carry, _):
                    tok, pos, pools, rng = carry
                    logits, pools = self.decode_step_paged(
                        tok, pos, pools, tables,
                        decode_attention=decode_attention)
                    if sampled:
                        rng, sub = jax.random.split(rng)
                        nxt = jax.random.categorical(
                            sub, logits.astype(jnp.float32) / temperature,
                            axis=-1).astype(jnp.int32)
                    else:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, pos + 1, pools, rng), (nxt, logits)

                carry = (tok, jnp.asarray(pos0, jnp.int32), pools, rng)
                (_, _, pools, _), (toks, qlogits) = jax.lax.scan(
                    body, carry, None, length=gamma)
                return toks, qlogits, pools

        kw = {}
        if cache_sharding is not None:
            kw["out_shardings"] = (repl_sharding, repl_sharding,
                                   cache_sharding)
        fn = jax.jit(propose, donate_argnums=(4,), **kw)
        per_model[key] = fn
        return fn

    def _verify_fn(self, b: int, chunk_len: int):
        """Cached jitted speculative verifier for this (model, batch,
        chunk): one chunked forward scoring every proposed position.
        ``pos0`` may be scalar (the lockstep ``speculative_generate``
        path) or a (B,) per-row vector (ragged slot-pooled serving) —
        each shape traces once through the same wrapper."""
        per_model = _SPEC_JIT.setdefault(self, {})
        fn = per_model.get((b, chunk_len))
        if fn is not None:
            return fn
        from bigdl_tpu.nn.module import bind

        def verify(p, bufs, chunk, caches, pos0):
            with bind(self, p, bufs, False, None):
                return self.verify_chunk(chunk, caches, pos0)

        fn = jax.jit(verify, donate_argnums=(3,))
        per_model[(b, chunk_len)] = fn
        return fn

    def speculative_generate(self, prompt_ids, max_new_tokens: int,
                             draft, gamma: int = 4, max_len=None,
                             return_stats: bool = False,
                             temperature: float = 0.0, rng=None):
        """Speculative decoding: ``draft`` (a smaller, cheaper
        TransformerLM over the same vocabulary — an int8-quantized clone
        works) proposes ``gamma`` tokens per round with its own KV cache;
        this model then scores ALL of them in ONE chunked verify forward
        (``verify_chunk``, traced offset).

        ``temperature == 0`` (default): greedy — accept the longest
        prefix matching this model's argmax, take its own token at the
        first mismatch. Output is EXACTLY greedy ``generate()``.

        ``temperature > 0``: full speculative SAMPLING (Leviathan et al.
        2023) — the draft samples its proposals, each is accepted with
        probability min(1, p/q), and the first rejected position draws
        from the normalized residual max(p - q, 0); on full acceptance a
        bonus token samples from p. The output is distributed EXACTLY as
        tempered sampling from this model (the accept/residual identity
        is pinned empirically in tests).

        Either way the draft only changes how many target forwards it
        takes: per round, 1 target chunk forward yields accepted+1
        tokens instead of 1. Acceptance is conservative across the batch
        (min over rows) — rows that would have accepted more simply lose
        the extra proposals (wasted work, never wrong). Returns
        (B, t0 + n) ids, or ``(ids, {"rounds", "accept_rate"})`` with
        ``return_stats=True``.

        Reference analog: none (the reference has no speculative
        path)."""
        from bigdl_tpu.utils import random as bt_random

        sampled = temperature > 0.0
        if sampled and rng is None:
            rng = bt_random.next_key()
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        if prompt_ids.ndim == 1:
            prompt_ids = prompt_ids[None]
        b, t0 = prompt_ids.shape
        n = max_new_tokens
        if n == 0:
            return (prompt_ids, {"rounds": 0, "accept_rate": 0.0}) \
                if return_stats else prompt_ids
        ctx = min(self.max_len, draft.max_len)
        if max_len is not None:
            ctx = min(ctx, max_len)
        # highest position any round writes: a round starts with pos <=
        # t0+n-2 (the loop runs only while len(out) < n), and both the
        # verify chunk and the full-acceptance fill-in write up to
        # pos+gamma — so gamma <= ctx-t0-n+1 keeps every write in bounds
        gamma = min(gamma, ctx - t0 - n + 1)
        if t0 + n > ctx or gamma < 1:
            ids = self.generate(prompt_ids, n, max_len=max_len,
                                temperature=temperature, rng=rng)
            return (ids, {"rounds": n, "accept_rate": 0.0}) \
                if return_stats else ids

        t_params, t_bufs = self.params_dict(), self.buffers_dict()
        d_params, d_bufs = draft.params_dict(), draft.buffers_dict()
        t_prefill = self._decode_fns()[1]
        d_prefill = draft._decode_fns()[1]
        d_step = draft._decode_fns()[0]
        d_propose = draft._propose_fn(b, gamma, sampled=sampled)
        verify = self._verify_fn(b, gamma + 1)

        t_caches = self.init_cache(b, ctx, dtype=self.tok_embed.dtype)
        d_caches = draft.init_cache(b, ctx, dtype=draft.tok_embed.dtype)
        t_logits, t_caches = t_prefill(t_params, t_bufs, prompt_ids,
                                       t_caches)
        _, d_caches = d_prefill(d_params, d_bufs, prompt_ids, d_caches)

        if sampled:  # token @ t0 samples from the target prefill logits
            rng, sub = jax.random.split(rng)
            next_tok = jax.random.categorical(
                sub, t_logits.astype(jnp.float32) / temperature,
                axis=-1).astype(jnp.int32)
        else:
            next_tok = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
        out = [next_tok]
        pos = t0            # next_tok's position; its KV is not yet cached
        rounds = accepted = 0
        while len(out) < n:
            # draft proposes gamma tokens in ONE dispatch (lax.scan),
            # writing KV for positions pos .. pos+gamma-1 (its inputs)
            if sampled:
                rng, r_draft, r_acc = jax.random.split(rng, 3)
            else:
                r_draft = jax.random.PRNGKey(0)  # greedy: rng unused
            toks, qlogits, d_caches = d_propose(
                d_params, d_bufs, next_tok, jnp.int32(pos), d_caches,
                r_draft, jnp.float32(temperature if sampled else 1.0))
            props = toks.T                                     # (B, g)
            # one target forward scores positions pos .. pos+gamma:
            # chunk token j sits at position pos+j; logits row j predicts
            # the token AT position pos+j+1
            chunk = jnp.concatenate([next_tok[:, None], props], axis=1)
            v_logits, t_caches = verify(t_params, t_bufs, chunk, t_caches,
                                        jnp.int32(pos))
            if sampled:
                accept, resid, bonus = _spec_accept(
                    v_logits, jnp.swapaxes(qlogits, 0, 1), props,
                    jnp.float32(temperature), r_acc)
                acc = accept.astype(jnp.int32)
                a = int(jnp.min(jnp.sum(jnp.cumprod(acc, axis=1),
                                        axis=1)))
                out.extend(props[:, j] for j in range(a))
                if a == gamma:
                    out.append(bonus)       # fresh sample from p @ pos+g+1
                    next_tok = bonus
                else:
                    # rows still accepting at column a keep their
                    # proposal; rows rejecting draw from the residual —
                    # together distributed exactly as p (Thm 1)
                    tok_a = jnp.where(accept[:, a], props[:, a],
                                      resid[:, a])
                    out.append(tok_a)
                    next_tok = tok_a
            else:
                v_tok = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
                # longest prefix where the draft matched the target's
                # greedy choice, conservative across rows (min)
                match = (props == v_tok[:, :gamma]).astype(jnp.int32)
                a = int(jnp.min(jnp.sum(jnp.cumprod(match, axis=1),
                                        axis=1)))
                out.extend(props[:, j] for j in range(a))
                out.append(v_tok[:, a])  # target's token at pos+a+1
                next_tok = v_tok[:, a]
            if a == gamma:
                # full acceptance: proposals[-1] (position pos+gamma) was
                # never fed through the draft — write its KV so the next
                # round's draft attention sees a complete cache
                _, d_caches = d_step(d_params, d_bufs, props[:, -1],
                                     jnp.int32(pos + gamma), d_caches)
            pos += a + 1
            rounds += 1
            accepted += a
        ids = jnp.concatenate(
            [prompt_ids, jnp.stack(out[:n], axis=1)], axis=1)
        if return_stats:
            return ids, {"rounds": rounds,
                         "accept_rate": accepted / max(rounds * gamma, 1)}
        return ids

    def generate_ragged(self, prompt_ids, prompt_lengths,
                        max_new_tokens: int, temperature: float = 0.0,
                        rng=None, eos_id=None, top_k=None, top_p=None,
                        bucket_tokens=None, max_len=None):
        """MIXED prompt lengths in ONE batch: ``prompt_ids`` (B, Tmax)
        RIGHT-padded, ``prompt_lengths`` (B,) valid lengths. Returns
        (B, max_new_tokens) generated tokens — row i continues its own
        length-``t0_i`` prompt exactly as ``generate`` would on that row
        alone (tested).

        Why right padding works with no attention-mask machinery: valid
        tokens keep their absolute positions (RoPE rotations and the
        causal structure are row-independent), pads sit at LATER
        positions than every valid query so the causal prefill never
        attends them, each row's first decode step OVERWRITES its first
        pad's KV slot, and decode masks/rotations take a (B,) per-row
        position vector (the same one-dispatch scan — the carry just
        holds a vector). Sampling/eos options match ``generate``."""
        from bigdl_tpu.utils import random as bt_random

        sampled = temperature > 0.0
        _validate_sampling(sampled, top_k, top_p)
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        if prompt_ids.ndim != 2 or lengths.shape != prompt_ids.shape[:1]:
            raise ValueError(
                f"generate_ragged takes (B, Tmax) padded prompts + (B,) "
                f"lengths, got {prompt_ids.shape} / {lengths.shape}")
        b, tmax = prompt_ids.shape
        n = max_new_tokens
        if n < 1:
            raise ValueError("max_new_tokens must be >= 1")
        lmax = int(jnp.max(lengths))
        lmin = int(jnp.min(lengths))
        if lmin < 1 or lmax > tmax:
            raise ValueError(f"prompt_lengths must be in [1, {tmax}], "
                             f"got [{lmin}, {lmax}]")
        window = min(self.max_len, max_len) if max_len else self.max_len
        if lmax + n > window or tmax > window:
            raise ValueError(
                f"longest prompt ({lmax}) + max_new_tokens ({n}) or the "
                f"padded width ({tmax}) exceeds the context "
                f"length {window}")
        if sampled and rng is None:
            rng = bt_random.next_key()
        params, buffers = self.params_dict(), self.buffers_dict()
        fns = self._decode_fns()
        scan_jit, ragged_prefill = fns[3], fns[4]
        # cache covers the prefill's full padded width AND every row's
        # decode span; bucketed scan tails clamp-write harmlessly past
        # each row's own end (same argument as generate(bucket_tokens=)).
        # An explicit max_len PINS the cache shape (serving: the compiled
        # program then depends only on the padded width + max_len, not on
        # this batch's particular n).
        caches = self.init_cache(b, window if max_len
                                 else min(window, tmax + n),
                                 dtype=self.tok_embed.dtype)
        logits, caches = ragged_prefill(params, buffers, prompt_ids,
                                        lengths, caches)
        n_c = n
        if bucket_tokens:
            n_c = -(-n // bucket_tokens) * bucket_tokens
        toks = scan_jit(params, buffers, logits, lengths, caches,
                        rng if sampled else jax.random.PRNGKey(0),
                        jnp.float32(temperature if sampled else 1.0),
                        n_c, sampled, eos_id, top_k, top_p)
        return toks[:n].T

    def beam_search(self, prompt_ids, max_new_tokens: int,
                    num_beams: int = 4, length_penalty: float = 1.0,
                    eos_id: Optional[int] = None, max_len=None,
                    host_loop: bool = False):
        """Deterministic beam search over the KV-cache decoder. Returns
        (B, t0 + max_new_tokens) ids of the best beam per batch row
        (finished beams — after ``eos_id`` — are frozen and padded with
        eos). Ranking: summed token log-probs / L**length_penalty where L
        is each beam's OWN generated length. The step that emits eos IS
        scored (its log-prob joins the sum and it counts toward L, the
        standard HF-style ranking); only the padding after it is
        excluded. The whole select->step loop runs on device as one
        ``lax.scan`` dispatch with parent-pointer backtracking
        (``host_loop=True`` keeps the per-step path, its parity
        oracle)."""
        (prompt_ids, b, t0, params, buffers, step_jit,
         logits, caches) = self._decode_setup(prompt_ids, max_new_tokens,
                                              max_len)
        if max_new_tokens == 0:
            return prompt_ids
        k = num_beams
        if not host_loop:
            gen = self._beam_scan_fn(b, k, max_new_tokens, eos_id)(
                params, buffers, logits, jnp.int32(t0), caches,
                jnp.float32(length_penalty))
            return jnp.concatenate([prompt_ids, gen], axis=1)
        beam_step_jit = self._beam_step_fn(b, k)

        v = logits.shape[-1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))     # (B, V)
        scores, first = jax.lax.top_k(logp, k)                    # (B, K)
        # beams share the prompt cache: tile to (B*K, ...)
        caches = jax.tree.map(lambda c: jnp.repeat(c, k, axis=0), caches)
        beams = [jnp.repeat(prompt_ids[:, i], k).reshape(b, k)
                 for i in range(t0)] + [first.astype(jnp.int32)]
        alive = jnp.ones((b, k), bool) if eos_id is None else \
            first != eos_id
        lengths = jnp.ones((b, k), jnp.float32)  # scored tokens per beam
        frozen = None
        if eos_id is not None:  # finished beams may only emit eos, free
            frozen = jnp.full((v,), -jnp.inf).at[eos_id].set(0.0)

        for i in range(1, max_new_tokens):
            beam_idx = jnp.broadcast_to(jnp.arange(k), (b, k)) if i == 1 \
                else beam_idx  # first step: beams still in tile order
            logits, caches = beam_step_jit(
                params, buffers, beams[-1].reshape(b * k),
                jnp.int32(t0 + i - 1), caches, beam_idx)
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32)).reshape(b, k, v)
            if eos_id is not None:
                logp = jnp.where(alive[..., None], logp, frozen)
            cand = scores[..., None] + logp                       # (B, K, V)
            scores, flat = jax.lax.top_k(cand.reshape(b, k * v), k)
            beam_idx, tok = flat // v, (flat % v).astype(jnp.int32)
            was_alive = jnp.take_along_axis(alive, beam_idx, axis=1)
            lengths = jnp.take_along_axis(lengths, beam_idx, axis=1) \
                + was_alive.astype(jnp.float32)
            beams = [jnp.take_along_axis(t_, beam_idx, axis=1)
                     for t_ in beams] + [tok]
            if eos_id is not None:
                alive = was_alive & (tok != eos_id)

        final = jnp.stack(beams, axis=2)                          # (B, K, T)
        norm = scores / lengths ** length_penalty
        best = jnp.argmax(norm, axis=1)
        return jnp.take_along_axis(
            final, best[:, None, None], axis=1)[:, 0]
