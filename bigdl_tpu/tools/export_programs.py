"""Cross-lowering builders: the production programs packaged for
``jax.export(platforms=["tpu"])``.

Nothing here needs TPU hardware. ``jax.export`` runs the FULL TPU
lowering pipeline from any host — including Mosaic for the pallas flash
kernel, whose compiled payload lands in the module as a
``tpu_custom_call`` — so Mosaic/layout/lowering breakage is caught
offline instead of costing chip time. Consumers: ``tests/test_tpu_lowering.py``
(fast shapes, every suite run) and ``scripts/tpu_export.py`` (flagship
shapes, records artifact hashes in ``TPU_LOWERING.json``).

Each builder returns ``(fn, args)`` where ``fn`` is the jitted program
and ``args`` are ``ShapeDtypeStruct``s carrying the production
shardings, ready for ``jax.export.export(fn, platforms=["tpu"])(*args)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _abstract(tree):
    """Concrete pytree -> ShapeDtypeStructs preserving shardings."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        tree)


def flash_attention_program(b: int = 2, h: int = 8, h_kv: int = 4,
                            t: int = 1024, d: int = 64,
                            dtype=jnp.bfloat16, grad: bool = True):
    """The pallas flash kernel at its shipped auto_block default (256
    when the sequence tiles into it, else 128 — tuned on hardware, see
    flash_matrix.jsonl) with the GQA BlockSpec index map, fwd (+bwd when
    ``grad``), single chip.
    This is the program whose Mosaic lowering has never run on hardware —
    the VERDICT r4 bar (``ops/flash_attention.py`` must survive real
    Mosaic lowering, not just interpret mode)."""
    from bigdl_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    if grad:
        def loss(q, k, v):
            return jnp.mean(fwd(q, k, v).astype(jnp.float32))

        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    else:
        fn = jax.jit(fwd)
    q = jax.ShapeDtypeStruct((b, h, t, d), dtype)
    kv = jax.ShapeDtypeStruct((b, h_kv, t, d), dtype)
    return fn, (q, kv, kv)


def paged_decode_step_program(lanes: int = 8, vocab: int = 50304,
                              embed_dim: int = 1280, heads: int = 20,
                              layers: int = 2, max_len: int = 1024,
                              page_size: int = 16,
                              decode_attention: str = "kernel",
                              dtype=jnp.bfloat16):
    """The paged engine's decode step as it is built for ONE TPU chip
    (``decode_attention="kernel"``: the write scatters into the donated
    pool in place, then the pallas paged-attention kernel reads each
    lane's pages from the pool's leaves where they lie,
    ``ops/paged_attention.py``), at GPT-2 Large's widths cut to two
    layers: every lane with its full table over a pool of
    ``1 + lanes * table_len`` pages. ``"rows"`` and ``"heads"`` give the
    gathered forms the engine builds off a TPU and on a mesh."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.nn.module import abstract_init, bind

    model = abstract_init(lambda: TransformerLM(
        vocab, embed_dim=embed_dim, num_heads=heads, num_layers=layers,
        max_len=max_len))
    model.evaluate()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype), model.params_dict())
    table_len = max_len // page_size
    pool = jax.eval_shape(lambda: model.init_page_pool(
        1 + lanes * table_len, page_size, dtype=dtype))

    def step(p, tok, pos, pool, tables):
        with bind(model, p, {}, False, None):
            logits, pool = model.decode_step_paged(
                tok, pos, pool, tables, decode_attention=decode_attention)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return (jax.jit(step, donate_argnums=(3,)),
            (params, i32(lanes), i32(lanes), pool, i32(lanes, table_len)))


def hybrid_decode_step_program(lanes: int = 16, embed_dim: int = 3840,
                               heads: int = 30, ctx: int = 4096,
                               page_size: int = 16,
                               decode_attention: str = "kernel",
                               dtype=jnp.bfloat16):
    """One period of the hybrid decoder (three gated delta-rule layers and
    a full-attention layer at Olmo-Hybrid's widths) as :func:`
    paged_decode_step_program` builds GPT-2 Large's step: the decode step
    over ``lanes`` lanes of ``ctx`` positions, the pool (pages and lane
    state) donated."""
    from bigdl_tpu.models.hybrid import HybridDecoderLM
    from bigdl_tpu.nn.module import abstract_init, bind

    model = abstract_init(lambda: HybridDecoderLM(
        100352, embed_dim, heads, ("linear_attention",) * 3
        + ("full_attention",), 11008, ctx, num_kv_heads=heads,
        linear_heads=heads, linear_key_dim=96, linear_value_dim=192))
    model.evaluate()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype), model.params_dict())
    table_len = ctx // page_size
    pool = jax.eval_shape(lambda: model.init_page_pool(
        1 + lanes * table_len, page_size, dtype=dtype, lanes=lanes + 1))

    def step(p, tok, pos, pool, tables, active):
        with bind(model, p, {}, False, None):
            logits, pool = model.decode_step_paged(
                tok, pos, pool, tables, active=active,
                decode_attention=decode_attention)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return (jax.jit(step, donate_argnums=(3,)),
            (params, i32(lanes), i32(lanes), pool, i32(lanes, table_len),
             jax.ShapeDtypeStruct((lanes,), bool)))


def ring_flash_program(n_devices: int = 8, t_per_shard: int = 256,
                       dtype=jnp.bfloat16):
    """Ring attention composed with the flash kernel (trainable custom
    vjp), sharded over a ('data', 'seq') mesh — K/V blocks rotate over
    the 'seq' axis via ppermute, each ring step runs the Mosaic kernel."""
    from bigdl_tpu.parallel import Engine
    from bigdl_tpu.parallel.ring_attention import ring_attention

    dp = 2 if n_devices % 2 == 0 else 1
    sp = n_devices // dp
    mesh = Engine.create_mesh([("data", dp), ("seq", sp)])
    b, h, h_kv, d = 2 * dp, 8, 4, 64
    t = t_per_shard * sp

    def body(q, k, v):
        def loss_fn(q, k, v):
            o = ring_attention(q, k, v, axis_name="seq", causal=True,
                               use_flash=True, interpret=False)
            return jnp.mean(o.astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(q, k, v)
        return lax.pmean(loss, ("data", "seq")), grads

    spec = P("data", None, "seq", None)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(P(), (spec, spec, spec)), check_vma=False))
    sh = NamedSharding(mesh, spec)
    q = jax.ShapeDtypeStruct((b, h, t, d), dtype, sharding=sh)
    kv = jax.ShapeDtypeStruct((b, h_kv, t, d), dtype, sharding=sh)
    return fn, (q, kv, kv)


def distri_sharded_step_program(model_name: str = "lenet5",
                                n_devices: int = 8,
                                global_batch: int = 32,
                                format: str = "NCHW",
                                mesh=None):
    """The PRODUCTION DistriOptimizer ZeRO-1 sharded train step — the
    exact program ``_build_sharded_step`` jits (reduce-scatter bf16 wire,
    per-shard update, all-gather, donation), with abstract args laid out
    exactly as ``_optimize_impl`` lays them out."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.perf import build_model
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import DistriOptimizer, Engine
    from bigdl_tpu.parallel.all_reduce import flatten_params, pad_to_multiple
    from bigdl_tpu.utils import random as bt_random

    mesh = mesh or Engine.create_mesh([("data", n_devices)])
    n_data = mesh.shape["data"]
    model, input_shape, class_num = build_model(model_name, format=format)
    criterion = (nn.CrossEntropyCriterion() if model_name.startswith("resnet")
                 else nn.ClassNLLCriterion())
    dummy = [Sample(np.zeros(input_shape, np.float32),
                    np.array([1.0], np.float32))]
    opt = DistriOptimizer(model=model, dataset=DataSet.array(dummy),
                          criterion=criterion, batch_size=global_batch,
                          end_when=Trigger.max_iteration(1), mesh=mesh,
                          parameter_sync="sharded")
    method = SGD(learning_rate=0.01)
    opt.set_optim_method(method)

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P("data"))
    params = jax.device_put(model.params_dict(), repl)
    buffers = jax.device_put(
        jax.tree.map(lambda bf: jnp.broadcast_to(bf[None],
                                                 (n_data,) + bf.shape),
                     model.buffers_dict()),
        data_sh)
    flat, _ = flatten_params(params)
    flat, _ = pad_to_multiple(flat, n_data)
    flat = jax.device_put(flat, data_sh)
    slots = method.init_slots(flat)
    step, _, _ = opt._build_sharded_step(model, criterion, method, None,
                                         slots)
    x = jax.ShapeDtypeStruct((global_batch,) + tuple(input_shape),
                             jnp.float32, sharding=data_sh)
    y = jax.ShapeDtypeStruct((global_batch, 1), jnp.float32,
                             sharding=data_sh)
    lrs = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
    rng = _abstract(jax.device_put(bt_random.next_key(), repl))
    return step, (_abstract(params), _abstract(buffers), _abstract(flat),
                  _abstract(slots), x, y, lrs, rng)


def combined_3d_program(n_devices: int = 8, t_per_shard: int = 8,
                        embed_dim: int = 16, vocab: int = 32,
                        use_flash: bool = False,
                        abstract_args: bool = False):
    """The combined dp x sp x ep train step from the driver dryrun
    (``__graft_entry__._dryrun_combined_3d``): RoPE + GQA + ring
    attention over 'seq' + MoE all_to_all over 'expert' in one shard_map,
    per-axis-correct gradient reductions.

    ``use_flash=True`` + a 128-tileable ``t_per_shard`` makes the ring
    run the pallas kernel, so the exported module carries the Mosaic
    kernel inside the full composed program. ``abstract_args`` returns
    ShapeDtypeStructs (export) instead of concrete arrays (dryrun)."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.nn.module import pure_apply
    from bigdl_tpu.parallel import Engine

    ep = 2 if n_devices % 2 == 0 else 1
    rest = n_devices // ep
    dp = 2 if rest % 2 == 0 and rest > 1 else 1
    sp = rest // dp
    mesh = Engine.create_mesh([("data", dp), ("seq", sp), ("expert", ep)])
    seq_len = t_per_shard * sp
    model = TransformerLM(vocab_size=vocab, embed_dim=embed_dim,
                          num_heads=4, num_kv_heads=2, use_rope=True,
                          num_layers=1, max_len=seq_len, causal=True,
                          sequence_parallel="seq", use_flash=use_flash,
                          n_experts=2 * ep, expert_parallel="expert")
    apply_fn = pure_apply(model)
    params, buffers = model.params_dict(), model.buffers_dict()

    EXPERT_LEAVES = {"w1", "b1", "w2", "b2"}

    def spec_of(path, _leaf):
        names = {getattr(k, "key", getattr(k, "name", None)) for k in path}
        if names & {"mlp"} and names & EXPERT_LEAVES:
            return P("expert")
        return P()

    pspec = jax.tree_util.tree_map_with_path(spec_of, params)

    def step(p, ids, targets):
        def loss_fn(p):
            logits, _ = apply_fn(p, buffers, ids, rng=None, training=True)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return -jnp.mean(ll) + 0.01 * model.l_aux

        loss, grads = jax.value_and_grad(loss_fn)(p)
        loss = lax.pmean(loss, ("data", "seq", "expert"))
        # expert-sharded leaves average over the axes their tokens came
        # from, never over 'expert' itself
        grads = jax.tree.map(
            lambda g, s: lax.pmean(
                g, ("data", "seq") if s == P("expert")
                else ("data", "seq", "expert")),
            grads, pspec)
        return loss, jax.tree.map(lambda a, g: a - 0.1 * g, p, grads)

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspec, P(("data", "expert"), "seq"),
                  P(("data", "expert"), "seq")),
        out_specs=(P(), pspec), check_vma=False))

    dsh = NamedSharding(mesh, P(("data", "expert"), "seq"))
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
    if abstract_args:
        params = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            params, psh)
        ids = jax.ShapeDtypeStruct((2 * dp * ep, seq_len), jnp.int32,
                                   sharding=dsh)
        return fn, (params, ids, ids)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (2 * dp * ep, seq_len)).astype(np.int32)
    targets = np.roll(ids, -1, axis=1).astype(np.int32)
    params = jax.device_put(params, psh)
    ids = jax.device_put(ids, dsh)
    targets = jax.device_put(targets, dsh)
    return fn, (params, ids, targets)


def _serving_model(batch, vocab, embed_dim, layers, heads, kv_heads,
                   max_len, dtype):
    """Shared serving-program setup: the LM in eval mode with
    dtype-cast params, plus abstract (params, buffers, caches)."""
    from bigdl_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab, embed_dim=embed_dim, num_heads=heads,
                          num_kv_heads=kv_heads, num_layers=layers,
                          max_len=max_len, use_rope=True)
    model.evaluate()
    params = jax.tree.map(
        lambda a: (a.astype(dtype)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        model.params_dict())
    caches = _abstract(model.init_cache(batch, max_len, dtype=dtype))
    return (model, _abstract(params), _abstract(model.buffers_dict()),
            caches)


def decode_step_program(batch: int = 8, vocab: int = 32000,
                        embed_dim: int = 512, layers: int = 8, heads: int = 8,
                        kv_heads: int = 2, max_len: int = 2048,
                        dtype=jnp.bfloat16):
    """The serving flagship: one KV-cache decode step (GQA, RoPE, bf16
    cache) — the program run per generated token."""
    from bigdl_tpu.nn.module import bind

    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)

    def step(p, bufs, ids_t, pos, caches):
        with bind(model, p, bufs, False, None):
            return model.decode_step(ids_t, pos, caches)

    ids_t = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (jax.jit(step, donate_argnums=(4,)),
            (params, buffers, ids_t, pos, caches))


def decode_scan_program(batch: int = 8, n_tokens: int = 32,
                        vocab: int = 32000, embed_dim: int = 512,
                        layers: int = 8, heads: int = 8,
                        kv_heads: int = 2, max_len: int = 2048,
                        dtype=jnp.bfloat16):
    """The one-dispatch serving loop: n_tokens of sample->decode_step as a
    single on-device ``lax.scan`` (TransformerLM.decode_scan) — what
    generate() actually runs per batch, so its TPU lowering is the one
    that matters for serving."""
    from bigdl_tpu.nn.module import bind

    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)

    def scan_fn(p, bufs, logits, pos0, caches, rng):
        with bind(model, p, bufs, False, None):
            # eos + nucleus filtering included so the lowered module
            # carries the cond-skip and the per-step vocab sort too
            return model.decode_scan(logits, pos0, caches, rng,
                                     jnp.float32(0.8), n_tokens,
                                     sampled=True, eos_id=2, top_p=0.95)

    logits = jax.ShapeDtypeStruct((batch, vocab), dtype)
    pos0 = jax.ShapeDtypeStruct((), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return (jax.jit(scan_fn, donate_argnums=(2, 4)),
            (params, buffers, logits, pos0, caches, rng))


def sharded_decode_scan_program(n_devices: int = 8, batch: int = 4,
                                n_tokens: int = 16, vocab: int = 32000,
                                embed_dim: int = 512, layers: int = 8,
                                heads: int = 8, kv_heads: int = 2,
                                max_len: int = 2048, dtype=jnp.bfloat16):
    """The long-context serving lowering: the one-dispatch greedy decode
    loop with the KV caches SHARDED along T over the mesh (params
    replicated) — generate(kv_cache_sharding=...)'s program. GSPMD
    partitions the per-step attention + softmax reductions across
    devices (flash-decoding style)."""
    from bigdl_tpu.nn.module import bind
    from bigdl_tpu.parallel import Engine

    mesh = Engine.create_mesh([("seq", n_devices)])
    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)
    rep = NamedSharding(mesh, P())

    def reshard(tree, sh):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree)

    params, buffers = reshard(params, rep), reshard(buffers, rep)
    caches = reshard(caches, NamedSharding(mesh, P(None, None, "seq",
                                                   None)))

    def scan_fn(p, bufs, logits, pos0, caches, rng):
        with bind(model, p, bufs, False, None):
            return model.decode_scan(logits, pos0, caches, rng,
                                     jnp.float32(1.0), n_tokens,
                                     sampled=False, eos_id=2)

    logits = jax.ShapeDtypeStruct((batch, vocab), dtype, sharding=rep)
    pos0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    return (jax.jit(scan_fn, donate_argnums=(4,)),
            (params, buffers, logits, pos0, caches, rng))


def ragged_decode_program(batch: int = 8, n_tokens: int = 32,
                          vocab: int = 32000, embed_dim: int = 512,
                          layers: int = 8, heads: int = 8,
                          kv_heads: int = 2, max_len: int = 2048,
                          dtype=jnp.bfloat16):
    """The ragged serving program (generate_ragged / GenerationService):
    per-row last-valid prefill + the decode scan carrying a (B,) per-row
    position vector — per-row cache writes, masks, and RoPE."""
    from bigdl_tpu.nn.module import bind

    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)

    def ragged(p, bufs, ids, lengths, caches, rng):
        with bind(model, p, bufs, False, None):
            logits, caches = model._prefill_impl(
                ids, caches, 0, chunked=False, gather_last=lengths - 1)
            return model.decode_scan(logits, lengths, caches, rng,
                                     jnp.float32(0.8), n_tokens,
                                     sampled=True, eos_id=2, top_p=0.95)

    tmax = max_len - n_tokens
    ids = jax.ShapeDtypeStruct((batch, tmax), jnp.int32)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return (jax.jit(ragged, donate_argnums=(4,)),
            (params, buffers, ids, lengths, caches, rng))


def beam_scan_program(batch: int = 4, beams: int = 4, n_tokens: int = 32,
                      vocab: int = 32000, embed_dim: int = 512,
                      layers: int = 8, heads: int = 8, kv_heads: int = 2,
                      max_len: int = 2048, dtype=jnp.bfloat16):
    """The one-dispatch scanned beam search (select->step scan +
    parent-pointer backtracking, TransformerLM._beam_scan_fn's program)
    — beam serving's TPU lowering."""
    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)
    inner = model._beam_scan_closure(batch, beams, n_tokens, eos_id=2)

    logits = jax.ShapeDtypeStruct((batch, vocab), dtype)
    pos0 = jax.ShapeDtypeStruct((), jnp.int32)
    lp = jax.ShapeDtypeStruct((), jnp.float32)
    return (jax.jit(inner, donate_argnums=(4,)),
            (params, buffers, logits, pos0, caches, lp))


def chunked_prefill_program(batch: int = 8, chunk: int = 256,
                            vocab: int = 32000, embed_dim: int = 512,
                            layers: int = 8, heads: int = 8,
                            kv_heads: int = 2, max_len: int = 2048,
                            dtype=jnp.bfloat16):
    """One traced-offset prefill chunk (generate(prefill_chunk=...)) —
    the long-prompt serving path: fixed chunk length, full-cache masked
    attention, one compilation for every offset."""
    from bigdl_tpu.nn.module import bind

    model, params, buffers, caches = _serving_model(
        batch, vocab, embed_dim, layers, heads, kv_heads, max_len, dtype)

    def chunk_fn(p, bufs, ids, caches, pos0):
        with bind(model, p, bufs, False, None):
            return model.prefill_chunk(ids, caches, pos0)

    ids = jax.ShapeDtypeStruct((batch, chunk), jnp.int32)
    pos0 = jax.ShapeDtypeStruct((), jnp.int32)
    return (jax.jit(chunk_fn, donate_argnums=(3,)),
            (params, buffers, ids, caches, pos0))


def combined_3d_flash_program(n_devices: int = 8, t_per_shard: int = 256,
                              embed_dim: int = 256):
    """The combined dp x sp x ep step at FLASH-ELIGIBLE shapes: per-shard
    sequence tiles into the pallas kernel's auto blocks, so the exported
    module carries the Mosaic kernel INSIDE the full composed program
    (ring + MoE + RoPE + GQA), unlike the tiny-shape dryrun variant whose
    ring falls back to the dense path. (One parameterization of
    combined_3d_program — the expert-gradient reduction rule lives in
    exactly one place.)"""
    return combined_3d_program(n_devices, t_per_shard=t_per_shard,
                               embed_dim=embed_dim, vocab=128,
                               use_flash=True, abstract_args=True)


def export_for_tpu(fn, args):
    """jax.export the program for platforms=["tpu"]; returns the Exported.
    Tracing runs under ``force_interpret(False)`` so every flash call
    site (including ones buried inside full models, whose interpret
    default follows the HOST platform) lowers the real Mosaic kernel."""
    from jax import export

    from bigdl_tpu.ops.flash_attention import force_interpret

    with force_interpret(False):
        return export.export(fn, platforms=["tpu"])(*args)


def lower_for_tpu(fn, args) -> str:
    """``fn.lower(*args).as_text()`` for the TPU from any host, Mosaic
    kernels compiled in (no debug locations in the StableHLO; a kernel's
    payload carries its own: ``scripts/mosaic_program_hash.py``)."""
    from bigdl_tpu.ops.flash_attention import force_interpret

    with force_interpret(False):
        return fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

