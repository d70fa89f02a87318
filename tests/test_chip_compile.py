"""The main path's kernels and step programs COMPILE for the chip — checked
here, without one, by handing shapes on a *described* ``v5e:2x2`` topology to
the TPU compiler that ships with jaxlib (on-chip-measurement guide, section
2). A pass is not a chip run: nothing executes, so it says nothing about
results or times. It catches what interpret mode and ``jax.export`` lowering
cannot: a kernel the Mosaic compiler refuses (tiling, fast memory), a program
that does not fit 16 GB, a sharded step that cannot be partitioned.

Rules this file keeps (the suite runs under pytest-xdist, each worker imports
every test file, and only one process at a time may load the TPU library):
the topology is described ONLY inside the module-scoped ``topo`` fixture —
never at import, never in ``skipif``/``parametrize`` arguments, never in
conftest.py — everything compiles in the test's own process, the persistent
compile cache is off around these tests (a described-device executable
cannot be read back), and all such tests live in this one file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# GPT-2 Large's published widths (the chip_smoke server model); depth is
# cut to 2 layers here — tiling and partitioning depend on widths only
LM = dict(vocab_size=50304, embed_dim=1280, num_heads=20, num_layers=2,
          max_len=1024)
SERVE = dict(max_slots=8, prefill_chunk=128, prefill_rows=2, page_size=16)
FLASH = dict(batch=1, heads=8, kv_heads=2, seq=4096)   # GQA 4:1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _serving_engine(name, **kw):
    """The paged engine over the 2-layer GPT-2-Large-width model in
    bfloat16, built on the CPU."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import ContinuousBatchingEngine

    model = TransformerLM(**LM)
    model.evaluate()
    model.load_params_dict(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.params_dict()))
    return ContinuousBatchingEngine(model, service_name=name, **SERVE, **kw)


@pytest.fixture(scope="module")
def engine():
    """The source of the engine's own jitted programs."""
    eng = _serving_engine("chip_compile")
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def kernel_engine():
    """The same engine as it is built on one TPU chip: told, in this test,
    that its backend is a TPU (the CPU is what ``jax.default_backend()``
    sees here), it takes the paged-attention kernel for its decode step."""
    from unittest import mock

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        eng = _serving_engine("chip_compile_kernel")
    assert eng.stats()["paging"]["decode_attention"] == "kernel"
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def mesh_engine():
    """The same under the engine's tensor-parallel mode, on four of the
    CPU's devices: the source of the step the MESH engine builds (it keeps
    the per-head decode attention; PERF.md, PR 30)."""
    eng = _serving_engine(
        "chip_compile_tp",
        mesh=Mesh(np.asarray(jax.devices()[:4]), ("model",)))
    yield eng
    eng.stop()


def _abstract(tree, sharding, lead=None):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape if lead is None else (lead,) + a.shape[1:],
            a.dtype, sharding=sharding), tree)


def _engine_args(eng, params, repl, kv, pages=2048):
    """Abstract arguments of the engine's paged programs at the smoke's
    pool geometry, replicated inputs on ``repl``, the pool on ``kv``."""
    S, rows = SERVE["max_slots"], SERVE["prefill_rows"]
    c, T = SERVE["prefill_chunk"], eng._table_len

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=repl)

    pool = _abstract(eng._kv_pool, kv, lead=pages)
    bufs = _abstract(eng._buffers, repl)
    key = _abstract(jax.random.PRNGKey(0), repl)
    t1 = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
    logits = jax.ShapeDtypeStruct((rows, LM["vocab_size"]), jnp.float32,
                                  sharding=repl)
    return {
        "step": (params, bufs, i32(S), i32(S), pool, i32(S, T), key, t1),
        "chunk": (params, bufs, i32(rows, c), pool, i32(rows, T),
                  i32(rows), i32(rows)),
        "copy_page": (pool, i32(), i32()),
        "sample0": (logits, key, t1),
    }


def _fits(compiled, limit=16 << 30):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < limit, m
    return m


# ------------------------------------------------------------ flash kernel
def _flash_case(head_dim, one_chip, grad):
    from bigdl_tpu.ops.flash_attention import flash_attention, force_interpret

    def sds(heads):
        return jax.ShapeDtypeStruct(
            (FLASH["batch"], heads, FLASH["seq"], head_dim), jnp.bfloat16,
            sharding=one_chip)

    q, kv = sds(FLASH["heads"]), sds(FLASH["kv_heads"])

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                        (0, 1, 2))(q, k, v)

    # jax.devices() is the CPU here, so the kernel's own default would be
    # the interpreter: steer it to the compiled Mosaic path in the test
    with force_interpret(False):
        compiled = jax.jit(bwd if grad else fwd).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_forward_compiles(topo, one_chip, head_dim):
    _flash_case(head_dim, one_chip, grad=False)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_grad_compiles(topo, one_chip, head_dim):
    _flash_case(head_dim, one_chip, grad=True)


# ------------------------------------------------ the paged engine's programs
@pytest.mark.parametrize("program", ["step", "chunk", "copy_page", "sample0"])
def test_paged_engine_program_compiles(topo, one_chip, engine, program):
    """decode step / prefill chunk / page copy / first-token sample at
    GPT-2 Large widths, 2048 pages x 16, 8 lanes, table length 64."""
    args = _engine_args(engine, _abstract(engine._params, one_chip),
                        one_chip, one_chip)[program]
    jitted = getattr(engine, f"_{program}_jit")
    compiled = jitted.lower(*args).compile()
    m = _fits(compiled)
    if program in ("step", "chunk", "copy_page"):
        # the pool is donated and aliased in full: no second copy of it
        leaves = jax.tree.leaves(args[{"step": 4, "chunk": 3,
                                       "copy_page": 0}[program]])
        pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                         for leaf in leaves)
        assert m.alias_size_in_bytes >= pool_bytes
        # ... and a page takes its logical bytes: (16, 1280) bf16 is whole
        # (8, 128)(2, 1) tiles. (With 64 as the minor dimension the runtime
        # stores PAGES minor-most and pads them to a multiple of 128.)
        assert m.alias_size_in_bytes == pool_bytes
    if program in ("step", "chunk"):
        # the KV write lands in place: no copy of a whole leaf around the
        # scatter. The runtime lays a (pages, 16, 1280) leaf out row-major,
        # pages and offsets leading as the scatter wants them; any 4-D leaf
        # with the 64-wide head minor gets pages minor-most from it and is
        # re-laid twice a leaf a dispatch (PERF.md, PR 27).
        text = compiled.as_text()
        dims = ",".join(str(d) for d in leaves[0].shape)
        assert f"bf16[{dims}]{{2,1,0:" in text
        assert "scatter(" in text
        assert not re.findall(rf"= bf16\[{dims}\]\S* (?:copy|transpose)\(",
                              text)
    if program == "step":
        # one decode token a row contracts a block-diagonal q with the
        # gathered K and V left as rows of 1280: nothing of
        # (lanes, 1024, 20, 64) is ever made. Splitting the minor 1280
        # into (20, 64) re-laid both gathered views into tiles padded
        # 2.4x, 48 of the step's 112 ms on the chip (PERF.md, PR 30).
        assert engine.stats()["paging"]["decode_attention"] == "rows"
        split = re.findall(
            rf"= \w+\[{SERVE['max_slots']},1024,20,64\]\S* \w", text)
        assert not split, split[:4]
        parent = 65_462_784    # PR 27's step, same compiler and geometry
        assert m.temp_size_in_bytes < parent, (
            f"the step's temporaries: {m.temp_size_in_bytes} bytes, the "
            f"parent's (per-head einsums over the gathered pages) {parent}")


# ------------------------------------------- the hybrid decoder's programs
HYBRID = dict(slots=16, rows=2, chunk=256, page=16, ctx=4096, heads=30,
              embed=3840)


def _hybrid_period(sharding, decode_attention="rows"):
    """One period of the hybrid decoder (3 gated delta-rule layers and a
    full-attention layer) at its published widths, built as shapes:
    ``{"step": (fn, args, donated), "chunk": ...}`` at the cell's geometry,
    16 lanes and a 2 x 256 chunk over tables of 256 pages of 16."""
    from bigdl_tpu.models.hybrid import HybridDecoderLM
    from bigdl_tpu.nn.module import abstract_init, bind

    kinds = ("linear_attention",) * 3 + ("full_attention",)
    slots, rows, chunk, page, ctx = (HYBRID[k] for k in (
        "slots", "rows", "chunk", "page", "ctx"))
    model = abstract_init(lambda: HybridDecoderLM(
        100352, HYBRID["embed"], HYBRID["heads"], kinds, 11008, ctx,
        num_kv_heads=30, linear_heads=30, linear_key_dim=96,
        linear_value_dim=192))
    model.evaluate()
    sd = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=sharding)
    params = jax.tree.map(lambda a: sd(a, jnp.bfloat16), model.params_dict())
    pool = jax.tree.map(sd, jax.eval_shape(lambda: model.init_page_pool(
        1 + slots * ctx // page, page, dtype=jnp.bfloat16, lanes=slots + 1)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)

    def step(p, tok, pos, pool, tables, active):
        with bind(model, p, {}, False, None):
            logits, pool = model.decode_step_paged(
                tok, pos, pool, tables, active=active,
                decode_attention=decode_attention)
        return jnp.argmax(logits, -1), pool

    def prefill(p, ids, pool, tables, pos0, last, lanes):
        with bind(model, p, {}, False, None):
            return model.prefill_chunk_at_paged(ids, pool, tables, pos0,
                                                last, lanes=lanes)

    return {
        "step": (step, (params, i32(slots), i32(slots), pool,
                        i32(slots, ctx // page), jax.ShapeDtypeStruct(
                            (slots,), bool, sharding=sharding)), 3),
        "chunk": (prefill, (params, i32(rows, chunk), pool,
                            i32(rows, ctx // page), i32(rows), i32(rows),
                            i32(rows)), 2),
    }


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_hybrid_decoder_program_compiles_and_holds_its_pool_in_place(
        topo, one_chip, program):
    """One period of the hybrid decoder at its published widths: the
    decode step over 16 lanes and the 2 x 256 prefill chunk compile for the
    chip, the pool (pages AND lane state) is donated and aliased in full,
    pages take their logical bytes, and the lane state takes 4/3 of its
    (the minor 192 of S pads to 256 lanes of the tile): the factor the
    benchmark's adapter budgets with."""
    from benchmark.models import olmo_hybrid as adapter

    fn, args, donated = _hybrid_period(one_chip)[program]
    compiled = jax.jit(fn, donate_argnums=(donated,)).lower(*args).compile()
    m = _fits(compiled)
    pool = args[donated]
    size = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    pages, lanes = size(pool["pages"]), size(pool["lanes"])
    assert m.alias_size_in_bytes >= pages + lanes
    padded = m.alias_size_in_bytes - pages
    assert abs(padded / lanes - adapter.LANE_DEVICE_FACTOR) < 0.01, (
        padded, lanes)


@pytest.mark.parametrize("config", ["gpt2-large", "olmo-hybrid-7b"])
def test_chunk_attends_by_key_blocks_and_makes_no_whole_table_view(
        engine, config):
    """The prefill chunk's full-attention layers walk the rows' tables by
    key blocks (PERF.md, PR 38): lowered at the cell's chunk geometry the
    program has a ``while`` for the rounds and makes neither the scores over
    a whole table, (rows, heads, T, table_len * page_size), nor a row's
    whole gathered table, (rows, table_len, page_size, H * D): the dense
    form's 252 MB of float32 scores and 63 MB views a layer at the hybrid's
    widths. The property by shape, nothing compiled."""
    if config == "gpt2-large":
        lowered = engine._chunk_jit.lower(*_engine_args(
            engine, _abstract(engine._params, None), None, None)["chunk"])
        rows, t = SERVE["prefill_rows"], SERVE["prefill_chunk"]
        page, table, width = (SERVE["page_size"], engine._table_len,
                              LM["embed_dim"])
    else:
        fn, args, _ = _hybrid_period(None)["chunk"]
        lowered = jax.jit(fn).lower(*args)
        rows, t, page, width = (HYBRID["rows"], HYBRID["chunk"],
                                HYBRID["page"], HYBRID["embed"])
        table = HYBRID["ctx"] // page
    text = lowered.as_text()
    assert "stablehlo.while" in text
    scores = re.findall(rf"tensor<{rows}x[0-9x]*x{t}x{table * page}x\w+>",
                        text)
    views = re.findall(rf"tensor<{rows}x{table}x{page}x{width}x\w+>", text)
    assert not scores and not views, (scores[:2], views[:2])
    # the rounds are narrower than the table, and the blocks are there
    from bigdl_tpu.nn.attention import _key_block_pages
    kp = _key_block_pages(page, table)
    assert kp < table
    assert f"tensor<{rows}x{kp}x{page}x{width}x" in text


@pytest.mark.parametrize("config", ["gpt2-large", "olmo-hybrid-7b"])
def test_kernel_decode_step_compiles_and_reads_its_pool_in_place(
        topo, one_chip, kernel_engine, config):
    """The decode step as an engine builds it on one TPU chip, at both
    cells' widths (the engine's own program over 8 lanes of GPT-2 Large's
    1280 columns; one period of the hybrid decoder over 16 lanes of 3840):
    it compiles for the chip with the Mosaic paged-attention kernel in it,
    the pool is donated, aliased in full and takes its logical bytes, the
    write is still a scatter in place, no leaf is copied or transposed on
    its way into the custom call, and nothing of a lane's whole table is
    gathered: no (lanes, table_len, page_size, H * D) view, merged or
    not (the decode twin of
    ``test_chunk_attends_by_key_blocks_and_makes_no_whole_table_view``)."""
    from bigdl_tpu.ops.flash_attention import force_interpret

    if config == "gpt2-large":
        args = _engine_args(kernel_engine,
                            _abstract(kernel_engine._params, one_chip),
                            one_chip, one_chip)["step"]
        jitted, pool = kernel_engine._step_jit, args[4]
        lanes, table = SERVE["max_slots"], kernel_engine._table_len
        page, width = SERVE["page_size"], LM["embed_dim"]
    else:
        fn, args, donated = _hybrid_period(one_chip, "kernel")["step"]
        jitted, pool = jax.jit(fn, donate_argnums=(donated,)), args[donated]
        lanes, page, width = HYBRID["slots"], HYBRID["page"], HYBRID["embed"]
        table = HYBRID["ctx"] // page
    # jax.devices() is the CPU here: steer the kernel to Mosaic in the test
    with force_interpret(False):
        compiled = jitted.lower(*args).compile()
    m = _fits(compiled)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    leaves = jax.tree.leaves(pool["pages"] if isinstance(pool, dict)
                             else pool)
    pool_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                     for leaf in leaves)
    if isinstance(pool, dict):
        assert m.alias_size_in_bytes >= pool_bytes      # + the lanes' state
    else:
        assert m.alias_size_in_bytes == pool_bytes
    dims = ",".join(str(d) for d in leaves[0].shape)
    assert f"bf16[{dims}]{{2,1,0:" in text
    assert "scatter(" in text
    assert not re.findall(rf"= bf16\[{dims}\]\S* (?:copy|transpose)\(", text)
    views = re.findall(
        rf"= \w+\[{lanes},(?:{table},{page}|{table * page}),{width}\]", text)
    assert not views, views[:4]
    gathers = re.findall(rf"= bf16\[{lanes * table},{page},{width}\]", text)
    assert not gathers, gathers[:4]


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_sala_program_compiles_and_holds_its_pool_in_place(
        topo, one_chip, program):
    """One period of the MiniCPM-SALA cut (a block-sparse layer and three
    lightning layers) at its published widths and the served 32768
    positions, built as shapes from the benchmark's configuration: the
    decode step over 16 lanes (each gathering 128 blocks' pages of the 512 a
    lane may hold) and the 2 x 256 prefill chunk (a lane's pages by key
    blocks) compile for the chip, the pool (K, V, compressed keys AND lane
    state) is donated and aliased in full, pages and lanes take their
    logical bytes (the factor the adapter budgets with), and the step's
    temporaries for one period stay under a quarter of ``reserve_bytes``."""
    import dataclasses

    from benchmark import harness
    from benchmark.models import minicpm_sala as adapter
    from bigdl_tpu.nn.module import bind

    cfg = harness.load_json(harness.HERE, "configs", "minicpm-sala.json")
    cfg["sizes"] = dict(cfg["sizes"], num_hidden_layers=4,
                        layers_held=[9, 13])          # sparse + 3 lightning
    e = cfg["engine"]
    slots, rows, chunk, page = (e["max_slots"], e["prefill_rows"],
                                e["prefill_chunk"], e["page_size"])
    ctx = cfg["sizes"]["max_position_embeddings"]
    model = adapter.model_shapes(cfg)
    assert [b.kind for b in model._blocks()] == [
        "sparse_attention"] + ["lightning_attention"] * 3
    sd = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=one_chip)
    params = jax.tree.map(lambda a: sd(a, jnp.bfloat16), model.params_dict())
    pool = jax.tree.map(sd, jax.eval_shape(lambda: model.init_page_pool(
        1 + slots * ctx // page, page, dtype=jnp.bfloat16, lanes=slots + 1)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)

    def step(p, tok, pos, pool, tables, active):
        with bind(model, p, {}, False, None):
            logits, pool = model.decode_step_paged(
                tok, pos, pool, tables, active=active)
        return jnp.argmax(logits, -1), pool

    def prefill(p, ids, pool, tables, pos0, last, lanes):
        with bind(model, p, {}, False, None):
            return model.prefill_chunk_at_paged(ids, pool, tables, pos0,
                                                last, lanes=lanes)

    if program == "step":
        compiled = jax.jit(step, donate_argnums=(3,)).lower(
            params, i32(slots), i32(slots), pool, i32(slots, ctx // page),
            jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip)).compile()
    else:
        compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(rows, chunk), pool, i32(rows, ctx // page), i32(rows),
            i32(rows), i32(rows)).compile()
    m = _fits(compiled)
    size = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    pages, lanes = size(pool["pages"]), size(pool["lanes"])
    assert pages == (1 + slots * ctx // page) * adapter.cache_geometry(
        cfg)["page_device_bytes"]          # one sparse layer's
    assert lanes == (slots + 1) * adapter.lane_state_bytes(cfg)
    assert m.alias_size_in_bytes >= pages + lanes
    padded = m.alias_size_in_bytes - pages
    assert abs(padded / lanes - adapter.LANE_DEVICE_FACTOR) < 0.01, (
        padded, lanes)
    print(program, dataclasses.asdict(m) if dataclasses.is_dataclass(m) else m)
    # the programs' scratch: what reserve_bytes (1 GiB) is set from; the
    # whole 16 layers read 0.16 GB (step) and 0.28 GB (chunk) the same way
    assert m.temp_size_in_bytes < e["reserve_bytes"] // 4, m


@pytest.mark.parametrize("program", ["step", "chunk", "kernel_step"])
def test_latent_and_routed_programs_compile_and_hold_their_pool_in_place(
        topo, one_chip, program):
    """The first two layers of the latent-attention decoder with routed
    experts (a dense layer and a routed one holding 32 of 256 experts) at
    its published widths and its cell's geometry, built as shapes from the
    benchmark's configuration: the absorbed decode step over 32 lanes of
    16384 positions and the expanded 2 x 256 prefill chunk compile for the
    chip, and the latent leaves are donated and aliased in full at the
    bytes the adapter budgets a page with (rows of 640: whole lanes). A
    leaf whose rows are the 576 elements alone compiles to a copy of every
    layer's whole leaf in the chunk and does not fit (PERF.md, PR 48).
    ``kernel_step``: the step as an engine builds it on one TPU chip, the
    Mosaic kernel reading the leaves where they lie: no leaf is copied or
    transposed on its way into the custom call, no lane's table is gathered
    (the rows form's 0.67 GB a layer), and its scratch is small."""
    from bigdl_tpu.ops.flash_attention import force_interpret
    from benchmark import harness
    from benchmark.models import joyai_llm_flash as adapter
    from bigdl_tpu.nn.module import bind

    cfg = harness.load_json(harness.HERE, "configs", "joyai-llm-flash.json")
    cfg["sizes"] = dict(cfg["sizes"], num_hidden_layers=2, layers_held=[0, 2])
    e = cfg["engine"]
    slots, rows, chunk, page = (e["max_slots"], e["prefill_rows"],
                                e["prefill_chunk"], e["page_size"])
    ctx = cfg["sizes"]["max_position_embeddings"]
    model = adapter.model_shapes(cfg)
    assert [(b.kind, b.routed) for b in model._blocks()] == [
        ("latent_attention", False), ("latent_attention", True)]

    def shaped(path, a):
        name = jax.tree_util.keystr(path)
        wide = "router" in name or "select_bias" in name
        return jax.ShapeDtypeStruct(
            a.shape, jnp.float32 if wide else jnp.bfloat16, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(shaped, model.params_dict())
    n_pages = 1 + slots * ctx // page
    pool = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_page_pool(n_pages, page,
                                                    dtype=jnp.bfloat16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)

    attend = "kernel" if program == "kernel_step" else "rows"

    def step(p, tok, pos, pool, tables, active):
        with bind(model, p, {}, False, None):
            logits, pool, counts = model.decode_step_paged(
                tok, pos, pool, tables, active=active, routing=True,
                decode_attention=attend)
        return jnp.concatenate([jnp.argmax(logits, -1).astype(jnp.int32),
                                counts]), pool

    def prefill(p, ids, pool, tables, pos0, last):
        with bind(model, p, {}, False, None):
            return model.prefill_chunk_at_paged(ids, pool, tables, pos0, last)

    if program == "chunk":
        compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, i32(rows, chunk), pool, i32(rows, ctx // page), i32(rows),
            i32(rows)).compile()
    else:
        # jax.devices() is the CPU here: steer the kernel to Mosaic
        with force_interpret(False):
            compiled = jax.jit(step, donate_argnums=(3,)).lower(
                params, i32(slots), i32(slots), pool,
                i32(slots, ctx // page), jax.ShapeDtypeStruct(
                    (slots,), bool, sharding=one_chip)).compile()
    m = _fits(compiled)
    pages = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(pool))
    assert pool["lanes"] == [] and pages == n_pages * adapter.cache_geometry(
        cfg)["page_device_bytes"]
    assert pages <= m.alias_size_in_bytes < 1.01 * pages
    # the gathered step's scratch is the rows of one layer (0.67 GB) and
    # little else; the chunk gathers a key block at a time, the kernel
    # nothing
    assert m.temp_size_in_bytes < e["reserve_bytes"] // (
        2 if program == "step" else 16), m
    text = compiled.as_text()
    width = model._blocks()[0].mixer.row_width
    gathers = re.findall(
        rf"= bf16\[{slots * ctx // page},{page},{width}\]", text)
    assert ("tpu_custom_call" in text) == (program == "kernel_step")
    assert bool(gathers) == (program == "step"), gathers[:4]
    if program == "kernel_step":
        assert "scatter(" in text
        # ... as it lies
        assert not re.findall(
            rf"= bf16\[{n_pages},{page},{width}\]\S* (?:copy|transpose)\(",
            text)


# ------------------------------------------------------- across four chips
def test_tensor_parallel_decode_step_compiles_on_four_chips(topo,
                                                            mesh_engine):
    """The mesh engine's decode step on a ("model", 4) mesh: 20 heads -> 5
    per chip, params under transformer_tp_rules, the pool heads-sharded."""
    engine = mesh_engine
    assert engine.stats()["paging"]["decode_attention"] == "heads"
    from bigdl_tpu.parallel.tp import spec_for_params, transformer_tp_rules

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    repl = NamedSharding(mesh, P())
    kv = engine.model.kv_page_pool_sharding(mesh)
    specs = spec_for_params(engine._params, transformer_tp_rules("model"),
                            P())

    def walk(p, s):
        if isinstance(p, dict):
            return {k: walk(v, s[k]) for k, v in p.items()}
        return jax.ShapeDtypeStruct(p.shape, p.dtype,
                                    sharding=NamedSharding(mesh, s))

    args = _engine_args(engine, walk(engine._params, specs), repl, kv)
    compiled = jax.jit(engine._step_jit.__wrapped__, donate_argnums=(4,),
                       out_shardings=(repl, kv)).lower(
                           *args["step"]).compile()
    text = compiled.as_text()
    # the row-parallel reductions and nothing more: the counts PR 27's
    # step compiles to at 2 layers. The rows form of the decode attention
    # contracts over the heads-sharded dimension and would add 2
    # all-reduces and an all-gather or two a layer (9 and 5 here).
    counts = {c: len(re.findall(rf" {c}(?:-start)?\(", text))
              for c in ("all-reduce", "all-gather")}
    assert counts == {"all-reduce": 5, "all-gather": 2}
    # per-device bytes: the sharded pool is a quarter of the whole
    m = _fits(compiled)
    whole = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(args["step"][4]))
    assert m.alias_size_in_bytes < whole // 2


def test_data_parallel_sharded_step_compiles_on_four_chips(topo):
    """DistriOptimizer's sharded (reduce-scatter / all-gather) step on a
    ("data", 4) mesh, from the optimizer's own builder."""
    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel import DistriOptimizer
    from bigdl_tpu.parallel.distri_optimizer import (flatten_params,
                                                     pad_to_multiple)

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    model = nn.Sequential(nn.Linear(784, 128), nn.ReLU(),
                          nn.Linear(128, 10), nn.LogSoftMax())
    opt = DistriOptimizer(model=model, dataset=None,
                          criterion=nn.ClassNLLCriterion(), batch_size=256,
                          mesh=mesh, parameter_sync="sharded")
    method = SGD(learning_rate=0.01)
    params = model.params_dict()
    flat, _ = pad_to_multiple(flatten_params(params)[0], 4)
    slots = method.init_slots(flat)
    step, _, _ = opt._build_sharded_step(
        model, nn.ClassNLLCriterion(), method, {}, slots)
    bufs = jax.tree.map(
        lambda b: jax.ShapeDtypeStruct((4,) + b.shape, b.dtype,
                                       sharding=data),
        model.buffers_dict())
    slots_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=data if getattr(s, "ndim", 0) else repl), slots)
    compiled = step.lower(
        _abstract(params, repl), bufs, _abstract(flat, data), slots_abs,
        jax.ShapeDtypeStruct((256, 784), jnp.float32, sharding=data),
        jax.ShapeDtypeStruct((256, 1), jnp.float32, sharding=data),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=repl),
        _abstract(jax.random.PRNGKey(0), repl)).compile()
    # the gradient exchange survives compilation as collectives (the
    # compiler may fuse the scatter/gather pair into all-reduces)
    text = compiled.as_text()
    assert any(c in text for c in ("all-reduce", "reduce-scatter",
                                   "all-gather"))
    _fits(compiled)


# ------------------------------------------------------ BatchNorm's passes
def _stage1_bottleneck(bn):
    """One identity bottleneck of ResNet-50's first stage (64/64/256
    channels over 56 x 56, NHWC) as ``models/resnet`` builds it, its
    BatchNorm from ``bn``."""
    from bigdl_tpu import nn

    def conv(n_in, n_out, k):
        return nn.SpatialConvolution(n_in, n_out, k, k, 1, 1, k // 2, k // 2,
                                     format="NHWC")

    main = nn.Sequential()
    for n_in, n_out, k in ((256, 64, 1), (64, 64, 3), (64, 256, 1)):
        main.add(conv(n_in, n_out, k)).add(bn(n_out, 1e-3, format="NHWC"))
        if n_out == 64:
            main.add(nn.ReLU())
    return (nn.Sequential()
            .add(nn.ConcatTable().add(main).add(nn.Identity()))
            .add(nn.CAddTable()).add(nn.ReLU())).training_mode()


def test_batchnorm_training_pass_moves_fewer_bytes(topo, one_chip):
    """Forward and backward of a first-stage bottleneck at the training
    cell's size (256 x 56 x 56, float32): with the one-read statistics and
    the two-pass backward of ``nn/normalization.py`` XLA counts at least a
    tenth fewer bytes than with BatchNorm as autodiff of ``jnp.mean`` and
    ``jnp.var`` (tests/two_read_batchnorm.py). The ResNet-50 step is bound
    by the bytes it moves (PERF.md, PR 32): an edit to the pass that reads
    the activation once more shows here, in seconds, without the network."""
    from bigdl_tpu import nn
    from bigdl_tpu.nn.module import pure_apply
    from two_read_batchnorm import TwoReadSpatialBatchNormalization

    x = jax.ShapeDtypeStruct((256, 56, 56, 256), jnp.float32,
                             sharding=one_chip)

    def bytes_accessed(bn):
        block = _stage1_bottleneck(bn)
        apply_fn = pure_apply(block)

        def loss(params, buffers, x):
            y, new_buffers = apply_fn(params, buffers, x, training=True)
            return jnp.mean(y * y), new_buffers

        compiled = jax.jit(jax.value_and_grad(
            loss, (0, 2), has_aux=True)).lower(
                _abstract(block.params_dict(), one_chip),
                _abstract(block.buffers_dict(), one_chip), x).compile()
        _fits(compiled)
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return cost["bytes accessed"]

    one_read = bytes_accessed(nn.SpatialBatchNormalization)
    two_read = bytes_accessed(TwoReadSpatialBatchNormalization)
    assert one_read <= 0.9 * two_read, (one_read, two_read)
