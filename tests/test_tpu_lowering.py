"""Offline TPU-lowering validation.

``jax.export(platforms=["tpu"])`` runs the full TPU lowering pipeline
from the CPU host — including Mosaic for the pallas flash kernel, whose
compiled payload lands in the module as a ``tpu_custom_call`` — so this
suite proves the production programs LOWER for TPU without any
hardware, so lowering breakage costs no chip time (compiling them for a
described chip is tests/test_chip_compile.py). Flagship-shape exports + artifact hashes: scripts/tpu_export.py
-> TPU_LOWERING.json."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.tools import export_programs as ep


def _export(fn, args):
    exported = ep.export_for_tpu(fn, args)
    assert exported.platforms == ("tpu",)
    assert len(exported.mlir_module_serialized) > 0
    return exported


def test_flash_attention_fwd_lowers_for_tpu_mosaic():
    """The shipped kernel (128x128 blocks, GQA index map, bf16, causal)
    must survive REAL Mosaic lowering — interpret=False — and the module
    must contain the Mosaic custom call, not an interpreter fallback."""
    fn, args = ep.flash_attention_program(t=512, grad=False)
    exported = _export(fn, args)
    assert "tpu_custom_call" in exported.mlir_module()


def test_flash_attention_grad_lowers_for_tpu():
    fn, args = ep.flash_attention_program(t=512, grad=True)
    exported = _export(fn, args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("form", ["kernel", "rows", "heads"])
def test_paged_decode_step_lowers_for_tpu(form):
    """The paged engine's decode step at GPT-2 Large's widths. As it is
    built for one TPU chip it carries the Mosaic paged-attention kernel
    (``ops/paged_attention.py``) and makes no gathered view of the
    lanes' tables, (lanes, table_len, page_size, H * D) or that with
    (table_len, page_size) merged; the two gathered forms, off a TPU
    and on a mesh, lower as before, with no kernel and with that
    view."""
    lanes, table_len = 8, 64
    fn, args = ep.paged_decode_step_program(lanes=lanes,
                                            decode_attention=form)
    mod = _export(fn, args).mlir_module()
    views = [f"tensor<{lanes}x{table_len}x16x1280xbf16>",
             f"tensor<{lanes}x{table_len * 16}x1280xbf16>"]
    if form == "kernel":
        assert "tpu_custom_call" in mod
        assert not any(v in mod for v in views)
        assert "stablehlo.scatter" in mod               # the write stays
    else:
        assert "tpu_custom_call" not in mod
        assert views[0] in mod


@pytest.mark.parametrize("shape", ["gpt2-large", "olmo-hybrid"])
def test_the_pairs_decode_step_keeps_its_own_kernel(shape):
    """The decode step of a model whose pools are K and V pairs, as an
    engine builds it on one TPU chip (``gpt2l-chat-steady`` and
    ``olmoh-docqa-steady`` run it): its one Mosaic kernel is
    ``paged_attention`` with rounds of 128 keys in two buffers a leaf, and
    nothing of the one-leaf form a latent layer's pool takes
    (``paged_latent_attention``, wider rounds) is in it. Whether a build
    lowers it to the program another build did is
    ``scripts/mosaic_program_hash.py``'s to say (the raw text carries
    source lines)."""
    if shape == "gpt2-large":
        fn, args = ep.paged_decode_step_program(lanes=8)
        cols = 1280
    else:
        fn, args = ep.hybrid_decode_step_program(lanes=4, ctx=512)
        cols = 3840
    text = ep.lower_for_tpu(fn, args)
    assert "latent" not in text
    # one trace and one kernel for every layer of the model
    assert re.findall(r'kernel_name = "([^"]*)"', text) == ["paged_attention"]
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    # the kernel's own module, through jax's private MLIR bindings: a jax
    # bump that moves them is no fault of the pair's program
    spec = importlib.util.spec_from_file_location(
        "mosaic_program_hash", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "mosaic_program_hash.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    try:
        (_, module), = tool.mosaic_kernels(text)[1]
    except Exception as e:      # noqa: BLE001 (whatever the bindings raise)
        pytest.skip(f"the Mosaic payload could not be parsed: {e!r}")
    assert "loc(" not in module
    buffers = set(re.findall(r"memref<2x(\d+)x%dxbf16" % cols, module))
    assert buffers == {"128"}, buffers


def test_ring_flash_composed_lowers_for_tpu():
    """Ring attention (ppermute over 'seq') composed with the Mosaic
    flash kernel, with gradients through the custom vjp, on the 8-way
    ('data','seq') mesh."""
    fn, args = ep.ring_flash_program(n_devices=8, t_per_shard=128)
    exported = _export(fn, args)
    assert exported.nr_devices == 8
    mod = exported.mlir_module()
    assert "tpu_custom_call" in mod
    assert "collective_permute" in mod  # the ring's ppermute


def test_distri_sharded_train_step_lowers_for_tpu():
    """The production ZeRO-1 sharded DistriOptimizer step (reduce-scatter
    bf16 wire, per-shard update, all-gather, donation) exports for TPU
    over the 8-device mesh."""
    fn, args = ep.distri_sharded_step_program("lenet5", n_devices=8,
                                              global_batch=32)
    exported = _export(fn, args)
    assert exported.nr_devices == 8


def test_combined_3d_step_lowers_for_tpu():
    """The driver-dryrun composed dp x sp x ep program (RoPE + GQA +
    ring attention + MoE all_to_all) exports for TPU — the same fn the
    dryrun executes (shared builder)."""
    fn, args = ep.combined_3d_program(n_devices=8)
    exported = _export(fn, args)
    assert exported.nr_devices == 8


def test_decode_step_lowers_for_tpu():
    """The serving flagship: one KV-cache decode step (GQA + RoPE, bf16
    cache) cross-lowers for TPU."""
    fn, args = ep.decode_step_program(batch=2, vocab=256, embed_dim=64,
                                      layers=2, heads=4, kv_heads=2,
                                      max_len=128)
    _export(fn, args)


def test_decode_scan_lowers_for_tpu():
    """The one-dispatch n-token decode loop (lax.scan over the KV cache,
    tempered sampling inside) — what generate() actually runs —
    cross-lowers for TPU."""
    fn, args = ep.decode_scan_program(batch=2, n_tokens=8, vocab=256,
                                      embed_dim=64, layers=2, heads=4,
                                      kv_heads=2, max_len=128)
    _export(fn, args)


def test_sharded_decode_scan_lowers_for_tpu():
    """The sequence-sharded KV-cache decode loop (long-context serving,
    generate(kv_cache_sharding=...)'s program) cross-lowers for TPU as
    an 8-device module."""
    fn, args = ep.sharded_decode_scan_program(
        n_devices=8, batch=2, n_tokens=4, vocab=64, embed_dim=32,
        layers=1, heads=4, kv_heads=2, max_len=64)
    exported = _export(fn, args)
    assert exported.nr_devices == 8


def test_ragged_decode_lowers_for_tpu():
    """The ragged serving program (per-row last-valid prefill + the
    decode scan over a (B,) position vector) cross-lowers for TPU."""
    fn, args = ep.ragged_decode_program(batch=2, n_tokens=4, vocab=64,
                                        embed_dim=32, layers=1, heads=4,
                                        kv_heads=2, max_len=32)
    _export(fn, args)


def test_beam_scan_lowers_for_tpu():
    """The one-dispatch scanned beam search (top-k reselection + cache
    lineage gathers + parent-pointer backtracking inside one scan)
    cross-lowers for TPU."""
    fn, args = ep.beam_scan_program(batch=2, beams=3, n_tokens=6,
                                    vocab=64, embed_dim=32, layers=1,
                                    heads=4, kv_heads=2, max_len=32)
    _export(fn, args)


def test_chunked_prefill_lowers_for_tpu():
    """The traced-offset prefill chunk (long-prompt serving path)
    cross-lowers for TPU."""
    fn, args = ep.chunked_prefill_program(batch=2, chunk=32, vocab=256,
                                          embed_dim=64, layers=2, heads=4,
                                          kv_heads=2, max_len=128)
    _export(fn, args)


def test_combined_3d_flash_lowers_with_mosaic_kernel():
    """At flash-eligible shapes the FULL composed program (ring + MoE +
    RoPE + GQA train step) must carry the Mosaic kernel inside the
    exported module — force_interpret(False) reaches flash call sites
    buried in the model."""
    fn, args = ep.combined_3d_flash_program(n_devices=8, t_per_shard=128,
                                            embed_dim=64)
    exported = _export(fn, args)
    assert exported.nr_devices == 8
    mod = exported.mlir_module()
    assert "tpu_custom_call" in mod
    assert "collective_permute" in mod


@pytest.mark.slow
def test_resnet50_sharded_step_lowers_for_tpu():
    """Flagship: the full ResNet-50 NHWC sharded train step (bench
    config) cross-lowers for TPU. Slow (~minutes of XLA lowering);
    scripts/tpu_export.py records its artifact hash."""
    fn, args = ep.distri_sharded_step_program("resnet50", n_devices=8,
                                              global_batch=32,
                                              format="NHWC")
    exported = _export(fn, args)
    assert exported.nr_devices == 8
